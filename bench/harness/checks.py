"""The numbers that decide ``correct``, each beside its limit."""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def rel_err(got, want) -> np.ndarray:
    """Elementwise ``|got - want| / |want|``; 0 where both are 0."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    both_zero = (got == 0) & (want == 0)
    denom = np.where(want == 0, 1.0, np.abs(want))
    return np.where(both_zero, 0.0, np.abs(got - want) / denom)


def covered(x: np.ndarray, others: np.ndarray, tol: np.ndarray) -> bool:
    """Some row of ``others`` matches or beats the minimization row ``x``
    within ``tol`` (relative, per objective) on every objective."""
    if len(others) == 0:
        return False
    slack = x + tol * np.abs(x)
    return bool((others <= slack).all(axis=1).any())


def outranked(x: np.ndarray, others: np.ndarray, tol: np.ndarray) -> bool:
    """Some row of ``others`` beats ``x`` by more than ``tol`` on every
    objective: no rounding inside ``tol`` can have put ``x`` on a front
    beside it."""
    if len(others) == 0:
        return False
    return bool((others + tol * np.abs(others)
                 < x - tol * np.abs(x)).all(axis=1).any())


def pareto_rows(F: np.ndarray) -> np.ndarray:
    """Mask of the non-dominated rows of a two-column minimization
    matrix; equal rows do not dominate each other."""
    F = np.asarray(F, dtype=np.float64)
    if len(F) == 0:
        return np.zeros(0, dtype=bool)
    order = np.lexsort((F[:, 1], F[:, 0]))
    s = F[order]
    # sorted by the first column, then the second: a row is dominated
    # iff a different row before it has a second column at most its own
    best_before = np.minimum.accumulate(np.concatenate([[np.inf], s[:-1, 1]]))
    new = np.concatenate([[True], (s[1:] != s[:-1]).any(axis=1)])
    first = np.maximum.accumulate(np.where(new, np.arange(len(s)), 0))
    keep = np.empty(len(F), dtype=bool)
    keep[order] = s[:, 1] < best_before[first]
    return keep


def dominated(F: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Which ``rows`` some row of the two-column minimization matrix
    ``F`` matches or beats on both columns."""
    if len(F) == 0:
        return np.zeros(len(rows), dtype=bool)
    order = np.argsort(F[:, 0], kind="stable")
    best = np.minimum.accumulate(F[order, 1])
    i = np.searchsorted(F[order, 0], rows[:, 0], side="right")
    return (i > 0) & (best[np.maximum(i - 1, 0)] <= rows[:, 1])


def _gaps(front: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """``(len(front), len(rows), k)``: how much worse each row is than
    each front member, per objective, relative to the front member."""
    return (rows[None, :, :] - front[:, None, :]) / np.abs(front[:, None, :])


def missed_gap(front: np.ndarray, rows: np.ndarray) -> float:
    """The largest relative amount by which ``rows`` fail to match or
    beat a member of ``front``: for each member, the row that comes
    closest on its worst objective; 0 where every member is matched."""
    if len(front) == 0:
        return 0.0
    if len(rows) == 0:
        return float("inf")
    return float(max(0.0, _gaps(front, rows).max(axis=2).min(axis=1).max()))


def extra_gap(front: np.ndarray, rows: np.ndarray) -> float:
    """The largest relative amount by which a member of ``front`` beats
    one of ``rows`` on every objective; 0 where none does."""
    if len(front) == 0 or len(rows) == 0:
        return 0.0
    return float(max(0.0, _gaps(front, rows).min(axis=2).max()))
