"""Analytical synthesis oracle — stands in for Synopsys DC + VCS @ FreePDK45.

The paper obtains "actual" power / area / timing from a commercial synthesis
flow and then fits polynomial models to them.  That flow is unavailable here,
so this module produces the ground-truth side from gate-level analytical
models (constants in :mod:`repro.core.pe`), with a small deterministic,
config-dependent "process" perturbation so the regression fit in
:mod:`repro.core.ppa_model` is a genuine estimation problem rather than an
identity.  DESIGN.md §2 records this substitution.

The perturbation is a **counter-based hash** over the config's packed
integer field words (:mod:`repro.core.confighash`) — fully vectorized, no
per-config Python, and bit-identical between the scalar, batched-numpy,
and jax paths (the scalar path simply evaluates a length-1 batch).  The
same 128-bit digest keys the in-process LRU report cache and the on-disk
npz cache, so a cold run over a previously seen space skips synthesis
entirely.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import pathlib
from typing import Sequence

import numpy as np

from repro.core.accelerator import AcceleratorConfig, configs_to_soa
from repro.core.confighash import (config_digests, digest_keys,
                                   digests_to_u64, uniform01)
from repro.core.dataflow import leakage_mw_soa
from repro.core.pe import (rf_access_energy_pj, sram_access_energy_pj,
                           sram_area_um2)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

@dataclasses.dataclass(frozen=True)
class SynthesisReport:
    """What the synthesis + simulation flow reports for one design."""

    area_mm2: float            # post-synthesis cell area
    power_mw: float            # dynamic + leakage at nominal activity
    clock_ghz: float           # achieved clock after timing closure
    throughput_gmacs: float    # peak effective GMAC/s at that clock

    def as_dict(self) -> dict[str, float]:
        return dataclasses.asdict(self)


# columns of the array-form synthesis result, in stable (npz) order
REPORT_COLUMNS = ("area_mm2", "power_mw", "clock_ghz", "throughput_gmacs")


def synthesize_soa(soa: dict[str, np.ndarray],
                   digests=None, xp=np) -> dict[str, np.ndarray]:
    """Run the analytical synthesis flow for a whole config batch.

    Pure fused array math over the struct-of-arrays form
    (:func:`repro.core.accelerator.configs_to_soa`): every op is
    elementwise, so any row of a batch is bit-identical to a length-1
    evaluation of that config — the scalar :func:`synthesize` is literally
    this function on one row.  Returns ``{column: (N,) float64}`` for
    :data:`REPORT_COLUMNS`.
    """
    if digests is None:
        digests = config_digests(soa, xp=xp)
    f = np.float64
    # one independent digest lane per perturbed quantity
    jit_area = 1.0 + 0.03 * (2.0 * uniform01(digests[0], xp=xp) - 1.0)
    jit_clk = 1.0 + 0.02 * (2.0 * uniform01(digests[1], xp=xp) - 1.0)
    jit_pw = 1.0 + 0.04 * (2.0 * uniform01(digests[2], xp=xp) - 1.0)

    n = soa["num_pes"].astype(f)
    glb_bits = soa["glb_bits"].astype(f)
    spad_bits = soa["spad_bits"].astype(f)

    # ---- area ------------------------------------------------------------
    pe_area = soa["mac_area_um2"] + sram_area_um2(spad_bits, xp=xp)
    glb_area = sram_area_um2(glb_bits, xp=xp)
    # NoC + control overhead grows slightly super-linearly with array size
    noc_area = 120.0 * n * (1.0 + 0.004 * xp.sqrt(n))
    area_mm2 = (n * pe_area + glb_area + noc_area) * jit_area / 1e6

    # ---- timing ----------------------------------------------------------
    # Wire delay degrades the achievable clock for very large arrays.
    wire_penalty = 1.0 + 0.002 * xp.sqrt(n)
    clock_ghz = xp.minimum((soa["max_clock_ghz"] / wire_penalty) * jit_clk,
                           soa["clock_cap"])

    # ---- power at nominal activity (70% MAC utilization) ------------------
    util = 0.70
    mac_pw = n * util * soa["mac_energy_pj"] * clock_ghz * 1e9 * 1e-12  # mW
    # each MAC: ifmap read + weight read + ~1 psum spad access
    e_spad = rf_access_energy_pj(spad_bits, xp=xp)
    spad_pw = n * util * 3.0 * e_spad * clock_ghz * 1e9 * 1e-12
    # GLB serves ~1 access per 8 MACs across the array (row-stationary reuse)
    e_glb = sram_access_energy_pj(glb_bits, xp=xp)
    glb_pw = n * util * (1.0 / 8.0) * e_glb * clock_ghz * 1e9 * 1e-12
    leak_mw = leakage_mw_soa(soa)                         # GLB ~2uW/kB
    power_mw = (mac_pw + spad_pw + glb_pw + leak_mw) * jit_pw

    return {
        "area_mm2": area_mm2,
        "power_mw": power_mw,
        "clock_ghz": clock_ghz,
        "throughput_gmacs": n * clock_ghz,
    }


def synthesize(cfg: AcceleratorConfig) -> SynthesisReport:
    """Run the analytical 'synthesis flow' for one design point — a
    length-1 batch through :func:`synthesize_soa`, so scalar and batched
    results are bit-identical by construction."""
    cols = synthesize_soa(configs_to_soa((cfg,)))
    return SynthesisReport(**{k: float(cols[k][0]) for k in REPORT_COLUMNS})


def config_hash(cfg: AcceleratorConfig) -> str:
    """Stable identity key for one design point: the hex form of its
    128-bit packed-field digest.  Folds in *every* field — including
    ``clock_ghz``, which ``cfg.name()`` omits but which changes timing
    closure.  Batch paths should use :func:`config_keys` instead."""
    return config_keys((cfg,))[0].hex()


def config_keys(configs: Sequence[AcceleratorConfig],
                soa: dict[str, np.ndarray] | None = None) -> list[bytes]:
    """16-byte digest keys for a config batch (vectorized)."""
    if soa is None:
        soa = configs_to_soa(tuple(configs))
    return digest_keys(config_digests(soa))


# ---------------------------------------------------------------------------
# In-process report cache: bounded LRU keyed by the 16-byte digest.
# ---------------------------------------------------------------------------

_SYNTH_CACHE: collections.OrderedDict[bytes, SynthesisReport] = \
    collections.OrderedDict()
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}
_CACHE_LIMIT = 1 << 18          # ~260k reports ≈ tens of MB, bounded


def synthesis_cache_stats() -> dict[str, int]:
    stats = dict(_CACHE_STATS, size=len(_SYNTH_CACHE), limit=_CACHE_LIMIT)
    stats.update(array_hits=_SWEEP_CACHE.hits, array_misses=_SWEEP_CACHE.misses,
                 array_size=len(_SWEEP_CACHE),
                 array_evictions=_SWEEP_CACHE.evictions)
    return stats


def set_synthesis_cache_limit(limit: int) -> int:
    """Cap both in-process synthesis caches (entries/rows); returns the
    old cap.  Shrinking evicts oldest entries immediately — in the object
    LRU and in the sweep engine's array store alike."""
    global _CACHE_LIMIT
    old, _CACHE_LIMIT = _CACHE_LIMIT, max(0, int(limit))
    _evict_to_limit()
    _SWEEP_CACHE.max_rows = _CACHE_LIMIT
    _SWEEP_CACHE._compact()
    return old


def clear_synthesis_cache() -> None:
    _SYNTH_CACHE.clear()
    _CACHE_STATS.update(hits=0, misses=0, evictions=0)
    _SWEEP_CACHE.clear()


def _evict_to_limit() -> None:
    while len(_SYNTH_CACHE) > _CACHE_LIMIT:
        _SYNTH_CACHE.popitem(last=False)
        _CACHE_STATS["evictions"] += 1


def _cache_put(key: bytes, rep: SynthesisReport) -> None:
    _SYNTH_CACHE[key] = rep
    _evict_to_limit()


def synthesize_cached(cfg: AcceleratorConfig) -> SynthesisReport:
    """`synthesize` with memoization — re-sweeping a design space (new
    workload, extended sweep) never re-runs the flow for a known config."""
    key = config_keys((cfg,))[0]
    hit = _SYNTH_CACHE.get(key)
    if hit is not None:
        _CACHE_STATS["hits"] += 1
        _SYNTH_CACHE.move_to_end(key)
        return hit
    _CACHE_STATS["misses"] += 1
    rep = synthesize(cfg)
    _cache_put(key, rep)
    return rep


def synthesize_many(configs: Sequence[AcceleratorConfig],
                    use_cache: bool = True,
                    soa: dict[str, np.ndarray] | None = None
                    ) -> list[SynthesisReport]:
    """Vectorized synthesis for a batch of design points.

    Digests, jitter, and the PPA math all evaluate as fused array
    expressions across the whole batch; cached configs are skipped
    entirely.  ``soa`` (from
    :func:`repro.core.accelerator.configs_to_soa`) can be passed to reuse
    an existing struct-of-arrays conversion.
    """
    configs = list(configs)
    if not configs:
        return []
    if soa is None:
        soa = configs_to_soa(configs)
    out: list[SynthesisReport | None] = [None] * len(configs)
    digests = config_digests(soa)
    if use_cache:
        keys = digest_keys(digests)
        todo = []
        for i, key in enumerate(keys):
            hit = _SYNTH_CACHE.get(key)
            if hit is not None:
                _CACHE_STATS["hits"] += 1
                _SYNTH_CACHE.move_to_end(key)
                out[i] = hit
            else:
                _CACHE_STATS["misses"] += 1
                todo.append(i)
        if not todo:
            return out  # type: ignore[return-value]
        idx = np.array(todo, dtype=np.intp)
        sub = {k: v[idx] for k, v in soa.items()}
        cols = synthesize_soa(sub, digests=tuple(d[idx] for d in digests))
        for j, i in enumerate(todo):
            rep = SynthesisReport(
                **{k: float(cols[k][j]) for k in REPORT_COLUMNS})
            out[i] = rep
            _cache_put(keys[i], rep)
        return out  # type: ignore[return-value]
    cols = synthesize_soa(soa, digests=digests)
    return [SynthesisReport(**{k: float(cols[k][i])
                               for k in REPORT_COLUMNS})
            for i in range(len(configs))]


# ---------------------------------------------------------------------------
# Persisted synthesis cache: npz of (N, 2) uint64 digest keys + one float64
# column per REPORT_COLUMNS entry.  Array-level (no report objects), so the
# streamed sweep driver can hydrate 1M-config spaces in bounded time.
# ---------------------------------------------------------------------------

_LOAD = 4      # the index is rebuilt before it passes 1/_LOAD full ...
_SPARSE = 8    # ... into a table of at least _SPARSE slots per row


class PersistentSynthesisCache:
    """Digest-keyed synthesis store with npz persistence.

    ``lookup`` / ``insert`` operate on whole chunks; rows live in one
    growing value matrix so hits gather with a single fancy index.  A cold
    sweep over a previously saved space does zero synthesis math.

    ``max_rows`` bounds memory: on overflow the oldest half of the rows is
    dropped and the store compacted (counted in ``evictions``).

    The index is an open-addressing table over the ``(N, 2)`` uint64
    digest words: home slot = low bits of the first word, linear probing,
    a whole batch probed per round of array operations.  A slot holds the
    sequence number of its key's newest row (row ``r`` is sequence number
    ``_base + r``) and the key's first word.  Compaction only advances
    ``_base``: the dropped rows' slots go stale, probed past and never
    matched.  Once a quarter of the table is taken, the rows are indexed
    afresh under sequence numbers above every old one, which frees every
    slot below ``_floor`` without a refill.
    """

    def __init__(self, path: str | pathlib.Path | None = None,
                 max_rows: int | None = None):
        self.path = pathlib.Path(path) if path is not None else None
        self.max_rows = max_rows
        self._keys = np.empty((0, 2), dtype=np.uint64)
        self._vals = np.empty((0, len(REPORT_COLUMNS)), dtype=np.float64)
        self._top = np.empty(0, dtype=bool)   # row is its key's newest
        self._n = 0
        self._slots = np.empty(0, dtype=np.complex128)
        self._seq = self._slots.view(np.int64)[::2]
        self._floor = self._base = 0
        self._used = 0                      # slots taken, stale included
        self._live = 0                      # distinct keys with a row
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        if self.path is not None and self.path.exists():
            try:
                self.load(self.path)
            except Exception as exc:
                # a corrupted / truncated / foreign npz must never poison
                # the cache with garbage rows: warn and rebuild from empty
                # (the next save overwrites the bad file).  An *explicit*
                # load() call still raises.
                import warnings
                warnings.warn(
                    f"persistent synthesis cache at {self.path} is "
                    f"unreadable ({type(exc).__name__}: {exc}); starting "
                    f"with an empty cache and rebuilding",
                    RuntimeWarning, stacklevel=2)

    def clear(self) -> None:
        """Drop all rows and stats; keeps the cap and the save path."""
        path, self.path = self.path, None     # don't reload from disk
        self.__init__(path=None, max_rows=self.max_rows)
        self.path = path

    def _compact(self) -> None:
        if self.max_rows is None or self._n <= self.max_rows:
            return
        keep = self.max_rows // 2           # newest half survives
        drop = self._n - keep
        self._live -= int(np.count_nonzero(self._top[:drop]))
        self._keys[:keep] = self._keys[drop:self._n]
        self._vals[:keep] = self._vals[drop:self._n]
        self._top[:keep] = self._top[drop:self._n]
        self._n = keep
        self._base += drop                  # the dropped rows' slots go stale
        self.evictions += drop
        obs_metrics.get_registry().inc("synth_cache.evictions", drop)

    def __len__(self) -> int:
        return self._n

    def _grow(self, extra: int) -> None:
        need = self._n + extra
        cap = len(self._keys)
        if need > cap:
            cap = max(need, 2 * cap, 1024)
            self._keys = np.resize(self._keys, (cap, 2))
            self._vals = np.resize(self._vals, (cap, len(REPORT_COLUMNS)))
            self._top = np.resize(self._top, cap)

    def _append(self, u64: np.ndarray, vals: np.ndarray) -> None:
        """Store rows at the end and index them."""
        m = len(u64)
        self._grow(m)
        self._keys[self._n:self._n + m] = u64
        self._vals[self._n:self._n + m] = vals
        self._index(np.arange(self._n, self._n + m), u64)
        self._n += m

    # -- index -------------------------------------------------------------

    def _reindex(self, extra: int) -> None:
        """Index the stored rows afresh, with room for ``extra`` more.

        A slot is one 16-byte record (sequence number, first key word),
        held as one complex128 so that a batch gathers and scatters whole
        records with 1-D fancy indexing."""
        size = 1 << max(10, (_SPARSE * (self._n + extra) - 1).bit_length())
        if size > len(self._slots):
            self._slots = np.full((size, 2), -1, np.int64) \
                .view(np.complex128)[:, 0]
            self._seq = self._slots.view(np.int64)[::2]
            self._floor = self._base = 0
        else:
            # number the rows past every sequence number handed out so
            # far: each slot of the table reads as free
            self._floor = self._base = self._base + self._n
        self._used = self._live = 0
        self._index(np.arange(self._n), self._keys[:self._n])

    def _words(self, u64: np.ndarray):
        """First word (as int64), second word and home slot of each key."""
        k0 = np.ascontiguousarray(u64[:, 0]).view(np.int64)
        pos = (k0 & (len(self._slots) - 1)).astype(np.intp)
        return k0, np.ascontiguousarray(u64[:, 1]), pos

    def _probe(self, k0: np.ndarray, k1: np.ndarray, idx: np.ndarray,
               pos: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """One probe round for the keys ``idx`` at the slots ``pos``:
        the slots' sequence numbers, and which hold the key itself live
        (both words compare; a stale or free slot never matches)."""
        got = self._slots[pos].view(np.int64).reshape(-1, 2)
        seq = got[:, 0]
        same = (got[:, 1] == k0[idx]) & (seq >= self._base)
        cand = np.flatnonzero(same)
        if len(cand):
            rows = seq[cand] - self._base
            key1 = self._keys.view(np.complex128)[rows, 0] \
                .view(np.uint64)[1::2]
            same[cand] = key1 == k1[idx[cand]]
        return seq, same

    def _find(self, u64: np.ndarray) -> np.ndarray:
        """Row of each key's newest live row, -1 where absent."""
        rows = np.full(len(u64), -1, dtype=np.intp)
        if not self._n:
            return rows                     # no live row, every slot stale
        wrap = len(self._slots) - 1
        k0, k1, pos = self._words(u64)
        idx = np.arange(len(u64))
        while len(idx):
            seq, hit = self._probe(k0, k1, idx, pos)
            rows[idx[hit]] = seq[hit] - self._base
            on = np.flatnonzero((seq >= self._floor) & ~hit)
            idx, pos = idx[on], (pos[on] + 1) & wrap
        return rows

    def _index(self, rows: np.ndarray, u64: np.ndarray) -> None:
        """Point the index at the stored ``rows``, whose keys are ``u64``.

        A key found live has its slot raised to the newest row; an absent
        key takes the first free slot on its probe path.  Keys repeated
        in ``rows`` race for one free slot: one wins it, the rest find it
        there next round.
        """
        if _LOAD * (self._used + len(rows)) > len(self._slots):
            self._reindex(len(rows))
        seqs = self._base + rows
        wrap = len(self._slots) - 1
        k0, k1, pos = self._words(u64)
        rec = np.stack([seqs, k0], axis=-1).view(np.complex128)[:, 0]
        slot = np.empty(len(rows), dtype=np.intp)  # where each row landed
        idx = np.arange(len(rows))
        claimed = 0
        while len(idx):
            seq, same = self._probe(k0, k1, idx, pos)
            hit = np.flatnonzero(same)
            if len(hit):
                self._top[seq[hit] - self._base] = False
                np.maximum.at(self._seq, pos[hit], seqs[idx[hit]])
                slot[idx[hit]] = pos[hit]
            free = seq < self._floor
            on = ~free & ~same              # another key, or stale
            got = np.flatnonzero(free)
            if len(got):
                gpos, gidx = pos[got], idx[got]
                self._slots[gpos] = rec[gidx]
                slot[gidx] = gpos           # a loser's is set again later
                won = self._seq[gpos] == seqs[gidx]
                claimed += int(np.count_nonzero(won))
                free[got[won]] = False      # losers retry the same slot
            keep = np.flatnonzero(on | free)
            idx, pos = idx[keep], (pos[keep] + on[keep]) & wrap
        self._top[rows] = self._seq[slot] == seqs
        self._used += claimed
        self._live += claimed

    # -- batch API ---------------------------------------------------------

    def lookup(self, digests) -> tuple[np.ndarray, dict[str, np.ndarray]]:
        """(hit_mask, columns) for a digest batch; missed rows are zero."""
        rows = self._find(digests_to_u64(digests))
        mask = rows >= 0
        vals = np.zeros((len(rows), len(REPORT_COLUMNS)), dtype=np.float64)
        if mask.any():
            vals[mask] = self._vals[rows[mask]]
        nh = int(np.count_nonzero(mask))
        nm = len(rows) - nh
        self.hits += nh
        self.misses += nm
        reg = obs_metrics.get_registry()
        reg.inc("synth_cache.hits", nh)
        reg.inc("synth_cache.misses", nm)
        return mask, {c: vals[:, j] for j, c in enumerate(REPORT_COLUMNS)}

    def insert(self, digests, cols: dict[str, np.ndarray],
               rows_mask: np.ndarray | None = None) -> int:
        """Store (a masked subset of) a digest batch's columns; returns
        the change in distinct keys held, compaction included.

        Bulk path: rows append en masse and the index takes the batch in
        rounds of array operations.  Duplicate keys (re-inserted or
        repeated within the batch) leave their older rows in place as
        dead weight and point the index at the newest — values for a
        given digest are identical by construction, so this only costs
        bytes, not correctness.
        """
        u64 = digests_to_u64(digests)
        vals = np.stack([np.asarray(cols[c], dtype=np.float64)
                         for c in REPORT_COLUMNS], axis=-1)
        if rows_mask is not None:
            u64, vals = u64[rows_mask], vals[rows_mask]
        if len(u64) == 0:
            return 0
        before = self._live
        self._append(u64, vals)
        self._compact()
        return self._live - before

    def save(self, path: str | pathlib.Path | None = None) -> int:
        """Write all rows to ``path`` (default: the constructor path).

        Atomic: the npz goes to a sibling temp file first and is
        ``os.replace``d over the target, so a crash mid-save leaves the
        previous cache intact instead of a truncated file the constructor
        would have to discard and rebuild.
        """
        path = pathlib.Path(path) if path is not None else self.path
        if path is None:
            raise ValueError("PersistentSynthesisCache.save: no path")
        # write through a handle: np.savez would append ".npz" to a
        # suffix-less path and orphan the cache on the next load
        tmp = path.with_name(path.name + f".tmp{os.getpid()}")
        try:
            with open(tmp, "wb") as fh:
                np.savez_compressed(
                    fh, keys=self._keys[:self._n],
                    **{c: self._vals[:self._n, j]
                       for j, c in enumerate(REPORT_COLUMNS)})
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, path)
        finally:
            if tmp.exists():
                tmp.unlink()
        return self._n

    def export_state(self) -> dict:
        """Rows + accounting as a plain dict of arrays/scalars — the
        synthesis-cache slice of an exploration checkpoint
        (:mod:`repro.runtime.dse_checkpoint`).  Counters ride along so a
        resumed run's hit/miss accounting matches the uninterrupted run
        exactly."""
        return {
            "keys": self._keys[:self._n].copy(),
            "vals": self._vals[:self._n].copy(),
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
        }

    def import_state(self, state: dict) -> None:
        """Replace rows and counters with an :meth:`export_state`
        snapshot (the inverse: existing contents are dropped, not
        merged)."""
        keys = np.ascontiguousarray(state["keys"], dtype=np.uint64)
        vals = np.asarray(state["vals"], dtype=np.float64)
        if keys.ndim != 2 or keys.shape[1] != 2 \
                or vals.shape != (len(keys), len(REPORT_COLUMNS)):
            raise ValueError(
                f"cache snapshot shapes {keys.shape} / {vals.shape} are "
                f"not (N, 2) / (N, {len(REPORT_COLUMNS)})")
        self.clear()
        self._append(keys, vals)            # the last occurrence wins
        self.hits = int(state["hits"])
        self.misses = int(state["misses"])
        self.evictions = int(state["evictions"])
        self._compact()

    def load(self, path: str | pathlib.Path) -> int:
        """Merge rows from an npz file; returns how many were new.

        Raises (``ValueError`` for a structurally wrong file, whatever
        ``np.load`` raises for a corrupt one) instead of ever merging
        garbage — the constructor catches this and rebuilds, an explicit
        call surfaces it.
        """
        with np.load(pathlib.Path(path)) as z:
            missing = {"keys", *REPORT_COLUMNS} - set(z.files)
            if missing:
                raise ValueError(
                    f"synthesis cache {path} is missing array(s) "
                    f"{sorted(missing)}")
            keys = np.ascontiguousarray(z["keys"], dtype=np.uint64)
            if keys.ndim != 2 or keys.shape[1] != 2:
                raise ValueError(
                    f"synthesis cache {path}: keys shape {keys.shape} "
                    f"!= (N, 2)")
            vals = np.stack([z[c] for c in REPORT_COLUMNS], axis=-1)
            if vals.shape != (len(keys), len(REPORT_COLUMNS)):
                raise ValueError(
                    f"synthesis cache {path}: {len(keys)} keys but "
                    f"value block {vals.shape}")
            if not np.isfinite(vals).all():
                raise ValueError(
                    f"synthesis cache {path}: non-finite report values")
        # keys already present are skipped; within the file the first
        # occurrence wins
        take = np.zeros(len(keys), dtype=bool)
        take[np.unique(keys, axis=0, return_index=True)[1]] = True
        take &= self._find(keys) < 0
        before = self._n
        self._append(keys[take], vals[take])
        self._compact()
        return self._n - before

    def synthesize(self, soa: dict[str, np.ndarray]
                   ) -> dict[str, np.ndarray]:
        """Cache-through batched synthesis: hit rows gather from the
        store, miss rows run :func:`synthesize_soa` and are inserted."""
        with obs_trace.span("synth.digest"):
            digests = config_digests(soa)
        with obs_trace.span("synth.lookup"):
            mask, cols = self.lookup(digests)
        miss = ~mask
        if miss.any():
            idx = np.nonzero(miss)[0]
            with obs_trace.span("synth.model", n=len(idx)):
                sub = {k: v[idx] for k, v in soa.items()}
                fresh = synthesize_soa(sub, digests=tuple(d[idx]
                                                          for d in digests))
                for c in REPORT_COLUMNS:
                    cols[c][idx] = fresh[c]
            with obs_trace.span("synth.insert"):
                self.insert(tuple(d[idx] for d in digests), fresh)
        return cols


# module-level array store: the batched sweep engine's synthesis cache
# (object-free twin of _SYNTH_CACHE, bounded the same way)
_SWEEP_CACHE = PersistentSynthesisCache(max_rows=_CACHE_LIMIT)


def sweep_synthesis_cache() -> PersistentSynthesisCache:
    """The process-wide array-level synthesis cache used by
    :func:`repro.core.dse_batch.sweep_workload` and friends."""
    return _SWEEP_CACHE
