"""Vectorized serving-fleet simulator over the (N candidates, T steps) grid.

Scores every accelerator candidate from the fused sweep on a *serving
fleet* instead of a single inference: each candidate runs an
Orca-style continuous batcher (:mod:`repro.serving.scheduler`) with
``n_slots`` slots against one shared :class:`~repro.serving.traffic
.TrafficTrace`, and the simulator reports per-request completion times,
SLO attainment, throughput under load, and energy per served token.

Model
-----
One batcher iteration on candidate *n* takes ``step_s[n]`` seconds (the
candidate's fused-sweep latency aggregate) and advances every busy slot
by one token — prompt tokens replay during prefill, decode tokens issue
one per iteration, and a request with P prompt / G decode tokens holds
its slot for ``P + G - 1`` iterations (the iteration consuming the last
prompt token also emits the first decode token — exactly the
``ContinuousBatcher`` contract, which the tests pin as the golden
reference).  Every *active* iteration dispatches the full ``n_slots``
batch, so it costs ``n_slots * e_token_j[n]`` joules regardless of
occupancy: energy per served token is occupancy-sensitive, which is what
separates serving-fleet fronts from per-inference EDP fronts.

Bit-exactness across backends
-----------------------------
The only float in the simulation is the arrival-time → arrival-iteration
conversion ``ceil(arrival_s / step_s)``, computed once host-side in
float64.  The simulation loop itself is pure integer arithmetic, so the
numpy and jitted-jax paths produce *bit-identical* iteration stamps by
construction (the ``dse_batch`` backend policy asks only for <=1e-6);
the scalar event-driven reference matches them exactly as well.  Derived
metrics are bit-identical integer stamps scaled by ``step_s`` /
``e_token_j``, so when those inputs come from the numpy vs jax sweep
kernels the serving objectives inherit exactly the kernels' <=1e-6
relative noise — no cancellation amplification.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.dse_batch import resolve_backend
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving.traffic import TrafficTrace, resolve_traffic

_INT32_MAX = np.iinfo(np.int32).max


def _arrival_iters(step_s: np.ndarray, arrival_s: np.ndarray) -> np.ndarray:
    """(N, R) first iteration index at which each request is admissible.

    Request r is in the queue at the start of iteration k iff
    ``arrival_s[r] <= k * step_s[n]``, i.e. ``k >= ceil(arrival/step)``.
    Computed once host-side in float64 so every backend sees the same
    integers.
    """
    a = np.ceil(np.asarray(arrival_s, np.float64)[None, :]
                / np.asarray(step_s, np.float64)[:, None])
    if a.size and a.max() >= _INT32_MAX:
        raise ValueError(
            "trace arrival horizon overflows the iteration grid "
            f"(max arrival iteration {a.max():.3g}); step_s is too small "
            "for this trace — shorten the trace or cap max_iters")
    return a.astype(np.int64)


@dataclasses.dataclass(frozen=True)
class FleetResult:
    """Raw per-request iteration stamps plus derived serving metrics.

    ``submit_iter[n, r]`` is the iteration at which request r was
    admitted on candidate n (-1 if never admitted within ``n_iters``);
    ``comp_iter[n, r]`` is the iteration count by which it completed
    (``submit + P + G - 1``; 0 if never admitted).  A request counts as
    *served* iff ``0 < comp_iter <= n_iters``.
    """

    trace: TrafficTrace
    n_slots: int
    n_iters: int
    backend: str
    step_s: np.ndarray        # (N,) float64 seconds per iteration
    e_token_j: np.ndarray     # (N,) float64 joules per token-slot
    submit_iter: np.ndarray   # (N, R) int64, -1 = never admitted
    comp_iter: np.ndarray     # (N, R) int64, 0 = never admitted
    active_iters: np.ndarray  # (N,) int64 iterations with >=1 busy slot

    @property
    def n_candidates(self) -> int:
        return len(self.step_s)

    @property
    def served(self) -> np.ndarray:
        """(N, R) bool: admitted and completed within the horizon."""
        return (self.comp_iter > 0) & (self.comp_iter <= self.n_iters)

    @property
    def latency_s(self) -> np.ndarray:
        """(N, R) float64 queueing+service latency; +inf if unserved.

        Measured on the iteration grid — ``(comp - arrive_iter) * step``,
        i.e. from the first iteration boundary at which the request is
        admissible (the fixed-step clock can't see it earlier) to
        completion.  This drops the sub-step arrival offset (< one
        iteration) but keeps the value a bit-identical integer scaled by
        ``step_s``, so cross-backend noise stays multiplicative (<= the
        kernel's 1e-6 contract) instead of being amplified by
        near-cancellation against the wall-clock arrival time.
        """
        arrive = _arrival_iters(self.step_s,
                                np.asarray(self.trace.arrival_s))
        lat = ((self.comp_iter - arrive).astype(np.float64)
               * self.step_s[:, None])
        return np.where(self.served, lat, np.inf)

    def metrics(self, slo_s: float | None = None) -> dict[str, np.ndarray]:
        """Serving objectives, all (N,) float64.

        Unserved requests poison the latency percentiles to +inf and
        count against ``slo_attainment`` — an overloaded design is
        penalized, not silently excused.  The objectives layer maps the
        infinities onto its finite floor penalty.
        """
        slo = float(self.trace.slo_s if slo_s is None else slo_s)
        n = self.n_candidates
        r = self.trace.n_requests
        svc = np.asarray(self.trace.service_iters, np.int64)
        if r == 0:
            z = np.zeros(n, np.float64)
            return {"p50_latency_s": z.copy(), "p99_latency_s": z.copy(),
                    "slo_attainment": np.ones(n, np.float64),
                    "throughput_tps": z.copy(),
                    "energy_per_token_j": z.copy(),
                    "served_frac": np.ones(n, np.float64)}
        lat = self.latency_s
        served = self.served
        served_tokens = (svc[None, :] * served).sum(axis=1,
                                                    dtype=np.float64)
        makespan = (np.where(served, self.comp_iter, 0).max(axis=1)
                    .astype(np.float64) * self.step_s)
        energy = (self.active_iters.astype(np.float64) * self.n_slots
                  * self.e_token_j)
        with np.errstate(divide="ignore", invalid="ignore"):
            throughput = np.where(makespan > 0,
                                  served_tokens / makespan, 0.0)
            e_per_tok = np.where(served_tokens > 0,
                                 energy / served_tokens, np.inf)
            # percentile interpolates inf-inf to nan; the right answer
            # for an unserved tail is +inf
            p50 = np.nan_to_num(np.percentile(lat, 50.0, axis=1),
                                nan=np.inf, posinf=np.inf)
            p99 = np.nan_to_num(np.percentile(lat, 99.0, axis=1),
                                nan=np.inf, posinf=np.inf)
        return {
            "p50_latency_s": p50,
            "p99_latency_s": p99,
            "slo_attainment": ((lat <= slo).sum(axis=1)
                               / np.float64(r)),
            "throughput_tps": throughput,
            "energy_per_token_j": e_per_tok,
            "served_frac": served.sum(axis=1) / np.float64(r),
        }


def _simulate_numpy(arrive, svc, n_slots, n_iters):
    """Fixed-step integer sim: (N,R) arrive iters -> iteration stamps.

    Event-jumping makes this O(admissions), not O(n_iters): between
    admissions nothing changes except slots draining, so the loop jumps
    straight to the next iteration where *any* candidate can admit and
    counts the skipped window's active iterations in closed form
    (candidate n is busy at iteration j iff ``max(busy_until[n]) > j``).
    Iteration-for-iteration identical to the jax ``fori_loop`` path.
    """
    n, r = arrive.shape
    rows = np.arange(n)
    busy_until = np.zeros((n, n_slots), np.int64)
    next_req = np.zeros(n, np.int64)
    submit = np.full((n, r), -1, np.int64)
    comp = np.zeros((n, r), np.int64)
    active = np.zeros(n, np.int64)
    k = 0
    while k < n_iters:
        for s in range(n_slots):         # slot-order admission, FIFO queue
            idx = np.minimum(next_req, r - 1)
            can = ((next_req < r) & (arrive[rows, idx] <= k)
                   & (busy_until[:, s] <= k))
            done_at = k + svc[idx]
            busy_until[:, s] = np.where(can, done_at, busy_until[:, s])
            submit[rows[can], idx[can]] = k
            comp[rows[can], idx[can]] = done_at[can]
            next_req = next_req + can
        # after the slot pass, each pending head either hasn't arrived
        # (next event = its arrival) or found every slot busy (next event
        # = earliest slot release); drained candidates never admit again
        idx = np.minimum(next_req, r - 1)
        next_adm = np.where(
            next_req < r,
            np.maximum(arrive[rows, idx], busy_until.min(axis=1)),
            n_iters)
        k2 = min(max(int(next_adm.min()), k + 1), n_iters)
        max_bu = busy_until.max(axis=1)
        active += np.clip(np.minimum(max_bu, k2) - k, 0, None)
        k = k2
    return submit, comp, active


_JAX_SIMS: dict = {}


def _jax_sim(n_slots: int, n_iters: int):
    import jax
    import jax.numpy as jnp

    key = (n_slots, n_iters)
    fn = _JAX_SIMS.get(key)
    if fn is not None:
        return fn

    def sim(arrive, svc):
        n, r = arrive.shape
        rows = jnp.arange(n)

        def body(k, state):
            busy_until, next_req, submit, comp, active = state
            for s in range(n_slots):
                idx = jnp.minimum(next_req, r - 1)
                can = ((next_req < r) & (arrive[rows, idx] <= k)
                       & (busy_until[:, s] <= k))
                done_at = k + svc[idx]
                busy_until = busy_until.at[:, s].set(
                    jnp.where(can, done_at, busy_until[:, s]))
                submit = submit.at[rows, idx].set(
                    jnp.where(can, k, submit[rows, idx]))
                comp = comp.at[rows, idx].set(
                    jnp.where(can, done_at, comp[rows, idx]))
                next_req = next_req + can
            active = active + (busy_until > k).any(axis=1)
            return busy_until, next_req, submit, comp, active

        init = (jnp.zeros((n, n_slots), jnp.int32),
                jnp.zeros(n, jnp.int32),
                jnp.full((n, r), -1, jnp.int32),
                jnp.zeros((n, r), jnp.int32),
                jnp.zeros(n, jnp.int32))
        _, _, submit, comp, active = jax.lax.fori_loop(
            0, n_iters, body, init)
        return submit, comp, active

    fn = jax.jit(sim)
    _JAX_SIMS[key] = fn
    return fn


def _simulate_jax(arrive, svc, n_slots, n_iters):
    import jax.numpy as jnp

    # the sim is pure int32 arithmetic: identical to numpy by construction
    fn = _jax_sim(n_slots, n_iters)
    submit, comp, active = fn(jnp.asarray(arrive, jnp.int32),
                              jnp.asarray(svc, jnp.int32))
    return (np.asarray(submit, np.int64), np.asarray(comp, np.int64),
            np.asarray(active, np.int64))


def simulate_fleet(step_s, e_token_j, traffic, *, n_slots: int = 8,
                   max_iters: int | None = None,
                   backend: str = "auto") -> FleetResult:
    """Replay ``traffic`` against N candidates; return iteration stamps.

    ``step_s`` / ``e_token_j`` are (N,) per-candidate seconds-per-
    iteration and joules-per-token-slot from the fused sweep.  With
    ``max_iters=None`` the horizon auto-drains (last arrival plus total
    service, so every request completes); pass a finite ``max_iters`` to
    model a hard serving window, in which case stragglers are unserved.
    """
    trace = resolve_traffic(traffic)
    step = np.atleast_1d(np.asarray(step_s, np.float64))
    e_tok = np.atleast_1d(np.asarray(e_token_j, np.float64))
    if step.ndim != 1 or step.shape != e_tok.shape:
        raise ValueError(
            f"step_s and e_token_j must be matching 1-D arrays, got "
            f"shapes {step.shape} and {e_tok.shape}")
    if len(step) and ((step <= 0).any() or not np.isfinite(step).all()):
        raise ValueError("step_s must be finite and > 0")
    if n_slots < 1:
        raise ValueError(f"n_slots must be >= 1, got {n_slots}")
    bk = resolve_backend(backend)
    n, r = len(step), trace.n_requests
    if n == 0 or r == 0:
        return FleetResult(
            trace=trace, n_slots=n_slots, n_iters=0, backend=bk,
            step_s=step, e_token_j=e_tok,
            submit_iter=np.full((n, r), -1, np.int64),
            comp_iter=np.zeros((n, r), np.int64),
            active_iters=np.zeros(n, np.int64))
    arrive = _arrival_iters(step, trace.arrival_s)
    svc = np.asarray(trace.service_iters, np.int64)
    drain = int(arrive.max()) + int(svc.sum()) + 1
    n_iters = drain if max_iters is None else min(int(max_iters), drain)
    if n_iters >= _INT32_MAX:
        raise ValueError(
            f"simulation horizon {n_iters} overflows int32; cap max_iters")
    with obs_trace.span("fleet.simulate", n=n, requests=r,
                        n_iters=n_iters, n_slots=n_slots, backend=bk):
        if bk == "jax":
            submit, comp, active = _simulate_jax(arrive, svc, n_slots,
                                                 n_iters)
        else:
            submit, comp, active = _simulate_numpy(arrive, svc, n_slots,
                                                   n_iters)
    res = FleetResult(trace=trace, n_slots=n_slots, n_iters=n_iters,
                      backend=bk, step_s=step, e_token_j=e_tok,
                      submit_iter=submit, comp_iter=comp,
                      active_iters=active)
    reg = obs_metrics.get_registry()
    reg.inc("fleet.simulations")
    reg.inc("fleet.candidates", n)
    served = res.served
    if served.size:
        reg.set("fleet.served_frac", float(served.mean()))
    return res


def simulate_fleet_scalar(step_s: float, e_token_j: float, traffic, *,
                          n_slots: int = 8,
                          max_iters: int | None = None) -> FleetResult:
    """Event-driven scalar reference for one candidate.

    Walks requests in FIFO order, admitting each into the
    earliest-freeing slot (lowest index on ties, matching the batcher's
    slot-order ``_admit``).  Arrivals are sorted and a freed slot's next
    admission is never earlier than the previous one's, so FIFO order is
    preserved without an explicit queue.  Must reproduce
    :func:`simulate_fleet`'s stamps bit-exactly (pinned by tests).
    """
    trace = resolve_traffic(traffic)
    r = trace.n_requests
    svc = np.asarray(trace.service_iters, np.int64)
    step = np.asarray([step_s], np.float64)
    e_tok = np.asarray([e_token_j], np.float64)
    if r == 0:
        return simulate_fleet(step, e_tok, trace, n_slots=n_slots,
                              max_iters=max_iters, backend="numpy")
    arrive = _arrival_iters(step, trace.arrival_s)[0]
    drain = int(arrive.max()) + int(svc.sum()) + 1
    n_iters = drain if max_iters is None else min(int(max_iters), drain)
    free_at = np.zeros(n_slots, np.int64)
    submit = np.full(r, -1, np.int64)
    comp = np.zeros(r, np.int64)
    busy_spans: list[tuple[int, int]] = []
    for i in range(r):
        slot = int(np.argmin(free_at))    # earliest free, lowest index
        start = max(int(arrive[i]), int(free_at[slot]))
        if start >= n_iters:
            break                         # horizon hit; rest never admitted
        submit[i] = start
        comp[i] = start + int(svc[i])
        free_at[slot] = comp[i]
        busy_spans.append((start, int(comp[i])))
    # active iterations = union of [start, end) spans clipped to horizon
    active = 0
    cur_s = cur_e = -1
    for s0, e0 in sorted(busy_spans):
        s0, e0 = s0, min(e0, n_iters)
        if s0 >= e0:
            continue
        if s0 > cur_e:
            active += cur_e - cur_s if cur_e > cur_s else 0
            cur_s, cur_e = s0, e0
        else:
            cur_e = max(cur_e, e0)
    active += cur_e - cur_s if cur_e > cur_s else 0
    return FleetResult(trace=trace, n_slots=n_slots, n_iters=n_iters,
                       backend="scalar", step_s=step, e_token_j=e_tok,
                       submit_iter=submit[None, :], comp_iter=comp[None, :],
                       active_iters=np.asarray([active], np.int64))
