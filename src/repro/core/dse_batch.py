"""Vectorized batched DSE sweep engine.

The scalar path in :mod:`repro.core.dse` evaluates ``O(configs x layers)``
Python calls per sweep.  This module evaluates the *whole* design space at
once: the config batch becomes struct-of-arrays form (one array per field
across all N design points, :func:`repro.core.accelerator.configs_to_soa`),
the workload becomes one array per layer field, and the row-stationary
mapping from :mod:`repro.core.dataflow` is re-expressed as broadcasted
``(N, L)`` array expressions.

The kernel is written against an ``xp`` array namespace and a dtype policy:

* ``exact=True`` (NumPy default) — int64/float64, op-for-op identical to
  :func:`repro.core.dataflow.map_layer`, so per-layer and aggregate
  results bit-match the scalar path (``tests/test_dse_batch.py``);
* ``exact=False`` — the **x64-free** policy used under ``jax.jit`` with
  jax's default config: spatial-mapping integers stay int32 (provably
  small), while anything that can overflow 31 bits — MAC counts, byte /
  element tallies, cycle counts, energies — is promoted to float32 with
  explicit ``floor`` where the exact path truncates, and the per-config
  reductions are Kahan-compensated.  Headline ratios agree with the exact
  path to ~1e-7 relative (asserted at 1e-6 in tests).

Backends resolve explicitly (``"auto" | "numpy" | "jax"``): ``"jax"``
raises if jax is unusable instead of silently falling back, and ``"auto"``
picks jax exactly when an accelerator platform is attached.  Under jax the
config axis can be sharded across devices via
:func:`repro.launch.mesh.make_sweep_mesh` (``mesh=...``).

For spaces too large to hold in memory, :func:`sweep_chunked` streams an
arbitrary-size config generator through the same kernel in bounded-memory
chunks with a running Pareto-front reduction, optionally backed by the
on-disk synthesis cache (:class:`repro.core.synthesis
.PersistentSynthesisCache`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Iterator, Sequence

import numpy as np

from repro.core.accelerator import (AcceleratorConfig, configs_to_soa,
                                    soa_to_configs)
from repro.core.dataflow import LayerResult, leakage_mw_soa
from repro.core.pe import rf_access_energy_pj, sram_access_energy_pj
from repro.core.synthesis import (PersistentSynthesisCache, SynthesisReport,
                                  sweep_synthesis_cache, synthesize_soa)
from repro.core.workloads import Workload
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


def _ceil_div(a, b):
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class WorkloadBatch:
    """Struct-of-arrays view of a workload: one int64 array per layer field,
    shape ``(L,)``."""

    name: str
    layer_names: tuple[str, ...]
    arrays: dict[str, np.ndarray]

    @classmethod
    def from_workload(cls, wl: Workload) -> "WorkloadBatch":
        i8 = np.int64
        ls = wl.layers
        arrays = {
            "r": np.array([l.r for l in ls], dtype=i8),
            "s": np.array([l.s for l in ls], dtype=i8),
            "e": np.array([l.e for l in ls], dtype=i8),
            "f": np.array([l.f for l in ls], dtype=i8),
            "c": np.array([l.c for l in ls], dtype=i8),
            "k": np.array([l.k for l in ls], dtype=i8),
            "h": np.array([l.h for l in ls], dtype=i8),
            "w": np.array([l.w for l in ls], dtype=i8),
            "batch": np.array([l.batch for l in ls], dtype=i8),
            "groups": np.array([l.groups for l in ls], dtype=i8),
            "macs": np.array([l.macs for l in ls], dtype=i8),
        }
        return cls(name=wl.name, layer_names=tuple(l.name for l in ls),
                   arrays=arrays)

    def __len__(self) -> int:
        return len(self.layer_names)


@functools.lru_cache(maxsize=64)
def _workload_batch(wl: Workload) -> WorkloadBatch:
    """SoA conversion cache — workloads are small frozen dataclasses, so
    repeat sweeps of the same model skip the per-layer array build."""
    return WorkloadBatch.from_workload(wl)


def _pack_block_key(cfg: dict) -> np.ndarray | None:
    """Pack the clock/bandwidth-independent config fields into one int64
    key per design point (for unique-row factorization of the kernel's
    mapping/byte block).  Returns None when the fields don't fit 63 bits
    — the caller then falls back to the direct per-config path, so an
    overflow can never alias two distinct configs."""
    fields = (cfg["pe_rows"], cfg["pe_cols"], cfg["act_bits"],
              cfg["weight_bits"], cfg["glb_kb"], cfg["filter_spad"],
              cfg["psum_spad"])
    cols = [np.asarray(a[:, 0]) for a in fields]
    bits = []
    for col in cols:
        lo, hi = int(col.min()), int(col.max())
        if lo < 0:
            return None
        bits.append(max(1, hi.bit_length()))
    if sum(bits) > 63:
        return None
    key = np.zeros_like(cols[0])
    for col, b in zip(cols, bits):
        key = (key << b) | col
    return key


def _kahan_sum_rows(xp, x, dtype):
    """Sequential compensated row-sum over the layer axis.

    The exact path needs plain sequential adds (bit-matching ``sum()``);
    the float32 path compensates so L-layer accumulation error stays at
    one-ulp instead of L ulps."""
    total = xp.zeros(x.shape[0], dtype=dtype)
    comp = xp.zeros(x.shape[0], dtype=dtype)
    for j in range(x.shape[1]):
        y = x[:, j] - comp
        t = total + y
        comp = (t - total) - y
        total = t
    return total


# per-config aggregate output columns — the only outputs the search loop
# and the streamed Pareto reduction need; with ``outputs="aggregates"`` the
# kernel returns just these, so under jax.jit XLA dead-code-eliminates every
# (N, L) layer-level intermediate and the device->host transfer shrinks to
# O(N) (ROADMAP open item)
AGGREGATE_OUTPUTS = ("total_cycles_sum", "energy_pj_sum", "latency_s",
                     "energy_j", "throughput_gmacs", "perf_per_area")
# the (N, L) columns the multi-workload segment reduction consumes — with
# ``outputs="layer_totals"`` the kernel returns only these two, so XLA can
# DCE every other layer-level intermediate before the per-workload sums
LAYER_TOTAL_OUTPUTS = ("total_cycles", "energy_pj")
OUTPUT_MODES = ("full", "aggregates", "layer_totals")


def _sweep_kernel(xp, cfg: dict, lay: dict, *, exact: bool = True,
                  outputs: str = "full") -> dict:
    """All-configs x all-layers row-stationary mapping + energy model.

    ``cfg`` holds ``(N, 1)`` arrays, ``lay`` holds ``(1, L)`` arrays; every
    expression broadcasts to ``(N, L)``.  ``exact=True`` mirrors
    ``map_layer`` bit-for-bit; ``exact=False`` is the x64-free dtype-safe
    policy (see module docstring).

    Mixed precision: the ``act_bits`` / ``weight_bits`` / ``mac_energy_pj``
    config columns may be ``(N, L)`` instead of ``(N, 1)`` — one execution
    mode per (config, layer), see :func:`sweep_mixed`.  The same broadcast
    expressions cover both shapes, so a homogeneous assignment is
    bit-identical to the per-config-scalar path.

    ``outputs="aggregates"`` returns only :data:`AGGREGATE_OUTPUTS`.

    Grouped layers (``lay["groups"]``) follow ``map_layer``: the mapping
    and byte block is one group's, cycles, DRAM bytes and dynamic energy
    are scaled by the group count, and ``macs`` is the whole layer's.
    """
    f = np.float64 if exact else np.float32
    r, e, f_, ss = lay["r"], lay["e"], lay["f"], lay["s"]
    c, k, n = lay["c"], lay["k"], lay["batch"]
    g = lay["groups"]
    macs = lay["macs"]          # int64 when exact, float32 otherwise

    def fl(x):                  # promote a (possibly int) array to f
        return x.astype(f)

    macs_one = macs // g if exact else macs / fl(g)

    # The mapping / byte-count / GLB-traffic block depends on the config
    # only through (pe_rows, pe_cols, act_bits, weight_bits, glb_kb,
    # filter_spad, psum_spad) — NOT through bandwidth or the synthesized
    # clock.  Factorial design spaces repeat those key fields across
    # thousands of configs (e.g. 240 unique vs 720 points in the paper
    # space), so on the eager numpy path we evaluate the block once per
    # *unique* key row and gather — a bit-identical copy of the same
    # values at a fraction of the (N, L) op count.  The jax path keeps the
    # direct form (np.unique doesn't trace; jit fuses instead).
    _BLOCK_FIELDS = ("pe_rows", "pe_cols", "num_pes", "act_bits",
                     "weight_bits", "glb_kb", "filter_spad", "psum_spad")
    # per-layer precision columns make the block layer-dependent, so the
    # unique-row factorization only applies to homogeneous batches
    homogeneous = all(cfg[k2].shape[1] == 1
                      for k2 in ("act_bits", "weight_bits", "mac_energy_pj"))
    inv = None
    if exact and xp is np and homogeneous and cfg["pe_rows"].shape[0] > 16:
        key = _pack_block_key(cfg)
        if key is not None:
            _, uidx, inv = np.unique(key, return_index=True,
                                     return_inverse=True)
            inv = inv.reshape(-1)
            if len(uidx) == len(key):
                inv = None                  # all distinct: nothing to save
    cb = cfg if inv is None else {k2: cfg[k2][uidx] for k2 in _BLOCK_FIELDS}

    # ---- spatial mapping (small integers: int64 exact / int32 safe) --------
    sets_fit = xp.maximum(1, cb["pe_rows"] // r)
    c_simult = xp.minimum(c, sets_fit)
    k_simult = xp.maximum(1, sets_fit // c_simult)
    fit_horz = xp.minimum(e, cb["pe_cols"])
    n_e_groups = _ceil_div(e, fit_horz)
    n_c_groups = _ceil_div(c, c_simult)
    n_k_groups = _ceil_div(k, k_simult)

    if exact:
        passes = n * n_e_groups * n_c_groups * n_k_groups
        # int multiply is associative: fold the (1, L) factors first so
        # only one product runs per row — value identical to map_layer
        compute_cycles = passes * (g * ss * f_)
        utilization = macs / xp.maximum(1, compute_cycles * cb["num_pes"])
    else:
        # group products can pass 2**31 — promote the accumulator only
        compute_cycles = (fl(n) * fl(n_e_groups) * fl(n_c_groups)
                          * fl(n_k_groups) * fl(ss) * fl(f_) * fl(g))
        utilization = macs / xp.maximum(
            f(1.0), compute_cycles * fl(cb["num_pes"]))

    # ---- element / byte counts (quantization-aware) -------------------------
    ab, wb = cb["act_bits"], cb["weight_bits"]
    ifmap_elems = n * c * lay["h"] * lay["w"]
    weight_elems = k * c * r * ss
    ofmap_elems = n * k * e * f_
    if exact:
        ifmap_bytes = ifmap_elems * ab // 8
        weight_bytes = weight_elems * wb // 8
        ofmap_bytes = ofmap_elems * ab // 8
    else:
        # elems * bits exceeds int32; float32 with explicit truncation
        ifmap_bytes = xp.floor(fl(ifmap_elems) * fl(ab) / 8.0)
        weight_bytes = xp.floor(fl(weight_elems) * fl(wb) / 8.0)
        ofmap_bytes = xp.floor(fl(ofmap_elems) * fl(ab) / 8.0)

    glb_half = cb["glb_kb"] * 1024 // 2
    filt_bytes_one = xp.maximum(1, c * r * ss * wb // 8)
    k_fit_glb = xp.maximum(1, glb_half // filt_bytes_one)
    n_k_glb = _ceil_div(k, k_fit_glb)
    if exact:
        ifmap_restream = xp.where(ifmap_bytes <= glb_half, 1, n_k_glb)
        ifmap_dram = ifmap_bytes * ifmap_restream
        dram_bytes = g * (ifmap_dram + weight_bytes + ofmap_bytes)
        dram_elems = ifmap_elems * ifmap_restream + weight_elems \
            + ofmap_elems
    else:
        ifmap_restream = xp.where(ifmap_bytes <= fl(glb_half),
                                  f(1.0), fl(n_k_glb))
        dram_bytes = fl(g) * (ifmap_bytes * ifmap_restream + weight_bytes
                              + ofmap_bytes)
        dram_elems = fl(ifmap_elems) * ifmap_restream + fl(weight_elems) \
            + fl(ofmap_elems)

    # map_layer computes this subexpression twice with identical value;
    # evaluate once and share
    filt_res = xp.maximum(1, cb["filter_spad"] // xp.maximum(1, ss))
    k_res = filt_res
    w_res = xp.minimum(n_e_groups, filt_res)
    psum_strip = f_
    spill = xp.where(cb["psum_spad"] >= psum_strip, 0, n_c_groups - 1)
    if exact:
        glb_ifmap = ifmap_elems * _ceil_div(n_k_groups, k_res)
        glb_weight = weight_elems * xp.maximum(1, n_e_groups // w_res)
        glb_psum = 2 * ofmap_elems * xp.maximum(0, spill)
        glb_elems = 2 * dram_elems + glb_ifmap + glb_weight + glb_psum
        glb_bytes = g * glb_elems * ab // 8
    else:
        glb_ifmap = fl(ifmap_elems) * fl(_ceil_div(n_k_groups, k_res))
        glb_weight = fl(weight_elems) * fl(xp.maximum(1, n_e_groups // w_res))
        glb_psum = 2.0 * fl(ofmap_elems) * fl(xp.maximum(0, spill))
        glb_elems = 2.0 * dram_elems + glb_ifmap + glb_weight + glb_psum
        glb_bytes = xp.floor(fl(g) * glb_elems * fl(ab) / 8.0)

    if inv is not None:                     # scatter back to all N configs
        compute_cycles = compute_cycles[inv]
        utilization = utilization[inv]
        dram_bytes = dram_bytes[inv]
        glb_elems = glb_elems[inv]
        glb_bytes = glb_bytes[inv]

    # ---- stalls -------------------------------------------------------------
    clock_ghz = cfg["clock_ghz"]
    bw_bytes_per_cycle = cfg["dram_bw_gbps"] / clock_ghz
    if exact:
        mem_cycles = (dram_bytes
                      / xp.maximum(1e-9, bw_bytes_per_cycle)
                      ).astype(np.int64)
        total_cycles = xp.maximum(compute_cycles, mem_cycles)
    else:
        mem_cycles = xp.floor(dram_bytes
                              / xp.maximum(f(1e-9), bw_bytes_per_cycle))
        total_cycles = xp.maximum(compute_cycles, mem_cycles)

    # ---- energy -------------------------------------------------------------
    # the pe.py cost helpers are numpy-ufunc based, so they broadcast over
    # the batch (and trace under jax.jit) — single source for the constants
    e_spad_pj = rf_access_energy_pj(cfg["spad_bits"], xp=xp)
    spad_accesses = 3 * macs
    e_spad = 3 * macs_one * e_spad_pj
    e_mac = macs_one * cfg["mac_energy_pj"]
    e_glb_pj = sram_access_energy_pj(cfg["glb_bits"], xp=xp)
    e_glb = glb_elems * e_glb_pj
    e_leak = cfg["leak_mw"] * 1e-3 \
        * (total_cycles / (clock_ghz * 1e9)) * 1e12
    energy_pj = fl(g) * (e_mac + e_spad + e_glb) + e_leak

    if outputs == "layer_totals":
        # the segmented multi-workload reduction happens in the caller
        return {"total_cycles": total_cycles, "energy_pj": energy_pj}

    # ---- per-config aggregates ---------------------------------------------
    if exact:
        # sequential over L to bit-match the scalar sum()
        n_layers = energy_pj.shape[1]
        energy_sum = xp.zeros(energy_pj.shape[0], dtype=np.float64)
        for j in range(n_layers):
            energy_sum = energy_sum + energy_pj[:, j]
        total_cycles_sum = xp.sum(total_cycles, axis=1)
    else:
        energy_sum = _kahan_sum_rows(xp, energy_pj, f)
        total_cycles_sum = _kahan_sum_rows(xp, total_cycles, f)
    total_macs = xp.sum(macs)

    clk = clock_ghz[:, 0]
    latency_s = total_cycles_sum / (clk * 1e9)
    energy_j = energy_sum / 1e12
    throughput_gmacs = total_macs / latency_s / 1e9
    perf_per_area = throughput_gmacs / cfg["area_mm2"][:, 0]

    out = {
        "compute_cycles": compute_cycles, "mem_cycles": mem_cycles,
        "total_cycles": total_cycles, "utilization": utilization,
        "spad_accesses": spad_accesses, "glb_bytes": glb_bytes,
        "dram_bytes": dram_bytes, "energy_pj": energy_pj,
        "total_cycles_sum": total_cycles_sum, "energy_pj_sum": energy_sum,
        "latency_s": latency_s, "energy_j": energy_j,
        "throughput_gmacs": throughput_gmacs, "perf_per_area": perf_per_area,
    }
    if outputs == "aggregates":
        return {k: out[k] for k in AGGREGATE_OUTPUTS}
    return out


# ---------------------------------------------------------------------------
# Backend resolution
# ---------------------------------------------------------------------------

BACKENDS = ("auto", "numpy", "jax")


def _probe_jax() -> tuple[bool, str]:
    try:
        import jax
        jax.devices()
    except Exception as exc:  # import error, no platform, bad install...
        return False, f"{type(exc).__name__}: {exc}"
    return True, ""


_JAX_PROBE: tuple[bool, str] | None = None


def _jax_usable() -> tuple[bool, str]:
    global _JAX_PROBE
    if _JAX_PROBE is None:
        _JAX_PROBE = _probe_jax()
    return _JAX_PROBE


def _jax_has_accelerator() -> bool:
    import jax
    return any(d.platform != "cpu" for d in jax.devices())


def resolve_backend(backend: str = "auto") -> str:
    """Resolve ``"auto" | "numpy" | "jax"`` to a concrete engine.

    Explicit ``"jax"`` **raises** when jax is unusable — no silent numpy
    fallback.  ``"auto"`` picks jax exactly when an accelerator platform
    (GPU/TPU) is attached; on CPU NumPy is both faster to dispatch and
    bit-exact against the scalar reference, so it wins the tie.
    """
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown sweep backend: {backend!r} (choose from {BACKENDS})")
    if backend == "numpy":
        return "numpy"
    usable, why = _jax_usable()
    if backend == "jax":
        if not usable:
            raise RuntimeError(
                f"sweep backend 'jax' requested but jax is unusable ({why})")
        return "jax"
    return "jax" if usable and _jax_has_accelerator() else "numpy"


def resolve_use_pallas(use_pallas: bool | None, backend: str,
                       mesh=None) -> bool:
    """Resolve the ``use_pallas`` routing flag against a *resolved*
    backend.

    ``None`` (auto) engages the Pallas sweep kernel exactly when the jax
    backend is active on a real accelerator platform without ``mesh``
    sharding — on CPU the interpreter-mode kernel is for parity testing,
    not production throughput, so auto keeps the jitted XLA path.
    Explicit ``True`` raises instead of silently falling back when the
    backend can't honor it (numpy, or a sharded mesh — the Pallas kernel
    owns its own tiling and doesn't compose with ``shard_map`` yet).
    """
    if use_pallas is None:
        return (backend == "jax" and mesh is None
                and _jax_usable()[0] and _jax_has_accelerator())
    use_pallas = bool(use_pallas)
    if use_pallas and backend != "jax":
        raise ValueError(
            f"use_pallas=True requires the jax backend, but the sweep "
            f"resolved to backend={backend!r}")
    if use_pallas and mesh is not None:
        raise ValueError(
            "use_pallas=True does not compose with mesh= sharding yet; "
            "drop mesh= or use_pallas")
    return use_pallas


# ---------------------------------------------------------------------------
# jax path: jit cache + x64-free input conversion + optional shard_map
# ---------------------------------------------------------------------------

_JAX_KERNELS: dict = {}

# int32-safe cfg/lay fields under the x64-free policy; everything else
# (counts that can pass 2**31, float quantities) converts to float32
_CFG_INT32 = ("pe_rows", "pe_cols", "ifmap_spad", "filter_spad",
              "psum_spad", "glb_kb", "glb_bits", "num_pes", "act_bits",
              "weight_bits", "spad_bits")
_LAY_INT32 = ("r", "s", "e", "f", "c", "k", "h", "w", "batch", "groups")


def _to_jax_inputs(cfg: dict, lay: dict, exact: bool) -> tuple[dict, dict]:
    if exact:
        return cfg, lay
    jcfg = {k: (v.astype(np.int32) if k in _CFG_INT32
                else v.astype(np.float32)) for k, v in cfg.items()}
    jlay = {k: (v.astype(np.int32) if k in _LAY_INT32
                else v.astype(np.float32)) for k, v in lay.items()}
    return jcfg, jlay


def get_jax_kernel(mesh=None, outputs: str = "full"):
    """The jit-compiled sweep kernel for the current jax config.

    Compiled once per (x64-mode, mesh, outputs) and cached — repeat sweeps
    over same-shape batches hit the jit cache with zero retraces (asserted
    in tests via ``_cache_size``).  With ``mesh``, the config axis is
    sharded across the mesh's devices via ``shard_map``; layer arrays are
    replicated.  ``outputs="aggregates"`` jits the aggregates-only kernel,
    whose (N, L) intermediates XLA dead-code-eliminates.
    """
    import jax
    import jax.numpy as jnp

    exact = bool(jax.config.read("jax_enable_x64"))
    key = (exact, _mesh_cache_key(mesh), outputs)
    fn = _JAX_KERNELS.get(key)
    if fn is not None:
        return fn, exact

    def kernel(cfg, lay):
        return _sweep_kernel(jnp, cfg, lay, exact=exact, outputs=outputs)

    if mesh is None:
        fn = jax.jit(kernel)
    else:
        P = jax.sharding.PartitionSpec

        def sharded(cfg, lay):
            n = cfg["pe_rows"].shape[0]
            cfg_specs = {k: P("configs", None) for k in cfg}
            lay_specs = {k: P(None, None) for k in lay}
            shapes = jax.eval_shape(kernel, cfg, lay)
            # config-major outputs shard; (1, L) layer stats and 0-d
            # scalars replicate
            out_specs = {
                k: (P("configs", *([None] * (s.ndim - 1)))
                    if s.ndim >= 1 and s.shape[0] == n
                    else P(*([None] * s.ndim)))
                for k, s in shapes.items()}
            # replicated (1, L) layer stats are beyond the checker
            return jax.shard_map(
                kernel, mesh=mesh, in_specs=(cfg_specs, lay_specs),
                out_specs=out_specs, check_vma=False)(cfg, lay)

        fn = jax.jit(sharded)
    _JAX_KERNELS[key] = fn
    return fn, exact


def _run_kernel(cfg: dict, lay: dict, backend: str,
                mesh=None, outputs: str = "full",
                use_pallas: bool = False) -> dict[str, np.ndarray]:
    if outputs not in OUTPUT_MODES:
        raise ValueError(
            f"unknown sweep outputs: {outputs!r} (choose from "
            f"{OUTPUT_MODES})")
    if backend == "jax" and use_pallas and outputs == "aggregates" \
            and mesh is None:
        # the Pallas kernel covers the aggregate-reduction path (the only
        # one the streamed/search hot loops use); per-layer output modes
        # keep the jitted XLA kernel
        from repro.kernels.sweep_kernel import sweep_aggregates_pallas
        out = sweep_aggregates_pallas(cfg, lay)
        with obs_trace.span("kernel.wait"):
            return {k: np.asarray(v) for k, v in out.items()}
    if backend == "jax":
        _require_jax_mesh(mesh)
        fn, exact = get_jax_kernel(mesh, outputs)
        # under the x64-free policy "macs" lands in float32 via
        # _to_jax_inputs (it feeds only float math in the kernel)
        jcfg, jlay = _to_jax_inputs(cfg, lay, exact)
        n = cfg["pe_rows"].shape[0]
        if mesh is not None:
            jcfg = _pad_rows(jcfg, -n % _mesh_shards(mesh))
        out = {k: np.asarray(v)[:n] if np.ndim(v) else np.asarray(v)
               for k, v in fn(jcfg, jlay).items()}
        return out
    return _sweep_kernel(np, cfg, lay, outputs=outputs)


@dataclasses.dataclass
class BatchedSweep:
    """One evaluated sweep: N configs x L layers, all results as arrays.

    ``DSEPoint``/``DSEResult`` in :mod:`repro.core.dse` are thin views over
    this; nothing here is materialized per-point unless asked for.
    """

    workload: str
    configs: tuple[AcceleratorConfig, ...]
    layer_names: tuple[str, ...]
    macs: np.ndarray               # (L,)
    clock_ghz: np.ndarray          # (N,)
    area_mm2: np.ndarray           # (N,)
    arrays: dict[str, np.ndarray]  # kernel outputs

    def __len__(self) -> int:
        return len(self.configs)

    def result_view(self, i: int) -> "BatchedWorkloadResult":
        return BatchedWorkloadResult(self, i)


class BatchedWorkloadResult:
    """Duck-typed :class:`repro.core.dataflow.WorkloadResult` view over one
    row of a :class:`BatchedSweep` — O(1) until ``.layers`` is asked for."""

    __slots__ = ("_sweep", "_i", "_layers")

    def __init__(self, sweep: BatchedSweep, i: int):
        self._sweep = sweep
        self._i = i
        self._layers: tuple[LayerResult, ...] | None = None

    # ---- identity fields ---------------------------------------------------
    @property
    def workload(self) -> str:
        return self._sweep.workload

    @property
    def config_name(self) -> str:
        return self._sweep.configs[self._i].name()

    @property
    def area_mm2(self) -> float:
        return float(self._sweep.area_mm2[self._i])

    @property
    def clock_ghz(self) -> float:
        return float(self._sweep.clock_ghz[self._i])

    # ---- per-layer materialization (lazy) ----------------------------------
    @property
    def layers(self) -> tuple[LayerResult, ...]:
        if self._layers is None:
            a, i = self._sweep.arrays, self._i
            self._layers = tuple(
                LayerResult(
                    name=nm, macs=int(self._sweep.macs[j]),
                    compute_cycles=int(a["compute_cycles"][i, j]),
                    mem_cycles=int(a["mem_cycles"][i, j]),
                    total_cycles=int(a["total_cycles"][i, j]),
                    utilization=float(a["utilization"][i, j]),
                    spad_accesses=int(a["spad_accesses"][0, j]),
                    glb_bytes=int(a["glb_bytes"][i, j]),
                    dram_bytes=int(a["dram_bytes"][i, j]),
                    energy_pj=float(a["energy_pj"][i, j]),
                )
                for j, nm in enumerate(self._sweep.layer_names))
        return self._layers

    # ---- aggregates (precomputed in the kernel) ----------------------------
    @property
    def total_macs(self) -> int:
        return int(self._sweep.macs.sum())

    @property
    def total_cycles(self) -> int:
        return int(self._sweep.arrays["total_cycles_sum"][self._i])

    @property
    def latency_s(self) -> float:
        return float(self._sweep.arrays["latency_s"][self._i])

    @property
    def energy_j(self) -> float:
        return float(self._sweep.arrays["energy_j"][self._i])

    @property
    def throughput_gmacs(self) -> float:
        return float(self._sweep.arrays["throughput_gmacs"][self._i])

    @property
    def perf_per_area(self) -> float:
        return float(self._sweep.arrays["perf_per_area"][self._i])

    @property
    def edp(self) -> float:
        return self.energy_j * self.latency_s


def _reports_to_cols(reports) -> dict[str, np.ndarray]:
    """Accept synthesis results as a report list *or* column dict."""
    if isinstance(reports, dict):
        return reports
    return {
        "clock_ghz": np.array([r.clock_ghz for r in reports],
                              dtype=np.float64),
        "area_mm2": np.array([r.area_mm2 for r in reports],
                             dtype=np.float64),
    }


def _make_cfg_lay(soa: dict, cols: dict, wb: WorkloadBatch
                  ) -> tuple[dict, dict]:
    leak_mw = leakage_mw_soa(soa)
    cfg = {k: soa[k][:, None] for k in
           ("pe_rows", "pe_cols", "ifmap_spad", "filter_spad", "psum_spad",
            "glb_kb", "glb_bits", "num_pes", "act_bits", "weight_bits",
            "spad_bits", "dram_bw_gbps", "mac_energy_pj")}
    cfg["clock_ghz"] = np.asarray(cols["clock_ghz"],
                                  dtype=np.float64)[:, None]
    cfg["area_mm2"] = np.asarray(cols["area_mm2"], dtype=np.float64)[:, None]
    cfg["leak_mw"] = leak_mw[:, None]
    lay = {k: v[None, :] for k, v in wb.arrays.items()}
    return cfg, lay


def _sweep_workload(workload: Workload,
                    configs: Sequence[AcceleratorConfig],
                    reports: Sequence[SynthesisReport] | dict | None = None,
                    *,
                    use_cache: bool = True,
                    backend: str = "auto",
                    soa: dict[str, np.ndarray] | None = None,
                    mesh=None,
                    outputs: str = "full",
                    use_pallas: bool | None = None) -> BatchedSweep:
    """Evaluate ``workload`` on every config in one batched pass.

    ``reports``/``soa`` let :func:`repro.core.dse.explore_many` synthesize
    and SoA-convert once and reuse across workloads; ``reports`` may be a
    list of :class:`SynthesisReport` or a column dict from
    :func:`repro.core.synthesis.synthesize_soa`.

    ``outputs="aggregates"`` keeps only the per-config columns
    (:data:`AGGREGATE_OUTPUTS`): the result's per-point views still serve
    every aggregate metric, but ``.layers`` is unavailable.
    """
    backend = resolve_backend(backend)
    use_pallas = resolve_use_pallas(use_pallas, backend, mesh)
    configs = tuple(configs)
    if soa is None:
        soa = configs_to_soa(configs)
    if reports is None:
        cols = (sweep_synthesis_cache().synthesize(soa) if use_cache
                else synthesize_soa(soa))
    else:
        cols = _reports_to_cols(reports)
    wb = _workload_batch(workload)
    cfg, lay = _make_cfg_lay(soa, cols, wb)
    out = _run_kernel(cfg, lay, backend, mesh=mesh, outputs=outputs,
                      use_pallas=use_pallas)
    return BatchedSweep(workload=workload.name, configs=configs,
                        layer_names=wb.layer_names, macs=wb.arrays["macs"],
                        clock_ghz=cfg["clock_ghz"][:, 0],
                        area_mm2=cfg["area_mm2"][:, 0], arrays=out)


# ---------------------------------------------------------------------------
# Mixed-precision sweep: one execution mode per (config, layer)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _mode_tables() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-PE-type (act_bits, weight_bits, mac_energy_pj) lookup tables,
    indexed by the canonical ``tuple(PEType)`` order."""
    from repro.core.pe import PEType, pe_spec
    specs = [pe_spec(t) for t in PEType]
    return (np.array([s.act_bits for s in specs], dtype=np.int64),
            np.array([s.weight_bits for s in specs], dtype=np.int64),
            np.array([s.mac_energy_pj for s in specs], dtype=np.float64))


def mixed_assign_cfg(cfg: dict, assign: np.ndarray) -> dict:
    """Replace the per-config scalar precision columns with per-layer ones.

    ``assign`` is an ``(N, L)`` int array of PE-type indices (canonical
    ``tuple(PEType)`` order).  Only ``act_bits`` / ``weight_bits`` /
    ``mac_energy_pj`` become ``(N, L)``; everything physical (array dims,
    scratchpad storage, clock, area, leakage) keeps its hardware value, so
    synthesis — and its confighash-keyed caches — see only the hardware
    config.
    """
    ab_t, wb_t, me_t = _mode_tables()
    a = np.asarray(assign, dtype=np.int64)
    if a.size and (a.min() < 0 or a.max() >= len(ab_t)):
        raise ValueError(
            f"assignment contains PE-type indices outside "
            f"[0, {len(ab_t)})")
    out = dict(cfg)
    out["act_bits"] = ab_t[a]
    out["weight_bits"] = wb_t[a]
    out["mac_energy_pj"] = me_t[a]
    return out


def check_assignment(soa: dict, assign: np.ndarray) -> None:
    """Raise ``ValueError`` unless every (config, layer) mode is executable
    on that config's hardware (operand widths fit the datapath)."""
    from repro.core.pe import PEType, mode_compat_matrix
    a = np.asarray(assign)
    n_types = len(tuple(PEType))
    if a.ndim != 2 or a.shape[0] != len(soa["pe_rows"]):
        raise ValueError(
            f"assignment shape {a.shape} does not match "
            f"{len(soa['pe_rows'])} configs")
    if a.min(initial=0) < 0 or a.max(initial=0) >= n_types:
        raise ValueError(
            f"assignment contains PE-type indices outside [0, {n_types})")
    ok = mode_compat_matrix()[soa["pe_type_idx"][:, None], a]
    if not ok.all():
        n_bad = int((~ok).sum())
        raise ValueError(
            f"{n_bad} (config, layer) mode assignment(s) are not "
            f"executable on their hardware PE type")


def _sweep_mixed(workload: Workload,
                 soa: dict[str, np.ndarray],
                 assign: np.ndarray,
                 cols: dict[str, np.ndarray] | None = None,
                 *,
                 use_cache: bool = True,
                 backend: str = "auto",
                 outputs: str = "aggregates",
                 mesh=None,
                 use_pallas: bool | None = None) -> dict[str, np.ndarray]:
    """Evaluate a batch of mixed-precision genomes in one fused pass.

    ``soa`` is the hardware half of the genome batch
    (:func:`repro.core.accelerator.soa_from_fields`), ``assign`` the
    ``(N, L)`` per-layer execution-mode half.  Synthesis runs on the
    hardware configs alone — through the digest-keyed sweep cache by
    default, so re-visited hardware (the common case in an evolutionary
    search) skips the flow entirely.  Returns the kernel output columns
    plus ``clock_ghz`` / ``area_mm2``; numpy results are bit-exact vs
    :func:`repro.core.dataflow.run_workload_mixed` row by row.
    """
    backend = resolve_backend(backend)
    use_pallas = resolve_use_pallas(use_pallas, backend, mesh)
    wb = _workload_batch(workload)
    assign = np.asarray(assign, dtype=np.int64)
    if assign.shape != (len(soa["pe_rows"]), len(wb)):
        raise ValueError(
            f"assignment shape {assign.shape} != "
            f"({len(soa['pe_rows'])} configs, {len(wb)} layers)")
    check_assignment(soa, assign)
    if cols is None:
        cols = (sweep_synthesis_cache().synthesize(soa) if use_cache
                else synthesize_soa(soa))
    cfg, lay = _make_cfg_lay(soa, cols, wb)
    cfg = mixed_assign_cfg(cfg, assign)
    out = dict(_run_kernel(cfg, lay, backend, mesh=mesh, outputs=outputs,
                           use_pallas=use_pallas))
    out["clock_ghz"] = cfg["clock_ghz"][:, 0]
    out["area_mm2"] = cfg["area_mm2"][:, 0]
    return out


# ---------------------------------------------------------------------------
# Multi-workload mixed-precision sweep: W workloads per genome batch, one
# fused kernel call, synthesis shared per hardware digest
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _workload_batch_many(wls: tuple[Workload, ...]
                         ) -> tuple[WorkloadBatch, tuple[tuple[int, int], ...]]:
    """Concatenate W workloads into one layer-axis batch plus the
    ``(start, end)`` column bounds of each workload's segment."""
    wbs = [_workload_batch(w) for w in wls]
    bounds: list[tuple[int, int]] = []
    start = 0
    for wb in wbs:
        bounds.append((start, start + len(wb)))
        start += len(wb)
    arrays = {k: np.concatenate([wb.arrays[k] for wb in wbs])
              for k in wbs[0].arrays}
    names = tuple(f"{wb.name}/{nm}" for wb in wbs for nm in wb.layer_names)
    combined = WorkloadBatch(name="+".join(wb.name for wb in wbs),
                             layer_names=names, arrays=arrays)
    return combined, tuple(bounds)


def _segment_aggregates(xp, totals: dict, cfg: dict, lay: dict,
                        bounds: tuple[tuple[int, int], ...],
                        exact: bool) -> dict:
    """Per-workload aggregate columns from the combined layer axis.

    Mirrors the single-workload kernel's aggregate block op-for-op on each
    ``[start, end)`` segment, so workload ``w``'s row is bit-identical
    (exact path) to running that workload through :func:`sweep_mixed`
    alone.  Returns ``{column: (W, N)}`` over :data:`AGGREGATE_OUTPUTS`.
    """
    f = np.float64 if exact else np.float32
    tc, ep = totals["total_cycles"], totals["energy_pj"]
    clk = cfg["clock_ghz"][:, 0]
    area = cfg["area_mm2"][:, 0]
    rows: dict[str, list] = {k: [] for k in AGGREGATE_OUTPUTS}
    for s, e in bounds:
        epw, tcw = ep[:, s:e], tc[:, s:e]
        if exact:
            energy_sum = xp.zeros(epw.shape[0], dtype=np.float64)
            for j in range(epw.shape[1]):
                energy_sum = energy_sum + epw[:, j]
            cycles_sum = xp.sum(tcw, axis=1)
        else:
            energy_sum = _kahan_sum_rows(xp, epw, f)
            cycles_sum = _kahan_sum_rows(xp, tcw, f)
        total_macs = xp.sum(lay["macs"][:, s:e])
        latency_s = cycles_sum / (clk * 1e9)
        energy_j = energy_sum / 1e12
        throughput_gmacs = total_macs / latency_s / 1e9
        perf_per_area = throughput_gmacs / area
        for k, v in zip(AGGREGATE_OUTPUTS,
                        (cycles_sum, energy_sum, latency_s, energy_j,
                         throughput_gmacs, perf_per_area)):
            rows[k].append(v)
    return {k: xp.stack(v, axis=0) for k, v in rows.items()}


_JAX_MANY_KERNELS: dict = {}


def _mesh_shards(mesh) -> int:
    """Config-axis shard count implied by a ``mesh=`` argument: ``None``
    -> 1, an int -> itself (the numpy backend's simulated shard count),
    a ``jax.sharding.Mesh`` -> its device count.  Pure attribute access —
    never imports jax."""
    if mesh is None:
        return 1
    if isinstance(mesh, int):
        if mesh < 1:
            raise ValueError(f"mesh shard count must be >= 1, got {mesh}")
        return mesh
    return int(mesh.devices.size)


def _require_jax_mesh(mesh) -> None:
    if isinstance(mesh, int):
        raise ValueError(
            "backend='jax' needs a jax.sharding.Mesh for mesh=, not "
            "an int shard count (see repro.launch.mesh.make_sweep_mesh)")


def _mesh_cache_key(mesh):
    """Key a mesh by value (axes + device ids), not identity: fresh but
    equivalent meshes reuse one compiled kernel instead of growing the
    jit caches (and pinning executables) without bound."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), mesh.devices.shape,
            tuple(d.id for d in mesh.devices.flat))


def _pad_rows(arrays: dict, pad: int) -> dict:
    """Repeat each array's last row ``pad`` times (row-local kernels make
    the padded rows valid throwaway work; callers slice them back off)."""
    if pad <= 0:
        return arrays
    return {k: np.concatenate([v, v[-1:].repeat(pad, axis=0)])
            for k, v in arrays.items()}


def get_jax_many_kernel(bounds: tuple[tuple[int, int], ...], mesh=None):
    """Jit-compiled multi-workload kernel, cached per (x64-mode, segment
    bounds, mesh): the layer mapping runs once over the concatenated layer
    axis and the per-workload reductions happen inside the same jit, so
    XLA fuses everything into one dispatch and DCEs the (N, L)
    intermediates.  With ``mesh`` the config axis is sharded across the
    mesh's devices via ``shard_map`` — every (config, layer) expression
    and the per-workload segment reductions are row-local, so each device
    reduces its own config shard independently and the stacked ``(W, n)``
    aggregate columns concatenate along the config axis with no
    cross-device collectives at all."""
    import jax
    import jax.numpy as jnp

    exact = bool(jax.config.read("jax_enable_x64"))
    key = (exact, bounds, _mesh_cache_key(mesh))
    fn = _JAX_MANY_KERNELS.get(key)
    if fn is None:
        def kernel(cfg, lay):
            totals = _sweep_kernel(jnp, cfg, lay, exact=exact,
                                   outputs="layer_totals")
            return _segment_aggregates(jnp, totals, cfg, lay, bounds,
                                       exact=exact)

        if mesh is None:
            fn = jax.jit(kernel)
        else:
            P = jax.sharding.PartitionSpec

            def sharded(cfg, lay):
                cfg_specs = {k: P("configs", None) for k in cfg}
                lay_specs = {k: P(None, None) for k in lay}
                # every output is a (W, n_local) stack of per-workload
                # aggregates — config-major on axis 1
                out_specs = {k: P(None, "configs")
                             for k in AGGREGATE_OUTPUTS}
                return jax.shard_map(
                    kernel, mesh=mesh,
                    in_specs=(cfg_specs, lay_specs),
                    out_specs=out_specs, check_vma=False)(cfg, lay)

            fn = jax.jit(sharded)
        _JAX_MANY_KERNELS[key] = fn
    return fn, exact


def _sweep_mixed_many(workloads: Sequence[Workload],
                      soa: dict[str, np.ndarray],
                      assigns: Sequence[np.ndarray],
                      cols: dict[str, np.ndarray] | None = None,
                      *,
                      use_cache: bool = True,
                      backend: str = "auto",
                      mesh=None,
                      use_pallas: bool | None = None
                      ) -> dict[str, np.ndarray]:
    """Evaluate one genome batch against W workloads in one fused pass.

    ``soa`` is the shared hardware half (N configs); ``assigns`` holds one
    ``(N, L_w)`` per-layer mode matrix per workload — the per-workload
    precision assignment of the QUIDAM co-exploration setting.  The W
    workloads' layer axes are concatenated into a single ``(N, sum L_w)``
    kernel evaluation (layers are independent under the row-stationary
    mapping), then reduced per workload segment, so the whole call costs
    one synthesis pass + one kernel dispatch regardless of W.  Synthesis
    runs on the hardware configs alone through the digest-keyed sweep
    cache by default — revisited hardware (the common case in a search)
    skips the flow entirely, keeping W-workload evaluation ~O(1 synthesis)
    per hardware config.

    Returns ``{column: (W, N)}`` over :data:`AGGREGATE_OUTPUTS` plus
    ``clock_ghz`` / ``area_mm2`` as ``(N,)``.  Workload ``w``'s row is
    bit-identical (numpy) to :func:`sweep_mixed` on that workload alone;
    jax agrees to the usual ~1e-7 relative parity.

    ``mesh`` shards the genome (config) axis: under jax a
    ``jax.sharding.Mesh`` from :func:`repro.launch.mesh.make_sweep_mesh`
    spreads the batch across devices via ``shard_map`` (the batch is
    padded to a device-count multiple and sliced back); under numpy an
    int (or a mesh, whose device count is taken) splits the batch into
    that many contiguous shards evaluated independently — bit-identical
    to the unsharded path, used to test shard-boundary semantics without
    multiple devices.
    """
    backend = resolve_backend(backend)
    use_pallas = resolve_use_pallas(use_pallas, backend, mesh)
    wls = tuple(workloads)
    if not wls:
        raise ValueError("sweep_mixed_many needs at least one workload")
    combined, bounds = _workload_batch_many(wls)
    n = len(soa["pe_rows"])
    assigns = [np.asarray(a, dtype=np.int64) for a in assigns]
    if len(assigns) != len(wls):
        raise ValueError(
            f"{len(assigns)} assignment matrices for {len(wls)} workloads")
    for (s, e), a, wl in zip(bounds, assigns, wls):
        if a.shape != (n, e - s):
            raise ValueError(
                f"assignment shape {a.shape} != ({n} configs, "
                f"{e - s} layers) for workload {wl.name!r}")
    assign_all = np.concatenate(assigns, axis=1)
    check_assignment(soa, assign_all)
    if cols is None:
        cols = (sweep_synthesis_cache().synthesize(soa) if use_cache
                else synthesize_soa(soa))
    cfg, lay = _make_cfg_lay(soa, cols, combined)
    cfg = mixed_assign_cfg(cfg, assign_all)
    if backend == "jax" and use_pallas:
        from repro.kernels.sweep_kernel import sweep_aggregates_pallas
        out = sweep_aggregates_pallas(cfg, lay, bounds=bounds)
        with obs_trace.span("kernel.wait"):
            out = {k: np.asarray(v) for k, v in out.items()}
    elif backend == "jax":
        _require_jax_mesh(mesh)
        fn, exact = get_jax_many_kernel(bounds, mesh)
        jcfg, jlay = _to_jax_inputs(cfg, lay, exact)
        if mesh is not None:
            jcfg = _pad_rows(jcfg, -n % _mesh_shards(mesh))
        out = {k: np.asarray(v)[:, :n] for k, v in fn(jcfg, jlay).items()}
    else:
        shards = min(_mesh_shards(mesh), max(1, n))
        if shards == 1:
            totals = _sweep_kernel(np, cfg, lay, outputs="layer_totals")
            out = _segment_aggregates(np, totals, cfg, lay, bounds,
                                      exact=True)
        else:
            # simulated sharding: contiguous config-axis splits through
            # the same kernel + segment reduction, concatenated back —
            # every expression is row-local, so this is bit-identical to
            # the single-shard path by construction
            parts = []
            splits = np.array_split(np.arange(n), shards)
            for idx in splits:
                if len(idx) == 0:
                    continue
                cfg_s = {k: v[idx] for k, v in cfg.items()}
                totals = _sweep_kernel(np, cfg_s, lay,
                                       outputs="layer_totals")
                parts.append(_segment_aggregates(np, totals, cfg_s, lay,
                                                 bounds, exact=True))
            out = {k: np.concatenate([p[k] for p in parts], axis=1)
                   for k in AGGREGATE_OUTPUTS}
    out["clock_ghz"] = cfg["clock_ghz"][:, 0]
    out["area_mm2"] = cfg["area_mm2"][:, 0]
    return out


# ---------------------------------------------------------------------------
# Streamed chunked sweep with running Pareto-front reduction
# ---------------------------------------------------------------------------

# per-point metric columns retained for Pareto survivors
_FRONT_METRICS = ("perf_per_area", "energy_j", "latency_s",
                  "throughput_gmacs")
_SOA_ID_FIELDS = ("pe_type_idx", "pe_rows", "pe_cols", "ifmap_spad",
                  "filter_spad", "psum_spad", "glb_kb", "dram_bw_gbps",
                  "clock_cap")


@dataclasses.dataclass
class ChunkedSweep:
    """Result of a streamed sweep: running totals + the Pareto frontier
    (maximize perf/area, minimize energy), *not* the full point set."""

    workload: str
    backend: str
    n_configs: int
    n_chunks: int
    front_soa: dict[str, np.ndarray]      # identity fields of survivors
    front_metrics: dict[str, np.ndarray]  # _FRONT_METRICS columns
    synthesis_cache: PersistentSynthesisCache | None = None
    # stage accounting from the streamed driver: wall_s (whole stream),
    # synth_s (host synthesis + feed pull), kernel_wait_s (time blocked on
    # kernel results — under the overlapped pipeline this shrinks toward
    # zero as synthesis of chunk i+1 hides behind the kernel on chunk i),
    # overlap (whether the two-stage pipeline was active)
    timings: dict | None = None

    @property
    def front_size(self) -> int:
        return len(self.front_metrics["energy_j"])

    def front_configs(self) -> list[AcceleratorConfig]:
        """Materialize the frontier as configs, sorted by energy."""
        order = np.argsort(self.front_metrics["energy_j"], kind="stable")
        return soa_to_configs(self.front_soa, order)

    def front_points(self) -> list[dict]:
        order = np.argsort(self.front_metrics["energy_j"], kind="stable")
        cfgs = soa_to_configs(self.front_soa, order)
        return [
            dict({m: float(self.front_metrics[m][i])
                  for m in _FRONT_METRICS}, config=cfg)
            for i, cfg in zip(order, cfgs)]


def _as_soa_chunks(chunks, chunk_size: int) -> Iterator[dict]:
    """Normalize a config feed — SoA dicts, config sequences, or a flat
    config generator — into bounded-size SoA chunks."""
    pending: list[AcceleratorConfig] = []
    if isinstance(chunks, dict):        # single SoA
        chunks = (chunks,)
    for item in chunks:
        if isinstance(item, dict):
            if pending:
                yield configs_to_soa(tuple(pending))
                pending.clear()
            n = len(item["pe_rows"])
            for s in range(0, n, chunk_size):
                yield {k: v[s:s + chunk_size] for k, v in item.items()}
        elif isinstance(item, AcceleratorConfig):
            pending.append(item)
            if len(pending) >= chunk_size:
                yield configs_to_soa(tuple(pending))
                pending.clear()
        else:                           # a sequence of configs
            for cfg in item:
                pending.append(cfg)
                if len(pending) >= chunk_size:
                    yield configs_to_soa(tuple(pending))
                    pending.clear()
    if pending:
        yield configs_to_soa(tuple(pending))


class ChunkDeadlineExceeded(RuntimeError):
    """A dispatched chunk failed to produce results within the watchdog
    deadline (``chunk_deadline_s``); the stream cancels it and recomputes
    the chunk serially on the exact numpy kernel."""


class ChunkCancelled(RuntimeError):
    """An in-flight chunk's worker future was cancelled — the watchdog
    replaced a zombie executor and dropped its queue.  The stream
    recomputes the chunk serially (no deadline warning: the chunk itself
    did nothing wrong)."""


class _AbandonedFinalizers:
    """Accounting for jax materialize threads the watchdog gave up on.

    A wedged device can pin a chunk's buffers inside ``np.asarray`` for
    as long as it stays wedged — Python cannot kill the thread — but an
    abandoned thread must (a) never park its materialized result in a
    long-lived box and (b) be observable, so repeated watchdog fires show
    up as a bounded ``live`` count instead of silent memory growth.
    """

    def __init__(self):
        import threading
        self._lock = threading.Lock()
        self.abandoned = 0      # watchdog timeouts that orphaned a thread
        self.completed = 0      # orphaned threads that finished + dropped

    def abandon(self) -> None:
        with self._lock:
            self.abandoned += 1
        obs_metrics.get_registry().inc("sweep.abandoned_finalizers")

    def finish(self) -> None:
        with self._lock:
            self.completed += 1

    @property
    def live(self) -> int:
        """Threads still wedged on a materialization (buffers pinned)."""
        with self._lock:
            return self.abandoned - self.completed


#: process-wide abandoned-materialization ledger (tests assert ``live``
#: returns to 0 once a slow — not wedged — device catches up)
abandoned_finalizers = _AbandonedFinalizers()


def _dispatch_chunk(cfg: dict, lay: dict, backend: str, mesh,
                    chunk_size: int, n: int, executor,
                    use_pallas: bool = False):
    """Launch the aggregates kernel for one chunk without blocking.

    Returns a ``finalize(timeout=None)`` producing the host-side ``(n,)``
    aggregate columns.  Under jax the jit call dispatches asynchronously
    and ``finalize`` materializes the device buffers; under numpy with an
    ``executor`` the kernel runs on a worker thread (numpy ufuncs release
    the GIL) so the caller can synthesize the next chunk meanwhile.

    ``timeout`` (seconds) bounds the wait and raises
    :class:`ChunkDeadlineExceeded` on expiry — the watchdog hook of the
    streamed driver.  The plain numpy path (no executor) runs
    synchronously on call, so a deadline cannot preempt it; that path *is*
    the serial fallback the watchdog re-dispatches onto.
    """
    if backend == "jax":
        # pad the tail chunk to the steady-state shape: one jit trace
        # serves the whole stream (padded rows are sliced off below)
        cfg = _pad_rows(cfg, (chunk_size - n % chunk_size) % chunk_size)
        if use_pallas and mesh is None:
            from repro.kernels.sweep_kernel import sweep_aggregates_pallas
            out = sweep_aggregates_pallas(cfg, lay)    # async dispatch
        else:
            fn, exact = get_jax_kernel(mesh, "aggregates")
            jcfg, jlay = _to_jax_inputs(cfg, lay, exact)
            if mesh is not None:
                jcfg = _pad_rows(jcfg,
                                 -len(jcfg["pe_rows"]) % _mesh_shards(mesh))
            out = fn(jcfg, jlay)                       # async dispatch

        def finalize(timeout: float | None = None):
            if timeout is None:
                return {k: np.asarray(v)[:n] for k, v in out.items()}
            # jax materialization has no native timeout: bound it with a
            # daemon-thread join so a wedged device cannot hang the stream
            import threading
            box: dict = {}
            lock = threading.Lock()

            def _materialize(buffers):
                try:
                    res = {k: np.asarray(v)[:n]
                           for k, v in buffers.items()}
                    exc = None
                except BaseException as e:      # surfaced to the caller
                    res, exc = None, e
                buffers = None      # noqa: F841 — drop the device refs
                with lock:
                    if box.get("abandoned"):
                        # the watchdog gave up on this chunk while we
                        # were blocked: discard the result here instead
                        # of parking host+device copies in `box` for the
                        # rest of the process, and mark the orphan done
                        abandoned_finalizers.finish()
                        return
                    if exc is not None:
                        box["exc"] = exc
                    else:
                        box["out"] = res

            th = threading.Thread(target=_materialize, args=(out,),
                                  daemon=True)
            th.start()
            th.join(timeout)
            with lock:
                if "out" not in box and "exc" not in box:
                    # timed out: flag the orphan so its eventual
                    # completion drops the buffers instead of keeping
                    # them reachable through the box
                    box["abandoned"] = True
                    abandoned_finalizers.abandon()
                    raise ChunkDeadlineExceeded(
                        f"jax chunk did not materialize within "
                        f"{timeout}s")
            if "exc" in box:
                raise box["exc"]
            return box["out"]

        return finalize
    kernel = functools.partial(_sweep_kernel, np, cfg, lay,
                               outputs="aggregates")
    if executor is not None:
        fut = executor.submit(kernel)

        def finalize(timeout: float | None = None):
            from concurrent.futures import CancelledError
            from concurrent.futures import TimeoutError as _FutTimeout
            try:
                return fut.result(timeout)
            except _FutTimeout:
                fut.cancel()   # a running kernel cannot be interrupted,
                #                but a still-queued one is dropped
                raise ChunkDeadlineExceeded(
                    f"chunk kernel still running after {timeout}s"
                ) from None
            except CancelledError:
                # the watchdog tore down the executor this chunk was
                # queued on (zombie-worker recovery) — not this chunk's
                # own deadline
                raise ChunkCancelled(
                    "chunk worker future was cancelled by executor "
                    "replacement") from None

        return finalize
    return lambda timeout=None: kernel()


def _sweep_chunked(workload: Workload,
                   configs: Iterable,
                   *,
                   backend: str = "auto",
                   chunk_size: int = 32768,
                   use_cache: bool = False,
                   cache: PersistentSynthesisCache | str | None = None,
                   save_cache: bool = True,
                   mesh=None,
                   overlap: bool = True,
                   prefetch_depth: int = 2,
                   use_pallas: bool | None = None,
                   checkpoint=None,
                   fail_at: dict[int, int] | None = None,
                   chunk_deadline_s: float | None = None,
                   degrade_on_failure: bool = False) -> ChunkedSweep:
    """Stream an arbitrary-size config feed through the sweep engine in
    bounded memory, keeping only running aggregates + the Pareto front.

    ``configs`` may be SoA dicts (e.g. from
    :func:`repro.core.accelerator.design_space_soa` — the fast path, no
    per-config objects), sequences of :class:`AcceleratorConfig`, or a
    flat config generator.  ``cache`` (a
    :class:`~repro.core.synthesis.PersistentSynthesisCache` or an npz
    path) persists synthesis results across runs, so a cold re-sweep of a
    seen space skips synthesis; ``use_cache`` instead routes through the
    in-process array cache.

    ``overlap=True`` (default) runs the stream as a **depth-k prefetch
    pipeline**: up to ``prefetch_depth`` chunks (default 2 — the classic
    two-stage overlap) are dispatched and in flight at once, their
    ``finalize`` handles held in a bounded deque, while the host pulls
    and synthesizes the next chunk; the running Pareto reduction drains
    the deque in FIFO order.  Chunks are synthesized, reduced, and
    cache-inserted in exactly the stream order of the serial path at
    *every* depth, so results, resume points, and
    :class:`~repro.core.synthesis.PersistentSynthesisCache` hit/miss
    accounting are identical (asserted in
    ``tests/test_chunked_pipeline.py``); ``overlap=False`` keeps the
    fully serial per-chunk loop (equivalent to ``prefetch_depth=1``).
    Depths beyond 2 only pay off once the kernel stage outruns host
    synthesis — e.g. the Pallas sweep kernel on a real accelerator
    (``use_pallas=True``; ``None`` auto-engages it exactly there, see
    :func:`resolve_use_pallas`).

    Fault tolerance (``tests/test_dse_checkpoint.py``):

    * ``checkpoint`` — a duck-typed snapshotter (see
      :class:`repro.runtime.dse_checkpoint.SweepCheckpointer`) with
      ``restore() -> snap | None``, ``should_save(cursor) -> bool`` and
      ``save(cursor, n_total, front_soa, front_metrics, cache_state)``.
      On entry the newest valid snapshot restores the stream cursor,
      running front, and cache accounting; already-reduced chunks are
      pulled from the feed but not synthesized, so a resumed run's front
      and hit/miss counters are bit-identical to an uninterrupted one.
    * ``fail_at`` — ``{chunk_index: n_times}`` deterministic
      :class:`~repro.runtime.fault_tolerance.InjectedFailure` injection at
      chunk boundaries (decremented in place so a shared dict fails each
      boundary only ``n_times`` across restarts).
    * ``chunk_deadline_s`` — watchdog: a dispatched chunk exceeding the
      deadline is cancelled and recomputed serially on the exact numpy
      kernel (counted in ``timings["watchdog_redispatches"]``).
    * ``degrade_on_failure`` — opt-in: a jax failure mid-stream (dispatch
      or materialization) degrades the remaining stream to numpy with a
      warning instead of losing the run; stream order and cache
      accounting are preserved (``timings["degraded"]``).  Off by
      default, so a device failure raises instead of finishing on the
      host.
    """
    import sys
    import time
    import warnings
    from collections import deque
    backend = resolve_backend(backend)
    if backend == "jax":
        _require_jax_mesh(mesh)
    use_pallas = resolve_use_pallas(use_pallas, backend, mesh)
    if int(prefetch_depth) < 1:
        raise ValueError(
            f"prefetch_depth must be >= 1, got {prefetch_depth}")
    # depth 1 <=> the fully serial loop; overlap=False forces it
    depth = int(prefetch_depth) if overlap else 1
    if isinstance(cache, (str, bytes)) or hasattr(cache, "__fspath__"):
        cache = PersistentSynthesisCache(cache)
    wb = _workload_batch(workload)
    fail_at = fail_at if fail_at is not None else {}

    front_soa: dict[str, np.ndarray] | None = None
    front_metrics: dict[str, np.ndarray] | None = None
    n_total = 0
    n_chunks = 0
    resume_cursor = 0
    if checkpoint is not None:
        snap = checkpoint.restore()
        if snap is not None:
            resume_cursor = int(snap["cursor"])
            if resume_cursor > 0:
                n_total = int(snap["n_total"])
                n_chunks = resume_cursor
                front_soa = snap["front_soa"]
                front_metrics = snap["front_metrics"]
                if cache is not None \
                        and snap.get("cache_state") is not None:
                    cache.import_state(snap["cache_state"])
    t_wall = time.perf_counter()
    timings = {"overlap": bool(overlap), "prefetch_depth": depth,
               "use_pallas": bool(use_pallas), "wall_s": 0.0,
               "synth_s": 0.0, "kernel_wait_s": 0.0,
               "watchdog_redispatches": 0, "executor_replacements": 0,
               "cancelled_recomputes": 0, "abandoned_finalizers": 0,
               "degraded": False}
    _reg = obs_metrics.get_registry()
    root_span = obs_trace.span_start(
        "sweep_chunked", workload=workload.name, backend=backend,
        chunk_size=int(chunk_size), overlap=bool(overlap),
        prefetch_depth=depth, use_pallas=bool(use_pallas),
        resume_cursor=resume_cursor)
    n_total0, n_chunks0 = n_total, n_chunks   # restored-from-snapshot base
    telemetry_flushed = False

    def _flush_telemetry(status: str) -> None:
        # Finalize wall_s + registry totals exactly once per attempt —
        # on the success path after the terminal saves (pre-telemetry
        # semantics), and from the error path's finally so an
        # InjectedFailure / crashed attempt still reports its time and
        # the registry sums stay consistent across resumed runs (only
        # work done *this* attempt is counted, not restored totals).
        nonlocal telemetry_flushed
        if telemetry_flushed:
            return
        telemetry_flushed = True
        timings["wall_s"] = time.perf_counter() - t_wall
        _reg.inc("sweep.chunks", n_chunks - n_chunks0)
        _reg.inc("sweep.configs", n_total - n_total0)
        _reg.inc("sweep.wall_s", timings["wall_s"])
        _reg.inc("sweep.synth_s", timings["synth_s"])
        _reg.inc("sweep.kernel_wait_s", timings["kernel_wait_s"])
        _reg.set("sweep.prefetch_depth", depth)
        if status != "ok":
            _reg.inc("sweep.failures")
        if timings["wall_s"] > 0:
            _reg.set("sweep.configs_per_s",
                     (n_total - n_total0) / timings["wall_s"])
        obs_trace.span_end(root_span, status=status,
                           configs=n_total, chunks=n_chunks,
                           wall_s=timings["wall_s"])

    def reduce_chunk(soa: dict, n: int, out: dict) -> None:
        nonlocal front_soa, front_metrics
        perf = np.asarray(out["perf_per_area"], dtype=np.float64)[:n]
        energy = np.asarray(out["energy_j"], dtype=np.float64)[:n]
        # prefilter: only the chunk's own frontier can join the global one
        local = pareto_mask(perf, energy)
        idx = np.nonzero(local)[0]
        cand_soa = {k: soa[k][idx] for k in _SOA_ID_FIELDS}
        cand_metrics = {m: np.asarray(out[m], dtype=np.float64)[:n][idx]
                        for m in _FRONT_METRICS}
        if front_soa is None:
            front_soa, front_metrics = cand_soa, cand_metrics
        else:
            front_soa = {k: np.concatenate([front_soa[k], cand_soa[k]])
                         for k in _SOA_ID_FIELDS}
            front_metrics = {
                m: np.concatenate([front_metrics[m], cand_metrics[m]])
                for m in _FRONT_METRICS}
        keep = pareto_mask(front_metrics["perf_per_area"],
                           front_metrics["energy_j"])
        front_soa = {k: v[keep] for k, v in front_soa.items()}
        front_metrics = {m: v[keep] for m, v in front_metrics.items()}

    executor = None

    def _ensure_executor() -> None:
        nonlocal executor
        if overlap and backend == "numpy" and executor is None:
            from concurrent.futures import ThreadPoolExecutor
            executor = ThreadPoolExecutor(max_workers=1)

    def _replace_executor() -> None:
        # zombie-worker recovery: fut.cancel() cannot interrupt a kernel
        # that is already running, so after a watchdog fire the old
        # executor's single worker is still occupied — every later chunk
        # would queue behind it and cascade into its own deadline.  Tear
        # the executor down (without waiting on the zombie) and start a
        # fresh one; still-queued futures of other in-flight chunks are
        # cancelled and surface as ChunkCancelled at their drain.
        nonlocal executor
        if executor is None:
            return
        executor.shutdown(wait=False, cancel_futures=True)
        executor = None
        timings["executor_replacements"] += 1
        _reg.inc("sweep.executor_replacements")
        _ensure_executor()

    _ensure_executor()

    def _degrade(dcfg: dict, dlay: dict, exc: BaseException,
                 what: str) -> dict:
        # jax died mid-stream: warn, recompute this chunk on the exact
        # numpy kernel, and switch the remaining stream to numpy — the
        # run survives instead of losing hours of reduced front
        nonlocal backend
        warnings.warn(
            f"jax backend failed during chunk {what} "
            f"({type(exc).__name__}: {exc}); degrading stream to numpy "
            f"for this and all remaining chunks", RuntimeWarning,
            stacklevel=3)
        backend = "numpy"
        timings["degraded"] = True
        _reg.inc("sweep.degraded")
        _ensure_executor()
        return _sweep_kernel(np, dcfg, dlay, outputs="aggregates")

    # FIFO of in-flight chunks, each:
    # (soa, n, cfg, lay, finalize, backend_at_dispatch, save_info,
    #  cache_state, chunk_index)
    pending: deque = deque()

    def drain_one() -> None:
        if not pending:
            return
        (psoa, pn, pcfg, play, pfin, pbackend, psave, pcache,
         pci) = pending.popleft()
        t0 = time.perf_counter()
        try:
            with obs_trace.span("kernel.wait", chunk=pci):
                out = pfin(timeout=chunk_deadline_s)
        except ChunkDeadlineExceeded:
            warnings.warn(
                f"chunk kernel exceeded the {chunk_deadline_s:.3g}s "
                f"watchdog deadline; cancelled and re-dispatched "
                f"serially on the numpy kernel", RuntimeWarning,
                stacklevel=3)
            timings["watchdog_redispatches"] += 1
            _reg.inc("sweep.watchdog_redispatches")
            if pbackend == "jax":
                timings["abandoned_finalizers"] += 1
            # the deadlined worker (numpy path) is a zombie occupying
            # the 1-worker executor — replace it so the next dispatch
            # doesn't queue behind it and cascade-deadline
            _replace_executor()
            with obs_trace.span("sweep.watchdog_recompute", chunk=pci):
                out = _sweep_kernel(np, pcfg, play, outputs="aggregates")
        except ChunkCancelled:
            # this chunk was queued on an executor the watchdog tore
            # down; recompute serially, no deadline of its own
            timings["cancelled_recomputes"] += 1
            _reg.inc("sweep.cancelled_recomputes")
            with obs_trace.span("sweep.cancelled_recompute", chunk=pci):
                out = _sweep_kernel(np, pcfg, play, outputs="aggregates")
        except Exception as exc:
            if pbackend != "jax" or not degrade_on_failure:
                raise
            out = _degrade(pcfg, play, exc, "materialization")
        timings["kernel_wait_s"] += time.perf_counter() - t0
        with obs_trace.span("sweep.reduce", chunk=pci, n=pn):
            reduce_chunk(psoa, pn, out)
        if psave is not None:
            with obs_trace.span("sweep.checkpoint", cursor=psave[0]):
                checkpoint.save(cursor=psave[0], n_total=psave[1],
                                front_soa=front_soa,
                                front_metrics=front_metrics,
                                cache_state=pcache)

    try:
        feed = _as_soa_chunks(configs, chunk_size)
        ci = -1                 # absolute index of the chunk being pulled
        while True:
            t0 = time.perf_counter()
            with obs_trace.span("sweep.pull"):
                soa = next(feed, None)
            if soa is not None:
                n = len(soa["pe_rows"])
                if n == 0:
                    continue
                ci += 1
                if ci < resume_cursor:
                    # reduced before the restart: advance the feed without
                    # synthesizing — the snapshot already carries this
                    # chunk's rows, front contribution, and cache
                    # accounting
                    continue
                if fail_at.get(ci, 0) > 0:
                    fail_at[ci] -= 1
                    from repro.runtime.fault_tolerance import \
                        InjectedFailure
                    raise InjectedFailure(
                        f"injected failure at chunk boundary {ci}")
                n_total += n
                n_chunks += 1
                # stage 1 (host): synthesis — in stream order, so cache
                # lookups/inserts match the serial path row for row
                with obs_trace.span("sweep.synthesize", chunk=ci, n=n):
                    if cache is not None:
                        cols = cache.synthesize(soa)
                    elif use_cache:
                        cols = sweep_synthesis_cache().synthesize(soa)
                    else:
                        cols = synthesize_soa(soa)
                    cfg, lay = _make_cfg_lay(soa, cols, wb)
                # synth_s keeps its pre-telemetry meaning: host stage-1
                # time including the feed pull (t0 is read before next())
                timings["synth_s"] += time.perf_counter() - t0
                save_info = cache_state = None
                if checkpoint is not None \
                        and checkpoint.should_save(ci + 1):
                    # capture the cache *now*, while its rows and counters
                    # cover exactly chunks 0..ci — under the overlapped
                    # pipeline chunk ci+1 is synthesized before chunk ci's
                    # snapshot is written, and letting its rows leak into
                    # the snapshot would turn its re-synthesis after a
                    # resume into cache hits (accounting drift)
                    save_info = (ci + 1, n_total)
                    if cache is not None:
                        cache_state = cache.export_state()
                # stage 2 (device / worker thread): dispatch the kernel
                try:
                    with obs_trace.span("sweep.dispatch", chunk=ci):
                        finalize = _dispatch_chunk(cfg, lay, backend,
                                                   mesh, chunk_size, n,
                                                   executor, use_pallas)
                except Exception as exc:
                    if backend != "jax" or not degrade_on_failure:
                        raise
                    out_now = _degrade(cfg, lay, exc, "dispatch")
                    finalize = lambda timeout=None, o=out_now: o  # noqa: E731
                pending.append((soa, n, cfg, lay, finalize, backend,
                                save_info, cache_state, ci))
                _reg.observe("sweep.inflight", len(pending))
                _reg.inc("sweep.layer_pairs", n * len(wb))
                # bounded prefetch: drain FIFO until at most depth-1
                # chunks stay in flight behind the next synthesis
                while len(pending) >= depth:
                    drain_one()
            else:
                while pending:  # feed exhausted: drain the queue dry
                    drain_one()
                break
    finally:
        if executor is not None:
            executor.shutdown(wait=True)
        if sys.exc_info()[0] is not None:
            _flush_telemetry("error")

    if front_soa is None:
        front_soa = {k: np.empty(0, dtype=np.int64)
                     for k in _SOA_ID_FIELDS}
        front_metrics = {m: np.empty(0, dtype=np.float64)
                         for m in _FRONT_METRICS}
    if checkpoint is not None:
        # terminal snapshot: resuming a completed run restores the full
        # front and skips the whole feed (idempotent)
        with obs_trace.span("sweep.checkpoint", cursor=n_chunks,
                            terminal=True):
            checkpoint.save(
                cursor=n_chunks, n_total=n_total, front_soa=front_soa,
                front_metrics=front_metrics,
                cache_state=cache.export_state() if cache is not None
                else None)
    if cache is not None and save_cache and cache.path is not None:
        cache.save()
    _flush_telemetry("ok")
    return ChunkedSweep(workload=workload.name, backend=backend,
                        n_configs=n_total, n_chunks=n_chunks,
                        front_soa=front_soa, front_metrics=front_metrics,
                        synthesis_cache=cache, timings=timings)


def _pareto_mask_bcast(perf: np.ndarray, energy: np.ndarray,
                       chunk: int) -> np.ndarray:
    """O(n^2) chunked-broadcast dominance test (reference for the sorted
    algorithm; memory stays at ``chunk * n`` bools)."""
    n = perf.shape[0]
    keep = np.ones(n, dtype=bool)
    for s in range(0, n, chunk):
        p = perf[s:s + chunk, None]
        e = energy[s:s + chunk, None]
        dominated = ((perf[None, :] >= p) & (energy[None, :] <= e)
                     & ((perf[None, :] > p) | (energy[None, :] < e))).any(1)
        keep[s:s + chunk] = ~dominated
    return keep


def _pareto_mask_sorted(perf: np.ndarray,
                        energy: np.ndarray) -> np.ndarray:
    """O(n log n) dominance test: sort by (energy asc, perf desc), then a
    point survives iff it has its energy-group's max perf and strictly
    beats the running perf max of all lower-energy groups.  Tie semantics
    identical to the broadcast test (duplicates both survive)."""
    n = perf.shape[0]
    order = np.lexsort((-perf, energy))
    ps, es = perf[order], energy[order]
    new_group = np.empty(n, dtype=bool)
    new_group[0] = True
    new_group[1:] = es[1:] != es[:-1]
    # group max perf = first row of the group (perf sorted desc in-group)
    group_id = np.cumsum(new_group) - 1
    group_max = ps[new_group]                       # (G,)
    cummax = np.maximum.accumulate(group_max)
    prev_best = np.full(len(group_max), -np.inf)
    prev_best[1:] = cummax[:-1]                     # strictly lower energy
    survive_sorted = (ps == group_max[group_id]) \
        & (ps > prev_best[group_id])
    keep = np.empty(n, dtype=bool)
    keep[order] = survive_sorted
    return keep


def pareto_mask(perf: np.ndarray, energy: np.ndarray,
                chunk: int = 1024) -> np.ndarray:
    """Boolean mask of non-dominated points for (maximize perf, minimize
    energy).

    Small batches use the chunked-broadcast dominance test; large ones
    switch to the sort-based O(n log n) algorithm (bit-identical output,
    asserted against each other in tests) so the streamed sweep's running
    reduction stays cheap at 1M-config scale.
    """
    perf = np.asarray(perf, dtype=np.float64)
    energy = np.asarray(energy, dtype=np.float64)
    if perf.shape[0] == 0:
        return np.zeros(0, dtype=bool)
    if perf.shape[0] <= 2048:
        return _pareto_mask_bcast(perf, energy, chunk)
    return _pareto_mask_sorted(perf, energy)


# ---------------------------------------------------------------------------
# Deprecated public entry points (one-release shims)
#
# The kernel-level sweep API is consolidated behind
# ``repro.core.dse.run(ExploreSpec)``: config-batch sweeps are
# ``ExploreSpec.single(..., outputs="sweep")`` (add ``chunk_size=`` for the
# streamed engine), and mixed-precision genome evaluation lives in
# ``repro.explore.search.Evaluator`` (driven by ``ExploreSpec.mixed()`` /
# ``.many()``).  These wrappers forward verbatim and warn; in-repo code
# must call the private implementations (CI runs the test suite with
# ``error::DeprecationWarning:repro``).
# ---------------------------------------------------------------------------

def _deprecated(old: str, new: str) -> None:
    import warnings
    warnings.warn(
        f"{old} is deprecated; use {new}",
        DeprecationWarning, stacklevel=3)


def sweep_workload(*args, **kwargs) -> BatchedSweep:
    """Deprecated: use ``repro.core.dse.run`` with
    ``ExploreSpec.single(..., outputs="sweep")``."""
    _deprecated("repro.core.dse_batch.sweep_workload",
                'repro.core.dse.run(ExploreSpec.single(..., '
                'outputs="sweep"))')
    return _sweep_workload(*args, **kwargs)


def sweep_mixed(*args, **kwargs) -> dict[str, np.ndarray]:
    """Deprecated: use ``repro.explore.search.Evaluator`` (driven by
    ``repro.core.dse.run`` + ``ExploreSpec.mixed()``)."""
    _deprecated("repro.core.dse_batch.sweep_mixed",
                "repro.explore.search.Evaluator / "
                "repro.core.dse.run(ExploreSpec.mixed(...))")
    return _sweep_mixed(*args, **kwargs)


def sweep_mixed_many(*args, **kwargs) -> dict[str, np.ndarray]:
    """Deprecated: use ``repro.explore.search.Evaluator`` (driven by
    ``repro.core.dse.run`` + ``ExploreSpec.many(precision="mixed")``)."""
    _deprecated("repro.core.dse_batch.sweep_mixed_many",
                "repro.explore.search.Evaluator / "
                'repro.core.dse.run(ExploreSpec.many(..., '
                'precision="mixed"))')
    return _sweep_mixed_many(*args, **kwargs)


def sweep_chunked(*args, **kwargs) -> ChunkedSweep:
    """Deprecated: use ``repro.core.dse.run`` with
    ``ExploreSpec.single(..., outputs="sweep", chunk_size=...)``."""
    _deprecated("repro.core.dse_batch.sweep_chunked",
                'repro.core.dse.run(ExploreSpec.single(..., '
                'outputs="sweep", chunk_size=...))')
    return _sweep_chunked(*args, **kwargs)
