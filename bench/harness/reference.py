"""Plain reference of the QAPPA model, independent of the program under test.

A float64 numpy restatement of the model the program implements: the PE
constants, the analytical synthesis oracle with its counter-hash jitter,
the aggregation of a layer table into per-config results, the tier-0
quantization-noise proxy, and the event-driven serving-fleet simulator.
How a network's layers map onto the array, and what each costs, is its
layer model's (``bench/layers/<model>.py``; the conv mapping is
``row_stationary``).  It imports nothing of ``repro`` and takes nothing
the program made: it reads the configuration file (layer shapes,
hardware factors) and the inputs the benchmark drew from its seed.

Everything is vectorized over configs in plain numpy.  ``prec="bf16"``
rounds every floating quantity it produces (synthesis results, per-layer
cycles and energies, aggregates, the noise table) to bfloat16: the
control, one precision below the float32 that the configurations state.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

# Per-PE-type constants, order (fp32, int16, lightpe1, lightpe2): operand
# widths in bits, MAC energy (pJ), MAC area (um^2), critical path (ns),
# static power per PE (uW).
PE_TYPES = ("fp32", "int16", "lightpe1", "lightpe2")
ACT_BITS = np.array([32, 16, 8, 8], dtype=np.int64)
WEIGHT_BITS = np.array([32, 16, 4, 8], dtype=np.int64)
PSUM_BITS = np.array([32, 32, 24, 24], dtype=np.int64)
MAC_ENERGY_PJ = np.array([1.38, 1.00, 0.105, 0.135])
MAC_AREA_UM2 = np.array([12050.0, 8850.0, 1430.0, 1450.0])
MAC_DELAY_NS = np.array([1.39, 1.25, 0.80, 0.893])
LEAK_UW = np.array([14.0, 3.0, 0.9, 1.3])

FLOOR_PENALTY = 1e9      # cap on an unserved candidate's serving objectives


def rounder(prec: str):
    """The rounding applied to every floating result: identity for
    float64, a round trip through bfloat16 for the control."""
    if prec == "f64":
        return lambda x: np.asarray(x, dtype=np.float64)
    if prec == "bf16":
        return lambda x: np.asarray(x, dtype=np.float64).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown reference precision {prec!r}")


# ---------------------------------------------------------------------------
# Counter hash (threefry-2x32, 13 rounds) behind the synthesis jitter
# ---------------------------------------------------------------------------

_U32 = np.uint32
_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = 0x1BD11BDA
_MULT = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F)
_IV = (0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A)


def _threefry(k0, k1, x0, x1, rounds: int = 13):
    ks = (k0, k1, k0 ^ k1 ^ _U32(_PARITY))
    x0, x1 = x0 + k0, x1 + k1
    for r in range((rounds + 3) // 4):
        rots = _ROT[:4] if r % 2 == 0 else _ROT[4:]
        for rot in rots[:min(4, rounds - 4 * r)]:
            x0 = x0 + x1
            x1 = ((x1 << _U32(rot)) | (x1 >> _U32(32 - rot))) ^ x0
        x0 = x0 + ks[(r + 1) % 3]
        x1 = x1 + ks[(r + 2) % 3] + _U32(r + 1)
    return x0, x1


def _digest(words):
    words = [np.asarray(w, dtype=_U32) for w in words]
    words.append(np.asarray(_U32(len(words))))
    h = [np.asarray(_U32(v)) for v in _IV]
    for w in words:
        h = [hi * _U32(c) + w for hi, c in zip(h, _MULT)]
    a0, a1 = _threefry(h[2], h[3], h[0], h[1])
    b0, b1 = _threefry(h[0] ^ _U32(_PARITY), h[1], h[2], h[3])
    return a0, a1, b0, b1


def _f64_words(x):
    bits = np.ascontiguousarray(x, dtype=np.float64).view(np.uint64)
    return ((bits & np.uint64(0xFFFFFFFF)).astype(_U32),
            (bits >> np.uint64(32)).astype(_U32))


def _uniform(lane):
    return (np.asarray(lane, dtype=_U32) >> _U32(8)).astype(np.float64) \
        * 2.0 ** -24


# ---------------------------------------------------------------------------
# Hardware: synthesis oracle
# ---------------------------------------------------------------------------

def rf_energy(bits):
    """Energy (pJ) of one access to a register file of ``bits``."""
    return 0.035 * np.sqrt(np.maximum(bits / 8192.0, 0.03125)) + 0.015


def sram_energy(bits):
    """Energy (pJ) of one access to an SRAM of ``bits``."""
    return 0.09 * np.sqrt(np.maximum(bits / 8192.0, 0.03125)) + 0.04


def _sram_area(bits):
    return np.where(bits > 0, 0.55 * bits + 300.0, 0.0)


def hardware(pe_type, rows, cols, ifmap, filt, psum, glb_kb, bw_gbps,
             prec: str = "f64") -> dict:
    """Synthesized hardware columns for a config batch: the raw fields
    plus clock (GHz), area (mm^2), leakage (mW) and storage sizes."""
    q = rounder(prec)
    hw = {k: np.asarray(v, dtype=np.int64) for k, v in (
        ("type", pe_type), ("rows", rows), ("cols", cols),
        ("ifmap", ifmap), ("filt", filt), ("psum", psum),
        ("glb_kb", glb_kb))}
    hw["bw"] = np.asarray(bw_gbps, dtype=np.float64)
    t = hw["type"]
    words = [hw[k].astype(_U32) for k in
             ("type", "rows", "cols", "ifmap", "filt", "psum", "glb_kb")]
    words.extend(_f64_words(hw["bw"]))
    words.extend(_f64_words(np.full(len(t), np.inf)))     # no clock cap
    d = _digest(words)
    jit_area = 1.0 + 0.03 * (2.0 * _uniform(d[0]) - 1.0)
    jit_clk = 1.0 + 0.02 * (2.0 * _uniform(d[1]) - 1.0)
    n = (hw["rows"] * hw["cols"]).astype(np.float64)
    spad_bits = (hw["ifmap"] * ACT_BITS[t] + hw["filt"] * WEIGHT_BITS[t]
                 + hw["psum"] * PSUM_BITS[t])
    glb_bits = hw["glb_kb"] * 8192
    pe_area = MAC_AREA_UM2[t] + _sram_area(spad_bits.astype(np.float64))
    noc_area = 120.0 * n * (1.0 + 0.004 * np.sqrt(n))
    area = (n * pe_area + _sram_area(glb_bits.astype(np.float64))
            + noc_area) * jit_area / 1e6
    clock = (1.0 / MAC_DELAY_NS[t]) / (1.0 + 0.002 * np.sqrt(n)) * jit_clk
    hw.update(area_mm2=q(area), clock_ghz=q(clock), spad_bits=spad_bits,
              glb_bits=glb_bits,
              leak_mw=n * LEAK_UW[t] * 1e-3 + 0.002 * hw["glb_kb"])
    return hw


# ---------------------------------------------------------------------------
# Workload: a network through its layer model
# ---------------------------------------------------------------------------

def evaluate(hw: dict, network, modes: np.ndarray,
             prec: str = "f64") -> dict:
    """Aggregates of one network on a config batch.

    ``network`` carries its rows and its layer model (``spec.Network``);
    ``modes`` is the ``(N, L)`` execution mode (PE-type index) of every
    layer on every config.  Returns per-config ``latency_s``,
    ``energy_j``, ``throughput_gmacs`` and ``perf_per_area``."""
    return aggregate(network.model.table(hw, network.rows, modes), hw, prec)


def take(tab: dict, idx: np.ndarray) -> dict:
    """The rows ``idx`` of a layer model's table."""
    return {k: v if k == "macs" else v[idx] for k, v in tab.items()}


def aggregate(tab: dict, hw: dict, prec: str = "f64") -> dict:
    """Per-config aggregates from a layer model's table and the
    bandwidth, clock, area and leakage of each config: a layer takes the
    longer of its compute and its DRAM transfer, and leaks for as long."""
    q = rounder(prec)
    clock = hw["clock_ghz"][:, None]
    mem = np.trunc(tab["dram_b"] / np.maximum(1e-9, hw["bw"][:, None]
                                               / clock))
    total = np.maximum(tab["compute"].astype(np.float64), mem)
    layer_pj = tab["pj"] + (hw["leak_mw"][:, None] * 1e-3
                            * (total / (clock * 1e9)) * 1e12)
    cycles = q(total).sum(axis=1)
    energy = q(layer_pj).sum(axis=1)
    latency = q(cycles / (hw["clock_ghz"] * 1e9))
    thr = q(tab["macs"] / latency / 1e9)
    return {"latency_s": latency, "energy_j": q(energy / 1e12),
            "throughput_gmacs": thr, "perf_per_area": q(thr / hw["area_mm2"])}


# ---------------------------------------------------------------------------
# Accuracy: tier-0 quantization-noise proxy
# ---------------------------------------------------------------------------

def _qdq_int(x, bits):
    dt = x.dtype.type
    qmax = dt(2 ** (bits - 1) - 1)
    scale = np.maximum(np.abs(x).max(), dt(1e-8)) / qmax
    return (np.clip(np.round(x / scale), -qmax, qmax).astype(x.dtype)
            * scale)


def _pow2(w, scale):
    dt = w.dtype.type
    e = np.round(np.log2(np.maximum(np.abs(w) / scale, dt(2.0 ** -7))))
    e = (np.clip(e + dt(7), dt(0), dt(7)) - dt(7)).astype(w.dtype)
    sign = np.where(w < 0, dt(-1.0), dt(1.0))
    return sign * np.exp2(e) * scale


def _qdq_pow2(w, terms):
    scale = np.maximum(np.abs(w).max(), w.dtype.type(1e-8))
    v1 = _pow2(w, scale)
    if terms == 1:
        return v1
    v2 = _pow2(w - v1, scale)
    return np.where(np.abs(w - (v1 + v2)) < np.abs(w - v1), v1 + v2, v1)


def noise_table(prec: str = "f64") -> np.ndarray:
    """Relative quantization-noise power per PE type: weight plus
    activation noise of each datapath's quantizers on the fixed synthetic
    tensors the proxy is defined on.  The quantizers run in float32, as
    the program states, or in bfloat16 for the control."""
    dt = np.float32 if prec == "f64" else ml_dtypes.bfloat16
    rng = np.random.default_rng(20220516)
    w = rng.normal(size=8192).astype(np.float32).astype(dt)
    x = np.abs(rng.normal(size=8192)).astype(np.float32).astype(dt)

    def rel(v, qv):
        v64 = v.astype(np.float64)
        return float(np.mean((v64 - qv.astype(np.float64)) ** 2)
                     / np.mean(v64 ** 2))

    return rounder(prec)([
        0.0,
        rel(w, _qdq_int(w, 16)) + rel(x, _qdq_int(x, 16)),
        rel(w, _qdq_pow2(w, 1)) + rel(x, _qdq_int(x, 8)),
        rel(w, _qdq_pow2(w, 2)) + rel(x, _qdq_int(x, 8)),
    ])


def accuracy_noise(modes: np.ndarray, network, table: np.ndarray):
    """MAC-weighted noise power of each config's layer modes, with each
    layer's MACs from the network's layer model."""
    macs = np.array(network.model.layer_macs(network.rows),
                    dtype=np.float64)
    return (table[modes] * (macs / macs.sum())).sum(axis=1)


# ---------------------------------------------------------------------------
# Serving fleet: event-driven continuous batcher, one candidate at a time
# ---------------------------------------------------------------------------

def fleet(step_s: float, e_token_j: float, arrive, svc,
          n_slots: int) -> tuple[float, float]:
    """``(p99 latency, energy per served token)`` of one candidate.

    ``arrive`` is each request's first admissible iteration and ``svc``
    the iterations it holds a slot (``prompt + decode - 1``).  FIFO
    admission into the earliest-free slot, lowest index first; an
    iteration of ``step_s`` seconds with any busy slot costs
    ``n_slots * e_token_j``."""
    free_at = np.zeros(n_slots, np.int64)
    comp = np.zeros(len(svc), np.int64)
    spans = []
    for i in range(len(svc)):
        slot = int(np.argmin(free_at))
        start = max(int(arrive[i]), int(free_at[slot]))
        comp[i] = start + int(svc[i])
        free_at[slot] = comp[i]
        spans.append((start, int(comp[i])))
    active, cur_s, cur_e = 0, -1, -1
    for s0, e0 in sorted(spans):
        if s0 > cur_e:
            active += max(0, cur_e - cur_s)
            cur_s, cur_e = s0, e0
        else:
            cur_e = max(cur_e, e0)
    active += max(0, cur_e - cur_s)
    lat = (comp - arrive).astype(np.float64) * step_s
    p99 = float(np.percentile(lat, 99.0))
    ept = active * n_slots * e_token_j / float(np.sum(svc))
    return min(p99, FLOOR_PENALTY), min(ept, FLOOR_PENALTY)


def fleet_outcomes(step_s: float, e_token_j: float, arrival_s, svc,
                   n_slots: int, rtol: float) -> list:
    """Every :func:`fleet` outcome for a step within ``rtol`` of
    ``step_s``.

    Arrival iterations are ``ceil(arrival / step)``, so a step that
    differs in its last float32 digits can move one of them by an
    iteration.  One outcome is taken inside each interval between the
    steps at which some arrival iteration changes; all use ``step_s`` and
    ``e_token_j`` for seconds and joules."""
    a = np.asarray(arrival_s, np.float64)
    lo, hi = step_s * (1.0 - rtol), step_s * (1.0 + rtol)
    cuts = sorted(ai / m for ai in a[a > 0]
                  for m in range(int(np.ceil(ai / hi)),
                                 int(np.floor(ai / lo)) + 1)
                  if lo < ai / m < hi)
    edges = [lo] + cuts + [hi]
    return [fleet(step_s, e_token_j,
                  np.ceil(a / (0.5 * (s0 + s1))).astype(np.int64), svc,
                  n_slots)
            for s0, s1 in zip(edges[:-1], edges[1:])]
