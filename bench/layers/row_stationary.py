"""Layer model ``row_stationary``: convolution and fully connected layers
mapped on the row-stationary dataflow, the QAPPA paper's mapping.

A row is ``[name, h, w, c, k, r, s, stride, batch]``: input height and
width (padding included), input channels, filters, filter height and
width, stride, batch.  Each row is one layer of the kernel's layer axis.

Everything but :func:`program_network` is part of the plain reference: a
float64 numpy restatement of ``core/dataflow.py``'s mapping and energy
model that imports nothing of ``repro``.

Kernel work per (config, layer) pair: the arithmetic of :func:`table` and
of ``reference.aggregate`` that depends on the config, as written there,
counting each of ``+ - * / // max min floor ceil`` and each
compare-and-select as one operation:

=====================================================  ====
spatial mapping: sets_fit 2, c_sim 1, k_sim 2,           16
fit_horz 1, three ceil-divisions 6, compute cycles 4
byte counts: ifmap/weight/ofmap bytes 9, filter bytes    42
3, k_fit 2, n_k_glb 2, restream 2, dram bytes 3, dram
elements 3, filt_res 2, w_res 1, spill 3, glb ifmap 3,
glb weight 3, glb psum 2, glb elements 4
stalls: memory cycles 2, total cycles 1                   3
energy: spad 1, MAC 1, GLB 1, leakage 3, sum 3            9
compensated sums of cycles and energy, 4 each             8
=====================================================  ====

78 a pair; the layer table holds 10 float32 fields a layer.
"""

from __future__ import annotations

import numpy as np

from harness.reference import (ACT_BITS, MAC_ENERGY_PJ, WEIGHT_BITS,
                               rf_energy, sram_energy)

OPS_PER_LAYER = 78
LAYER_FIELDS = 10


def _cdiv(a, b):
    return -(-a // b)


def layer_fields(layer) -> dict:
    """A row ``[name, h, w, c, k, r, s, stride, batch]`` as integers,
    with its output size and MACs."""
    _, h, w, c, k, r, s, stride, batch = layer
    e = max(1, (h - r) // stride + 1)
    f = max(1, (w - s) // stride + 1)
    return dict(h=h, w=w, c=c, k=k, r=r, s=s, e=e, f=f, n=batch,
                macs=batch * k * c * r * s * e * f)


def layer_macs(rows) -> list[int]:
    """MACs of each layer, one per row."""
    return [layer_fields(row)["macs"] for row in rows]


def table(hw: dict, rows, modes: np.ndarray) -> dict:
    """The per-layer quantities that neither the DRAM bandwidth nor the
    synthesized clock and area change, each ``(N, L)``: compute cycles,
    DRAM bytes, and the energy without leakage (pJ); and the network's
    MACs."""
    pe_rows, cols, glb_kb = hw["rows"], hw["cols"], hw["glb_kb"]
    e_spad_pj = rf_energy(hw["spad_bits"].astype(np.float64))
    e_glb_pj = sram_energy(hw["glb_bits"].astype(np.float64))
    shape = (len(pe_rows), len(rows))
    tab = {"compute": np.zeros(shape, np.int64),
           "dram_b": np.zeros(shape, np.int64),
           "pj": np.zeros(shape)}
    total_macs = 0
    for j, layer in enumerate(rows):
        x = layer_fields(layer)
        r, s, e, f, c, k, n = (x[v] for v in "r s e f c k n".split())
        ab, wb = ACT_BITS[modes[:, j]], WEIGHT_BITS[modes[:, j]]
        sets_fit = np.maximum(1, pe_rows // r)
        c_sim = np.minimum(c, sets_fit)
        k_sim = np.maximum(1, sets_fit // c_sim)
        fit_horz = np.minimum(e, cols)
        n_e, n_c, n_k = _cdiv(e, fit_horz), _cdiv(c, c_sim), _cdiv(k, k_sim)
        compute = n * n_e * n_c * n_k * s * f
        ifmap_el = n * c * x["h"] * x["w"]
        weight_el = k * c * r * s
        ofmap_el = n * k * e * f
        ifmap_b = ifmap_el * ab // 8
        glb_half = glb_kb * 1024 // 2
        filt_one = np.maximum(1, c * r * s * wb // 8)
        n_k_glb = _cdiv(k, np.maximum(1, glb_half // filt_one))
        restream = np.where(ifmap_b <= glb_half, 1, n_k_glb)
        dram_b = ifmap_b * restream + weight_el * wb // 8 + ofmap_el * ab // 8
        dram_el = ifmap_el * restream + weight_el + ofmap_el
        filt_res = np.maximum(1, hw["filt"] // max(1, s))
        glb_el = (2 * dram_el + ifmap_el * _cdiv(n_k, filt_res)
                  + weight_el * np.maximum(1, n_e // np.minimum(n_e, filt_res))
                  + 2 * ofmap_el * np.maximum(
                      0, np.where(hw["psum"] >= f, 0, n_c - 1)))
        macs = x["macs"]
        tab["compute"][:, j] = compute
        tab["dram_b"][:, j] = dram_b
        tab["pj"][:, j] = (macs * MAC_ENERGY_PJ[modes[:, j]]
                           + 3 * macs * e_spad_pj + glb_el * e_glb_pj)
        total_macs += macs
    tab["macs"] = total_macs
    return tab


def kernel_work(rows) -> tuple[int, int]:
    """Operations per config and float32 bytes of the layer table."""
    return OPS_PER_LAYER * len(rows), 4 * LAYER_FIELDS * len(rows)


def program_network(network):
    """The program's workload for ``network``: one ``ConvLayer`` a row."""
    from repro.core.workloads import ConvLayer, Workload
    return Workload(network.name,
                    tuple(ConvLayer(*row) for row in network.rows))
