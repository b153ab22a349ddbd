"""Layer model ``mla_moe_decode``: one decode step of a DeepSeek-V2/V3-style
model, multi-head latent attention (MLA) and routed plus shared experts,
as grouped GEMMs on the row-stationary mapping.

A network's ``layers`` is one object: the model's published
``config.json`` keys (``hidden_size``, ``num_hidden_layers``,
``first_k_dense_replace``, ``moe_layer_freq``, ``intermediate_size``,
``n_routed_experts``, ``moe_intermediate_size``, ``num_experts_per_tok``,
``n_shared_experts``, ``num_attention_heads``, ``q_lora_rank``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``vocab_size``), plus ``batch`` (B sequences, each
decoding one token) and ``context`` (C cached positions a sequence).
:func:`gemms` expands it into rows ``(name, m, k, n, g)``: g independent
GEMMs ``[m x k] . [k x n]``, each with its own ``[k x n]`` operand, run
one after another.  Per layer: ``q_proj``, ``kv_a``, MLA in its absorbed
decode form (``q_absorb`` and ``v_absorb`` one group a head, ``scores``
and ``context`` one group a sequence, over that sequence's latent
cache), ``o_proj``; then a dense MLP, or the router, the shared experts
fused and the routed experts (one group an expert, each on ``B * top_k /
n_routed_experts`` tokens); the LM head last.  Element-wise work and the
embedding lookup are not priced; routing is uniform.

A GEMM is a 1x1 convolution over an ``m x 1`` map (``h = m``, ``c = k``,
``k = n``), so the row-stationary mapping puts its m rows across the
array's columns.  A group's operands are what sit in the buffers, so the
GLB residency tests use one group's sizes; compute cycles, DRAM bytes
and the energy without leakage are g times a group's.

Everything but :func:`program_network` is part of the plain reference: a
float64 numpy restatement that imports nothing of ``repro``.

Kernel work per (config, layer) pair: the 78 operations of
``row_stationary`` (see its table) and three group multiplications, of
the compute cycles, the DRAM bytes and the energy without leakage: 81 a
pair.  The layer table holds 11 float32 fields a layer: row_stationary's
10 and the group count.
"""

from __future__ import annotations

import numpy as np

from harness.reference import (ACT_BITS, MAC_ENERGY_PJ, WEIGHT_BITS,
                               rf_energy, sram_energy)

OPS_PER_LAYER = 78 + 3
LAYER_FIELDS = 10 + 1


def _cdiv(a, b):
    return -(-a // b)


def gemms(rows) -> list[tuple[str, int, int, int, int]]:
    """The decode step's rows ``(name, m, k, n, groups)`` in layer order."""
    b, ctx = rows["batch"], rows["context"]
    d, heads = rows["hidden_size"], rows["num_attention_heads"]
    nope, rope = rows["qk_nope_head_dim"], rows["qk_rope_head_dim"]
    lat, v = rows["kv_lora_rank"], rows["v_head_dim"]
    n_exp, top_k = rows["n_routed_experts"], rows["num_experts_per_tok"]
    moe_w = rows["moe_intermediate_size"]
    shared_w = rows["n_shared_experts"] * moe_w
    if rows["q_lora_rank"] is not None:
        raise ValueError("q_lora_rank is not modelled")
    if b * top_k % n_exp:
        raise ValueError(f"{b} x {top_k} routed tokens do not spread evenly "
                         f"over {n_exp} experts")
    tokens = b * top_k // n_exp
    out = []
    for i in range(rows["num_hidden_layers"]):
        out += [(f"L{i}.q_proj", b, d, heads * (nope + rope), 1),
                (f"L{i}.kv_a", b, d, lat + rope, 1),
                (f"L{i}.q_absorb", b, nope, lat, heads),
                (f"L{i}.scores", heads, lat + rope, ctx, b),
                (f"L{i}.context", heads, ctx, lat, b),
                (f"L{i}.v_absorb", b, lat, v, heads),
                (f"L{i}.o_proj", b, heads * v, d, 1)]
        if i >= rows["first_k_dense_replace"] \
                and i % rows.get("moe_layer_freq", 1) == 0:
            out += [(f"L{i}.router", b, d, n_exp, 1),
                    (f"L{i}.shared_gate_up", b, d, 2 * shared_w, 1),
                    (f"L{i}.shared_down", b, shared_w, d, 1),
                    (f"L{i}.experts_gate_up", tokens, d, 2 * moe_w, n_exp),
                    (f"L{i}.experts_down", tokens, moe_w, d, n_exp)]
        else:
            w = rows["intermediate_size"]
            out += [(f"L{i}.mlp_gate_up", b, d, 2 * w, 1),
                    (f"L{i}.mlp_down", b, w, d, 1)]
    out.append(("lm_head", b, d, rows["vocab_size"], 1))
    return out


def layer_macs(rows) -> list[int]:
    """MACs of each row, all its groups."""
    return [g * m * k * n for _, m, k, n, g in gemms(rows)]


def table(hw: dict, rows, modes: np.ndarray) -> dict:
    """The per-row quantities that neither the DRAM bandwidth nor the
    synthesized clock and area change, each ``(N, L)``: compute cycles,
    DRAM bytes, and the energy without leakage (pJ); and the step's
    MACs."""
    pe_rows, cols, glb_kb = hw["rows"], hw["cols"], hw["glb_kb"]
    e_spad_pj = rf_energy(hw["spad_bits"].astype(np.float64))
    e_glb_pj = sram_energy(hw["glb_bits"].astype(np.float64))
    glb_half = glb_kb * 1024 // 2
    filt_res = np.maximum(1, hw["filt"])
    spill_ok = hw["psum"] >= 1            # a psum strip is one output
    expanded = gemms(rows)
    shape = (len(pe_rows), len(expanded))
    tab = {"compute": np.zeros(shape, np.int64),
           "dram_b": np.zeros(shape, np.int64),
           "pj": np.zeros(shape)}
    total_macs = 0
    for j, (_, m, k, n, g) in enumerate(expanded):
        ab, wb = ACT_BITS[modes[:, j]], WEIGHT_BITS[modes[:, j]]
        # k inputs stack down the array, m rows fold across its columns
        c_sim = np.minimum(k, pe_rows)
        k_sim = np.maximum(1, pe_rows // c_sim)
        n_m = _cdiv(m, np.minimum(m, cols))
        n_c, n_n = _cdiv(k, c_sim), _cdiv(n, k_sim)
        # one group's operands; residency in the GLB is a group's
        in_el, w_el, out_el = m * k, k * n, m * n
        in_b = in_el * ab // 8
        n_glb = _cdiv(n, np.maximum(1, glb_half // np.maximum(1, k * wb // 8)))
        restream = np.where(in_b <= glb_half, 1, n_glb)
        dram_b = in_b * restream + w_el * wb // 8 + out_el * ab // 8
        dram_el = in_el * restream + w_el + out_el
        glb_el = (2 * dram_el + in_el * _cdiv(n_n, filt_res)
                  + w_el * np.maximum(1, n_m // np.minimum(n_m, filt_res))
                  + 2 * out_el * np.maximum(0, np.where(spill_ok, 0,
                                                        n_c - 1)))
        macs = m * k * n
        tab["compute"][:, j] = g * (n_m * n_c * n_n)
        tab["dram_b"][:, j] = g * dram_b
        tab["pj"][:, j] = g * (macs * MAC_ENERGY_PJ[modes[:, j]]
                               + 3 * macs * e_spad_pj + glb_el * e_glb_pj)
        total_macs += g * macs
    tab["macs"] = total_macs
    return tab


def kernel_work(rows) -> tuple[int, int]:
    """Operations per config and float32 bytes of the layer table."""
    n_rows = len(gemms(rows))
    return OPS_PER_LAYER * n_rows, 4 * LAYER_FIELDS * n_rows


def program_network(network):
    """The program's workload for ``network``, built from the same keys."""
    from repro.core.workloads import Workload, mla_moe_decode_step
    rows = network.rows
    step = mla_moe_decode_step(rows, batch=rows["batch"],
                               context=rows["context"])
    return Workload(network.name, step.layers)
