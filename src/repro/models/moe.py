"""Top-k MoE layer with sort-based dispatch and expert parallelism.

Dispatch is MegaBlocks-style (sort by expert, equal-capacity buffers)
rather than GShard one-hot einsums: the (E, C, d) buffer keeps the
expert GEMMs dense and MXU-shaped, the scatter/gather is cheap data
movement, and the buffer's expert dim shards over the "model" mesh axis
(EP) so XLA lowers dispatch/combine to all-to-all traffic.

Router runs in f32 (precision-sensitive; see quant/policy.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard
from repro.quant.qlinear import qdot


def topk_route(x, w_router, n_experts: int, top_k: int):
    """x: (T, d) -> (gates (T,k) f32, experts (T,k) int32, router aux loss)."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)            # (T, E)
    gates, experts = jax.lax.top_k(probs, top_k)       # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum(frac_tokens * frac_probs)
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(experts[:, 0], n_experts, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)
    aux = n_experts * jnp.sum(me * ce)
    return gates, experts, aux


def moe_ffn_ep(x, p, cfg, *, policy, train, capacity_factor: float = 1.25):
    """Expert-parallel MoE via shard_map (EXPERIMENTS.md §Perf, cell B).

    The pjit scatter into a ("model"-sharded) global (E, C, d) buffer
    lowers as replicate + all-reduce of the whole buffer (~64 GB/layer for
    moonshot) — measured at 15.5 TB/step/device of all-reduce traffic.
    Here each (data x model) device dispatches its *local* tokens to its
    *local* experts only (tokens are replicated across "model" at block
    entry, experts are sharded over "model"), runs the local expert GEMMs,
    and a single activation-sized psum over "model" sums the top-k
    contributions.  No buffer-sized collectives remain.
    """
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import _mesh, data_axes

    mesh = _mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_ffn(x, p, cfg, policy=policy, train=train,
                       capacity_factor=capacity_factor)
    db = data_axes(mesh)
    E, K = cfg.n_experts, cfg.top_k
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    if E % n_model != 0:
        return moe_ffn(x, p, cfg, policy=policy, train=train,
                       capacity_factor=capacity_factor)

    def body(x_l, router, wg, wi, wo):
        b_l, s, d = x_l.shape
        T = b_l * s
        xf = x_l.reshape(T, d)
        gates, experts, aux = topk_route(xf, router, E, K)

        e_l = wg.shape[0]                      # local experts
        e0 = jax.lax.axis_index("model") * e_l
        flat_expert = experts.reshape(-1)
        flat_token = jnp.repeat(jnp.arange(T), K)
        flat_gate = gates.reshape(-1)
        local = (flat_expert >= e0) & (flat_expert < e0 + e_l)
        le = jnp.where(local, flat_expert - e0, 0)
        order = jnp.argsort(jnp.where(local, le, e_l))   # non-local last
        se, st, sg, keepmask = (le[order], flat_token[order],
                                flat_gate[order], local[order])
        counts = jnp.bincount(jnp.where(keepmask, se, e_l),
                              length=e_l + 1)[:e_l]
        starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(T * K) - jnp.where(keepmask, starts[se], 0)
        C = int(max(1, -(-T * K // E) * capacity_factor))
        keep = keepmask & (pos < C)

        buf = jnp.zeros((e_l, C, d), xf.dtype)
        idx_e = jnp.where(keep, se, 0)
        idx_c = jnp.where(keep, pos, 0)
        vals = jnp.where(keep[:, None], xf[st], 0.0)
        buf = buf.at[idx_e, idx_c].add(vals)

        def edot(a, w):
            if train and policy.quantized:
                from repro.quant.qlinear import qat_act, qat_weight
                a = qat_act(a, policy)
                w = qat_weight(w, policy, axis=1)
            return jnp.einsum("ecd,edf->ecf",
                              a.astype(policy.compute_dtype),
                              w.astype(policy.compute_dtype))

        g = edot(buf, wg)
        u = edot(buf, wi)
        hbuf = jax.nn.silu(g) * u
        out_buf = jnp.einsum("ecf,efd->ecd",
                             hbuf.astype(policy.compute_dtype),
                             wo.astype(policy.compute_dtype))
        gathered = out_buf[idx_e, idx_c]
        gathered = jnp.where(keep[:, None], gathered, 0.0)
        weighted = gathered * sg[:, None].astype(gathered.dtype)
        out = jax.ops.segment_sum(weighted, st, num_segments=T)
        out = jax.lax.psum(out.astype(jnp.float32), "model")
        aux = jax.lax.pmean(aux, db)   # varies over data axes only
        return out.reshape(b_l, s, d).astype(x_l.dtype), aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(db, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(db, None, None), P()),
    )(x, p["router"], p["w_experts_gate"], p["w_experts_in"],
      p["w_experts_out"])
    return out, aux


def moe_ffn(x, p, cfg, *, policy, train, capacity_factor: float = 1.25):
    """x: (b, s, d) -> (b, s, d).  p: router (d,E),
    w_experts_gate/in (E,d,ff), w_experts_out (E,ff,d)."""
    b, s, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = b * s
    xf = x.reshape(T, d)

    gates, experts, aux = topk_route(xf, p["router"], E, K)

    # ---- sort-based dispatch -------------------------------------------
    flat_expert = experts.reshape(-1)                     # (T*K,)
    flat_token = jnp.repeat(jnp.arange(T), K)             # (T*K,)
    flat_gate = gates.reshape(-1)
    order = jnp.argsort(flat_expert)                      # stable
    se, st, sg = (flat_expert[order], flat_token[order], flat_gate[order])
    # position of each entry within its expert group
    counts = jnp.bincount(se, length=E)                   # (E,)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * K) - starts[se]                  # rank in expert
    C = int(max(1, -(-T * K // E) * capacity_factor))     # per-expert cap
    keep = pos < C

    # scatter tokens into the (E, C, d) expert buffer (dropped -> zeros)
    buf = jnp.zeros((E, C, d), xf.dtype)
    idx_e = jnp.where(keep, se, 0)
    idx_c = jnp.where(keep, pos, 0)
    vals = jnp.where(keep[:, None], xf[st], 0.0)
    buf = buf.at[idx_e, idx_c].add(vals)
    buf = shard(buf, "moe_buffer")

    # ---- expert FFNs (batched GEMMs, EP-sharded on E) --------------------
    from repro.models.common import swiglu_mlp  # noqa: F401 (same math)
    def edot(a, w):
        if train and policy.quantized:
            from repro.quant.qlinear import qat_act, qat_weight
            a = qat_act(a, policy)
            w = qat_weight(w, policy, axis=1)
        return jnp.einsum("ecd,edf->ecf", a.astype(policy.compute_dtype),
                          w.astype(policy.compute_dtype))

    g = edot(buf, p["w_experts_gate"])
    u = edot(buf, p["w_experts_in"])
    hbuf = jax.nn.silu(g) * u
    out_buf = jnp.einsum("ecf,efd->ecd",
                         hbuf.astype(policy.compute_dtype),
                         p["w_experts_out"].astype(policy.compute_dtype))
    out_buf = shard(out_buf, "moe_buffer")

    # ---- combine ----------------------------------------------------------
    gathered = out_buf[idx_e, idx_c]                      # (T*K, d)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    weighted = gathered * sg[:, None].astype(gathered.dtype)
    out = jax.ops.segment_sum(weighted, st, num_segments=T)
    return out.reshape(b, s, d).astype(x.dtype), aux
