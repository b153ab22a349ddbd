"""Layer-wise DNN configurations (the paper's workload input, Fig. 1).

The paper evaluates VGG-16 (Simonyan & Zisserman 2014), ResNet-34 and
ResNet-50 (He et al. 2016).  A layer is a conv ``(H, W, C, K, R, S, stride)``
or an FC (conv with R=S=H=W=1).  Shapes are ImageNet-224 standard.

A layer with ``groups = g`` stands for g independent copies of its
one-group layer, run one after another, each with its own ifmap, weight
operand and ofmap: per-head projections, per-sequence attention over a
KV cache, routed experts (:func:`gemm`, :func:`mla_moe_decode_step`).
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class ConvLayer:
    name: str
    h: int          # input feature-map height
    w: int          # input feature-map width
    c: int          # input channels
    k: int          # output channels (filters)
    r: int = 3      # filter height
    s: int = 3      # filter width
    stride: int = 1
    batch: int = 1
    groups: int = 1  # independent copies, each with its own operands

    @property
    def e(self) -> int:  # output height
        return max(1, (self.h - self.r) // self.stride + 1)

    @property
    def f(self) -> int:  # output width
        return max(1, (self.w - self.s) // self.stride + 1)

    @property
    def macs(self) -> int:
        return (self.groups * self.batch * self.k * self.c * self.r
                * self.s * self.e * self.f)


def fc(name: str, cin: int, cout: int, batch: int = 1) -> ConvLayer:
    return ConvLayer(name=name, h=1, w=1, c=cin, k=cout, r=1, s=1,
                     stride=1, batch=batch)


def gemm(name: str, m: int, k: int, n: int, groups: int = 1) -> ConvLayer:
    """``groups`` independent GEMMs ``[m x k] . [k x n]``, each with its own
    ``[k x n]`` operand, as a 1x1 convolution over an ``m x 1`` map.

    The m rows sit where a convolution's output rows sit, across the
    array's columns (``fit_horz = min(m, pe_cols)``), so m tokens run in
    parallel.  :func:`fc` with ``batch=m`` would instead run them as m
    passes, one column busy each: an LM head at 64 tokens would price at
    about 3% utilization on a 32x32 array."""
    return ConvLayer(name=name, h=m, w=1, c=k, k=n, r=1, s=1, stride=1,
                     batch=1, groups=groups)


@dataclasses.dataclass(frozen=True)
class Workload:
    name: str
    layers: tuple[ConvLayer, ...]

    @property
    def total_macs(self) -> int:
        return sum(l.macs for l in self.layers)


def vgg16() -> Workload:
    ls: list[ConvLayer] = []
    cfg = [  # (h, c, k, repeat)
        (224, 3, 64, 1), (224, 64, 64, 1),
        (112, 64, 128, 1), (112, 128, 128, 1),
        (56, 128, 256, 1), (56, 256, 256, 2),
        (28, 256, 512, 1), (28, 512, 512, 2),
        (14, 512, 512, 3),
    ]
    i = 0
    for h, c, k, rep in cfg:
        for _ in range(rep):
            i += 1
            # 'same' padding modeled by padding the input by r-1
            ls.append(ConvLayer(f"conv{i}", h + 2, h + 2, c, k, 3, 3, 1))
    ls.append(fc("fc6", 512 * 7 * 7, 4096))
    ls.append(fc("fc7", 4096, 4096))
    ls.append(fc("fc8", 4096, 1000))
    return Workload("vgg16", tuple(ls))


def _resnet_stem() -> list[ConvLayer]:
    return [ConvLayer("conv1", 230, 230, 3, 64, 7, 7, 2)]


def resnet34() -> Workload:
    ls = _resnet_stem()
    stages = [  # (n_blocks, channels, fmap)
        (3, 64, 56), (4, 128, 28), (6, 256, 14), (3, 512, 7),
    ]
    cin = 64
    for si, (nb, ch, fm) in enumerate(stages):
        for b in range(nb):
            stride = 2 if (b == 0 and si > 0) else 1
            h_in = fm * stride
            ls.append(ConvLayer(f"s{si}b{b}a", h_in + 2, h_in + 2, cin, ch,
                                3, 3, stride))
            ls.append(ConvLayer(f"s{si}b{b}b", fm + 2, fm + 2, ch, ch, 3, 3, 1))
            if stride != 1 or cin != ch:
                ls.append(ConvLayer(f"s{si}b{b}ds", h_in, h_in, cin, ch,
                                    1, 1, stride))
            cin = ch
    ls.append(fc("fc", 512, 1000))
    return Workload("resnet34", tuple(ls))


def resnet50() -> Workload:
    ls = _resnet_stem()
    stages = [  # (n_blocks, bottleneck_ch, fmap)
        (3, 64, 56), (4, 128, 28), (6, 256, 14), (3, 512, 7),
    ]
    cin = 64
    for si, (nb, ch, fm) in enumerate(stages):
        cout = ch * 4
        for b in range(nb):
            stride = 2 if (b == 0 and si > 0) else 1
            h_in = fm * stride
            ls.append(ConvLayer(f"s{si}b{b}a", h_in, h_in, cin, ch,
                                1, 1, stride))
            ls.append(ConvLayer(f"s{si}b{b}b", fm + 2, fm + 2, ch, ch, 3, 3, 1))
            ls.append(ConvLayer(f"s{si}b{b}c", fm, fm, ch, cout, 1, 1, 1))
            if stride != 1 or cin != cout:
                ls.append(ConvLayer(f"s{si}b{b}ds", h_in, h_in, cin, cout,
                                    1, 1, stride))
            cin = cout
    ls.append(fc("fc", 2048, 1000))
    return Workload("resnet50", tuple(ls))


def mla_moe_decode_step(cfg: Mapping, batch: int, context: int) -> Workload:
    """One decode step of a DeepSeek-V2/V3-style model: multi-head latent
    attention (MLA) and routed plus shared experts, as GEMM rows.

    ``cfg`` holds the model's published ``config.json`` keys
    (``hidden_size``, ``num_hidden_layers``, ``first_k_dense_replace``,
    ``moe_layer_freq``, ``intermediate_size``, ``n_routed_experts``,
    ``moe_intermediate_size``, ``num_experts_per_tok``,
    ``n_shared_experts``, ``num_attention_heads``, ``q_lora_rank``,
    ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``, ``vocab_size``); ``batch`` sequences each decode one
    token against ``context`` cached positions.  Rows per layer ``i``,
    with B = ``batch``, C = ``context``:

    * ``L{i}.q_proj``, ``L{i}.kv_a`` (latent plus rope key), then MLA in
      its absorbed decode form (DeepSeek-V2, arXiv:2405.04434, §2.1):
      ``q_absorb`` (``W_UK``, one group a head), ``scores`` (the heads
      against the sequence's latent and rope cache, one group a
      sequence), ``context`` (the scores against the sequence's latent
      cache), ``v_absorb`` (``W_UV``, one group a head), ``o_proj``;
    * a dense layer: ``mlp_gate_up``, ``mlp_down``;
    * an MoE layer: ``router``, the shared experts fused
      (``shared_gate_up``, ``shared_down``), and the routed experts
      (``experts_gate_up``, ``experts_down``, one group an expert, each
      on its ``B * top_k / n_routed_experts`` tokens);

    and ``lm_head`` last.  Departures from the model: element-wise work
    (RMSNorm, RoPE, SiLU gating, softmax, sigmoid routing and top-k) and
    the embedding lookup are not priced, as the paper prices no BN or
    ReLU; routing is uniform; the scores and context rows each stream the
    cache (no fused attention unit); the cache is the weight operand of
    its rows, so it is held in the PE's weight format.
    """
    hidden = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    latent, v_dim = cfg["kv_lora_rank"], cfg["v_head_dim"]
    experts, top_k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("a low-rank query projection (q_lora_rank) is "
                         "not modelled")
    if batch * top_k % experts:
        raise ValueError(f"batch {batch} x {top_k} experts a token does not "
                         f"spread evenly over {experts} routed experts")
    per_expert = batch * top_k // experts
    shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    with obs_trace.span("workload.build", batch=batch,
                        context=context) as sp:
        rows: list[ConvLayer] = []
        for i in range(cfg["num_hidden_layers"]):
            rows += [
                gemm(f"L{i}.q_proj", batch, hidden, heads * (nope + rope)),
                gemm(f"L{i}.kv_a", batch, hidden, latent + rope),
                gemm(f"L{i}.q_absorb", batch, nope, latent, groups=heads),
                gemm(f"L{i}.scores", heads, latent + rope, context,
                     groups=batch),
                gemm(f"L{i}.context", heads, context, latent,
                     groups=batch),
                gemm(f"L{i}.v_absorb", batch, latent, v_dim, groups=heads),
                gemm(f"L{i}.o_proj", batch, heads * v_dim, hidden),
            ]
            if i < cfg["first_k_dense_replace"] \
                    or i % cfg.get("moe_layer_freq", 1):
                inter = cfg["intermediate_size"]
                rows += [gemm(f"L{i}.mlp_gate_up", batch, hidden, 2 * inter),
                         gemm(f"L{i}.mlp_down", batch, inter, hidden)]
            else:
                inter = cfg["moe_intermediate_size"]
                rows += [
                    gemm(f"L{i}.router", batch, hidden, experts),
                    gemm(f"L{i}.shared_gate_up", batch, hidden, 2 * shared),
                    gemm(f"L{i}.shared_down", batch, shared, hidden),
                    gemm(f"L{i}.experts_gate_up", per_expert, hidden,
                         2 * inter, groups=experts),
                    gemm(f"L{i}.experts_down", per_expert, inter, hidden,
                         groups=experts),
                ]
        rows.append(gemm("lm_head", batch, hidden, cfg["vocab_size"]))
        wl = Workload(f"mla_moe_decode_b{batch}_c{context}", tuple(rows))
        sp.set(rows=len(rows),
               grouped_rows=sum(l.groups > 1 for l in rows),
               macs=wl.total_macs)
    return wl


WORKLOADS = {
    "vgg16": vgg16,
    "resnet34": resnet34,
    "resnet50": resnet50,
}


def get_workload(name: str) -> Workload:
    return WORKLOADS[name]()
