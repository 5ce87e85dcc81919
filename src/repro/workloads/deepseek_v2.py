"""DeepSeek-V2-Lite prefill as a CIM graph (arXiv:2405.04434).

Widths come from ``configs.deepseek_v2_lite_16b`` (the published
``config.json``); the cut is given as arguments: depth (leading dense
layers, MoE layers), the routed experts this chip holds (one rank of an
expert-parallel deployment: rank r holds experts r*held .. r*held+held-1
of the router's ``n_experts``) and the vocabulary slice of the LM head.

Every projection is a crossbar ``Gemm``; attention, norms, RoPE,
routing and the combine run on the ALU under ``cimsim.functional``'s
integer contract.  int8 activations feeding an ALU rule are read as Q4
(``frac`` 4: real = v / 16), the format the rules' outputs keep.

* MLA, as published for Lite (no q LoRA): ``q = x Wq`` split per head
  into nope and rope parts; ``[c_kv | k_rope] = x Wkv_a``,
  ``c_kv = RMSNorm(c_kv)``; ``[k_nope | v] = c_kv Wkv_b`` per head; RoPE
  (YaRN frequencies) on ``q_rope`` and the shared ``k_rope``; causal
  softmax per head at ``qk_head_dim**-0.5 * mscale**2``; then ``Wo``.
  RoPE uses the rotate-half layout (HF DeepSeek-V2 de-interleaves its
  rope dims first: a fixed permutation of random weights' columns).
* MoE: ``y = sum_{top-k} g_i E_i(x) + shared(x)``, ``g = softmax`` of the
  router logits over all experts, greedy top-k on the int8 logits (ties
  to the lower index), not renormalised; each expert and the shared
  experts (one SwiGLU of ``n_shared * moe_d_ff``) are SwiGLU.  Only the
  held experts are in the graph; the others' part of ``y`` is absent, as
  on one rank of the deployment.
* The final RMSNorm and the LM head run on the last position only: a
  prefill emits the first token's logits.  RMSNorm weights are ones.

Node ``scope`` attrs name the blocks the executor's ``jax.named_scope``s
carry: ``mla`` (``rope``, ``attn`` inside), ``moe`` (``router``,
``experts``), ``ffn``, ``head``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import List, Optional, Sequence

from ..configs import deepseek_v2_lite_16b as published
from ..core.graph import Graph, Node

#: fractional bits of the int8 activations the ALU rules read and write
Q = 4
#: Softmax probabilities' fractional bits (inputs of the AV product)
P_FRAC = 15


@dataclass(frozen=True)
class Widths:
    """The published widths; tests pass smaller ones."""
    hidden: int = published.CONFIG.d_model
    heads: int = published.CONFIG.n_heads
    qk_nope: int = published.CONFIG.qk_nope_dim
    qk_rope: int = published.CONFIG.qk_rope_dim
    v_head: int = published.CONFIG.v_head_dim
    kv_lora: int = published.CONFIG.kv_lora
    d_ff: int = published.CONFIG.d_ff
    moe_d_ff: int = published.CONFIG.moe_d_ff
    n_experts: int = published.CONFIG.n_experts
    top_k: int = published.CONFIG.top_k
    n_shared: int = published.CONFIG.n_shared_experts
    rope_theta: float = published.CONFIG.rope_theta

    @property
    def softmax_scale(self) -> float:
        s = published.ROPE_SCALING
        m = 0.1 * s["mscale_all_dim"] * math.log(s["factor"]) + 1.0
        return (self.qk_nope + self.qk_rope) ** -0.5 * m * m


class Builder:
    """Appends nodes; every helper returns its output tensor's name."""

    def __init__(self, tokens: int):
        self.tokens = tokens
        self.nodes: List[Node] = []

    def op(self, name, op_type, inputs, scope, n_out=1, **attrs) -> str:
        outs = [f"{name}.out"] if n_out == 1 else \
            [f"{name}.out{i}" for i in range(n_out)]
        self.nodes.append(Node(name, op_type, list(inputs), outs,
                               dict(attrs, scope=scope)))
        return outs[0] if n_out == 1 else outs

    def gemm(self, name, x, cin, cout, scope, gates=None, route=None,
             share=1.0) -> str:
        if gates is None:
            return self.op(name, "Gemm", [x], scope, weight_shape=(cin, cout))
        return self.op(name, "Gemm", [x, gates], scope,
                       weight_shape=(cin, cout), route=route,
                       route_share=share)

    def norm(self, name, x, scope) -> str:
        return self.op(name, "RMSNorm", [x], scope, out_frac=Q)

    def swiglu(self, p, x, cin, width, scope, gates=None, route=None,
               share=1.0) -> str:
        g = self.gemm(f"{p}gate", x, cin, width, scope, gates, route, share)
        u = self.gemm(f"{p}up", x, cin, width, scope, gates, route, share)
        a = self.op(f"{p}act", "Silu", [g], scope, frac=Q, out_frac=Q)
        m = self.op(f"{p}mul", "Mul", [a, u], scope)
        return self.gemm(f"{p}down", m, width, cin, scope, gates, route, share)

    def heads(self, name, x, n_heads, dim, scope) -> str:
        """(T, H*dim) -> (H, T, dim)."""
        r = self.op(f"{name}.split_heads", "Reshape", [x], scope,
                    shape=(self.tokens, n_heads, dim))
        return self.op(f"{name}.to_heads", "Transpose", [r], scope,
                       perm=(1, 0, 2))

    def attend(self, p, q, k, v, n_heads, dv, scale, block) -> str:
        """Causal softmax(q k^T) v per head: (H, T, d) operands ->
        (T, H * dv)."""
        sc = f"{block}/attn"
        s = self.op(f"{p}qk", "MatMul", [q, k], sc, transpose_b=True)
        pr = self.op(f"{p}softmax", "Softmax", [s], sc, causal=True,
                     scale=scale, frac=Q, out_frac=P_FRAC)
        o = self.op(f"{p}av", "MatMul", [pr, v], sc)
        o = self.op(f"{p}from_heads", "Transpose", [o], block, perm=(1, 0, 2))
        return self.op(f"{p}merge_heads", "Reshape", [o], block,
                       shape=(self.tokens, n_heads * dv))

    def rope(self, name, x, dim, theta, block, scaling=None) -> str:
        return self.op(name, "RoPE", [x], f"{block}/rope", dim=dim,
                       theta=theta, scaling=scaling)

    def repeat_heads(self, name, x, n_in, n_heads, dim, scope) -> str:
        """(T, n_in, dim) -> (T, n_heads, dim): head i reads input head
        i % n_in (a key shared by every head, or grouped-query K/V)."""
        return self.op(f"{name}.all_heads", "Concat",
                       [x] * (n_heads // n_in), scope, axis=1)


def mla_layer(b: Builder, p: str, h: str, w: Widths) -> str:
    """One MLA block with its residual; returns the new residual."""
    T, H, dn, dr, dv = b.tokens, w.heads, w.qk_nope, w.qk_rope, w.v_head
    x = b.norm(f"{p}norm", h, "mla")
    q = b.gemm(f"{p}q", x, w.hidden, H * (dn + dr), "mla")
    kva = b.gemm(f"{p}kv_a", x, w.hidden, w.kv_lora + dr, "mla")
    ckv, kr = b.op(f"{p}kv_a_split", "Split", [kva], "mla", n_out=2,
                   axis=-1, parts=[w.kv_lora, dr])
    ckv = b.norm(f"{p}kv_norm", ckv, "mla")
    kvb = b.gemm(f"{p}kv_b", ckv, w.kv_lora, H * (dn + dv), "mla")
    q = b.op(f"{p}q_heads", "Reshape", [q], "mla", shape=(T, H, dn + dr))
    qn, qr = b.op(f"{p}q_split", "Split", [q], "mla", n_out=2, axis=-1,
                  parts=[dn, dr])
    yarn = dict(published.ROPE_SCALING)
    qr = b.rope(f"{p}q_rope", qr, dr, w.rope_theta, "mla", yarn)
    kr = b.rope(f"{p}k_rope", kr, dr, w.rope_theta, "mla", yarn)
    kr = b.op(f"{p}k_rope_1", "Reshape", [kr], "mla", shape=(T, 1, dr))
    kr = b.repeat_heads(f"{p}k_rope", kr, 1, H, dr, "mla")
    kvb = b.op(f"{p}kv_heads", "Reshape", [kvb], "mla", shape=(T, H, dn + dv))
    kn, v = b.op(f"{p}kv_split", "Split", [kvb], "mla", n_out=2, axis=-1,
                 parts=[dn, dv])
    q = b.op(f"{p}q_cat", "Concat", [qn, qr], "mla", axis=-1)
    k = b.op(f"{p}k_cat", "Concat", [kn, kr], "mla", axis=-1)
    q, k, v = (b.op(f"{p}{n}_t", "Transpose", [t], "mla", perm=(1, 0, 2))
               for n, t in (("q", q), ("k", k), ("v", v)))
    o = b.attend(p, q, k, v, H, dv, w.softmax_scale, "mla")
    o = b.gemm(f"{p}o", o, H * dv, w.hidden, "mla")
    return b.op(f"{p}res", "Add", [h, o], "mla")


def moe_layer(b: Builder, p: str, h: str, w: Widths,
              held: Sequence[int]) -> str:
    """One MoE FFN block with its residual: the ``held`` routed experts
    of ``n_experts`` (top-k over all of them) plus the shared experts."""
    x = b.norm(f"{p}norm", h, "moe")
    logits = b.gemm(f"{p}router", x, w.hidden, w.n_experts, "moe/router")
    gates = b.op(f"{p}topk", "TopKRouter", [logits], "moe/router",
                 n_experts=w.n_experts, k=w.top_k, held=list(held), frac=Q)
    share = w.top_k / w.n_experts
    outs = [b.swiglu(f"{p}e{e}.", x, w.hidden, w.moe_d_ff, "moe/experts",
                     gates, j, share) for j, e in enumerate(held)]
    y = b.op(f"{p}combine", "MoECombine", [gates] + outs, "moe")
    if w.n_shared:
        s = b.swiglu(f"{p}shared.", x, w.hidden, w.n_shared * w.moe_d_ff,
                     "moe/experts")
        y = b.op(f"{p}sum", "Add", [y, s], "moe")
    return b.op(f"{p}res", "Add", [h, y], "moe")


def dense_layer(b: Builder, p: str, h: str, w: Widths) -> str:
    x = b.norm(f"{p}norm", h, "ffn")
    y = b.swiglu(p, x, w.hidden, w.d_ff, "ffn")
    return b.op(f"{p}res", "Add", [h, y], "ffn")


def lm_head(b: Builder, h: str, w: Widths, vocab: int) -> str:
    """Final RMSNorm and LM head on the last position: (vocab,) logits."""
    _, last = b.op("head.last", "Split", [h], "head", n_out=2, axis=0,
                   parts=[b.tokens - 1, 1])
    last = b.op("head.flat", "Reshape", [last], "head", shape=(w.hidden,))
    x = b.norm("head.norm", last, "head")
    return b.gemm("head", x, w.hidden, vocab, "head")


def held_experts(n_experts: int, experts_held: int, rank: int) -> range:
    """The routed experts one rank of an expert-parallel deployment holds:
    ``experts_held`` consecutive ones, the ``rank``-th such block."""
    held = range(rank * experts_held, (rank + 1) * experts_held)
    if experts_held < 1 or rank < 0 or held.stop > n_experts:
        raise ValueError(f"rank {rank} cannot hold experts {held.start}.."
                         f"{held.stop - 1} of {n_experts}")
    return held


def deepseek_v2_lite(tokens: int = 1024, dense_layers: int = 1,
                     moe_layers: int = 4, experts_held: int = 8,
                     vocab: int = 12800, widths: Optional[dict] = None,
                     rank: int = 0) -> Graph:
    """The prefill graph of the cut: ``dense_layers`` dense then
    ``moe_layers`` MoE layers, each MLA; input ``input`` is the prompt's
    (tokens, hidden) int8 hidden states; output ``head.out``.  The MoE
    layers hold ``held_experts(n_experts, experts_held, rank)``."""
    w = replace(Widths(), **(widths or {}))
    held = held_experts(w.n_experts, experts_held, rank)
    b = Builder(tokens)
    h = "input"
    for layer in range(dense_layers + moe_layers):
        p = f"L{layer}."
        h = mla_layer(b, f"{p}attn.", h, w)
        h = dense_layer(b, f"{p}ffn.", h, w) if layer < dense_layers \
            else moe_layer(b, f"{p}moe.", h, w, held)
    out = lm_head(b, h, w, vocab)
    return Graph("deepseek_v2_lite", b.nodes, {"input": (tokens, w.hidden)},
                 [out])
