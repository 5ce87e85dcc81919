"""Assigned-LM-architecture blocks as CIM workloads.

Builds the layers of an assigned architecture (``cfg.pre`` then one
``cfg.unit`` period) so the CIM-MLC compiler can schedule them:
weight-stationary projections (Q/K/V/O, MLP, expert FFNs, SSM in/out
projections) map to crossbars; attention QK^T/AV MatMuls, softmax, RoPE,
routing and the SSD scan are ALU (DCOM) operators.  Attention is per
head (grouped-query heads read their K/V head), MLA and routed experts
are ``workloads.deepseek_v2``'s layers, and a MoE layer holds every
expert of its router.
"""
from __future__ import annotations

from ..configs import get_config
from ..core.graph import Graph
from .deepseek_v2 import Builder, Widths, mla_layer, moe_layer


def _attention(b: Builder, p: str, h: str, cfg) -> str:
    d, n_h, n_kv, hd, T = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, \
        cfg.head_dim, b.tokens
    x = b.norm(f"{p}norm", h, "attn")
    qkv = []
    for name, heads in (("q", n_h), ("k", n_kv), ("v", n_kv)):
        t = b.gemm(f"{p}{name}", x, d, heads * hd, "attn")
        t = b.op(f"{p}{name}_heads", "Reshape", [t], "attn",
                 shape=(T, heads, hd))
        if name != "v":
            t = b.rope(f"{p}{name}_rope", t, hd, cfg.rope_theta, "attn")
        if heads != n_h:
            t = b.repeat_heads(f"{p}{name}", t, heads, n_h, hd, "attn")
        qkv.append(b.op(f"{p}{name}_t", "Transpose", [t], "attn",
                        perm=(1, 0, 2)))
    o = b.attend(p, *qkv, n_h, hd, hd ** -0.5, "attn")
    o = b.gemm(f"{p}o", o, n_h * hd, d, "attn")
    return b.op(f"{p}res", "Add", [h, o], "attn")


def _ssm(b: Builder, p: str, h: str, cfg) -> str:
    x = b.norm(f"{p}norm", h, "ssm")
    xs = b.gemm(f"{p}w_x", x, cfg.d_model, cfg.d_inner, "ssm")
    y = b.op(f"{p}ssd", "SSMScan", [xs], "ssm")
    y = b.gemm(f"{p}out_proj", y, cfg.d_inner, cfg.d_model, "ssm")
    return b.op(f"{p}res", "Add", [h, y], "ssm")


def _mlp(b: Builder, p: str, h: str, cfg, gated: bool) -> str:
    x = b.norm(f"{p}norm", h, "ffn")
    if gated:
        y = b.swiglu(p, x, cfg.d_model, cfg.d_ff, "ffn")
    else:
        u = b.gemm(f"{p}up", x, cfg.d_model, cfg.d_ff, "ffn")
        a = b.op(f"{p}act", "Gelu" if cfg.act == "gelu" else "Silu", [u],
                 "ffn", frac=4, out_frac=4)
        y = b.gemm(f"{p}down", a, cfg.d_ff, cfg.d_model, "ffn")
    return b.op(f"{p}res", "Add", [h, y], "ffn")


def lm_block(arch: str, seq: int = 512) -> Graph:
    """The leading layers and one period of ``arch``'s layer pattern on
    a (seq, d_model) int8 input named ``input``."""
    cfg = get_config(arch)
    w = Widths(hidden=cfg.d_model, heads=cfg.n_heads,
               qk_nope=cfg.qk_nope_dim, qk_rope=cfg.qk_rope_dim,
               v_head=cfg.v_head_dim, kv_lora=cfg.kv_lora, d_ff=cfg.d_ff,
               moe_d_ff=cfg.moe_d_ff, n_experts=cfg.n_experts,
               top_k=cfg.top_k, n_shared=cfg.n_shared_experts,
               rope_theta=cfg.rope_theta)
    b = Builder(seq)
    h = "input"
    for i, spec in enumerate(cfg.pre + cfg.unit):
        p = f"L{i}."
        if spec.mixer == "mla":
            h = mla_layer(b, f"{p}attn.", h, w)
        elif spec.mixer in ("attn", "hybrid"):
            h = _attention(b, f"{p}attn.", h, cfg)
        if spec.mixer in ("ssm", "hybrid"):
            h = _ssm(b, f"{p}ssm.", h, cfg)
        if spec.mlp == "moe":
            h = moe_layer(b, f"{p}moe.", h, w, range(cfg.n_experts))
        elif spec.mlp != "none":
            h = _mlp(b, f"{p}mlp.", h, cfg, spec.mlp == "gated")
    return Graph(f"lmblock-{arch}", b.nodes, {"input": (seq, cfg.d_model)},
                 [h])
