"""Plain float32 ``jax.numpy`` forward of the DeepSeek-V2-Lite cut.

The same layers as ``workloads.deepseek_v2`` with nothing rounded:
weights are the program's int8 matrices taken as numbers, each
requantization ``clip(acc >> shift)`` is the division ``acc / 2**shift``
(the calibrated shifts are the model's fixed-point scales), and every ALU
op is its float formula on values read as Q4 (v / 16): RMSNorm with
``eps``, SiLU, the softmax at the published scale, RoPE's rotation with
YaRN frequencies, the router's softmax with greedy top-k.  Matmuls run
under ``jax.default_matmul_precision("highest")``.

What the int8 program leaves out against it is rounding and int8
saturation; ``tests/test_deepseek_v2.py`` bounds the difference.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Dict, Optional

import numpy as np

from ..cimsim.functional import yarn_inv_freq
from ..configs import deepseek_v2_lite_16b as published
from .deepseek_v2 import P_FRAC, Q, Widths, held_experts


def forward(weights: Dict[str, np.ndarray], shifts: Dict[str, int],
            x: np.ndarray, *, tokens: int = 1024, dense_layers: int = 1,
            moe_layers: int = 4, experts_held: int = 8, vocab: int = 12800,
            widths: Optional[dict] = None, rank: int = 0,
            parts: Optional[dict] = None):
    """The (vocab,) logits of one prompt ``x`` (tokens, hidden).  With
    ``parts`` (a dict) it also keeps, keyed by layer, each MoE layer's
    gated sum of its held experts before the combine's scale, and its
    shared experts' output."""
    import jax
    import jax.numpy as jnp
    w = replace(Widths(), **(widths or {}))
    held = list(held_experts(w.n_experts, experts_held, rank))
    T, H, dn, dr, dv = tokens, w.heads, w.qk_nope, w.qk_rope, w.v_head
    one = float(1 << Q)

    def lin(name, a, route_mask=None):
        y = a @ jnp.asarray(weights[name], jnp.float32) / 2.0 ** shifts[name]
        return y if route_mask is None else y * route_mask[:, None]

    def scaled(name, y):
        return y / 2.0 ** shifts[name]

    def norm(a):
        r = a / one
        return one * r / jnp.sqrt((r * r).mean(-1, keepdims=True)
                                  + published.RMS_NORM_EPS)

    def silu(a):
        return one * jax.nn.silu(a / one)

    inv = jnp.asarray(yarn_inv_freq(dr, w.rope_theta, published.ROPE_SCALING),
                      jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def rope(a):
        shape = (T,) + (1,) * (a.ndim - 2) + (dr // 2,)
        c, s = cos.reshape(shape), sin.reshape(shape)
        a1, a2 = a[..., :dr // 2], a[..., dr // 2:]
        return jnp.concatenate([a1 * c - a2 * s, a2 * c + a1 * s], -1)

    def swiglu(p, a, mask=None):
        g = lin(f"{p}gate", a, mask)
        u = lin(f"{p}up", a, mask)
        return lin(f"{p}down", scaled(f"{p}mul", silu(g) * u), mask)

    def mla(p, h):
        a = norm(h)
        q = lin(f"{p}q", a).reshape(T, H, dn + dr)
        kva = lin(f"{p}kv_a", a)
        kvb = lin(f"{p}kv_b", norm(kva[:, :w.kv_lora])).reshape(T, H, dn + dv)
        kr = jnp.broadcast_to(rope(kva[:, w.kv_lora:])[:, None], (T, H, dr))
        qf = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
        kf = jnp.concatenate([kvb[..., :dn], kr], -1)
        s = scaled(f"{p}qk", jnp.einsum("thd,shd->hts", qf, kf))
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)),
                      s / one * w.softmax_scale, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1) * (1 << P_FRAC)
        o = scaled(f"{p}av", jnp.einsum("hts,shd->thd", pr, kvb[..., dn:]))
        return scaled(f"{p}res", h + lin(f"{p}o", o.reshape(T, H * dv)))

    def moe(p, h, layer):
        a = norm(h)
        logits = lin(f"{p}router", a)
        top = jax.lax.top_k(logits, w.top_k)[1]
        chosen = jax.nn.one_hot(top, w.n_experts).sum(1) > 0
        g = jax.nn.softmax(logits / one, axis=-1) * (1 << 15)
        g = jnp.where(chosen, g, 0.0)[:, np.asarray(held)]
        acc = sum(g[:, j:j + 1] * swiglu(f"{p}e{e}.", a, g[:, j] > 0)
                  for j, e in enumerate(held))
        y = scaled(f"{p}combine", acc)
        shared = None
        if w.n_shared:
            shared = swiglu(f"{p}shared.", a)
            y = scaled(f"{p}sum", y + shared)
        if parts is not None:
            parts[layer] = (acc, shared)
        return scaled(f"{p}res", h + y)

    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(x, jnp.float32)
        for layer in range(dense_layers + moe_layers):
            p = f"L{layer}."
            h = mla(f"{p}attn.", h)
            if layer < dense_layers:
                h = scaled(f"{p}ffn.res", h + swiglu(f"{p}ffn.", norm(h)))
            else:
                h = moe(f"{p}moe.", h, layer)
        return lin("head", norm(h[-1]))
