"""DeepSeek-V2-Lite prefill (arXiv:2405.04434), one rank's share, as the
benchmark's own int8 reference: MLA attention per head with YaRN RoPE and
a causal softmax, top-k-of-n routed experts (of which this rank holds
``experts_held``) plus shared experts, and the LM head on the last
position.  The published widths are the defaults; the CPU tests pass
smaller ones.

Every op is this module's own NumPy code of the integer rules the CIM
program states for its ALU (int8 values read as Q4, i.e. v / 16):

* ``gemm``: token rows times the node's crossbar weights, requantized by
  the node's shift; a routed expert's ``gemm`` (``route`` = j, gates as
  second input) multiplies only the rows whose gate j is > 0, is
  calibrated on them alone and leaves its other rows 0;
* ``rmsnorm``: ``m = round(16 sum x**2 / D)`` (as ``16 (ss // D) +
  (32 (ss % D) + D) // (2 D)``), ``s = floor(sqrt(m * 1024))``,
  ``y = (x * 2**12 + s) // (2 s)``, clipped to int8;
* ``silu``: the table ``round(16 silu(v / 16))``;
* ``softmax``: ``d = max - v``, ``E[d] = (A[d >> 4] B[d & 15] + 2**14)
  >> 15`` with ``A[i] = round(2**15 exp(-16 i c))``, ``B[j] =
  round(2**15 exp(-j c))``, ``c = scale / 16``; ``p = (E << f + S // 2)
  // S`` with f the output's fractional bits; ``causal`` leaves keys
  after the query out;
* ``rope``: cos and sin as ``round(2**14 ...)`` of YaRN frequencies at
  each position; ``(x1 c - x2 s + 2**13) >> 14`` and
  ``(x2 c + x1 s + 2**13) >> 14``;
* ``topk``: the k largest int8 logits (ties to the lower index); gates
  the softmax above (f = 15) over all experts, at least 1 where chosen,
  0 elsewhere; the held experts' columns;
* ``combine``: ``sum_j gate_j y_j`` requantized; ``matmul`` and ``mul``
  exact products requantized.

A routed expert's ``macs`` are its expected share (tokens x k / n) of its
rows; an attention product's are the causal half of the full one.
"""
import math

import numpy as np

from reference import NetBuilder

Q = 4
ROPE_ONE = 1 << 14
YARN = {"factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
PUBLISHED = dict(hidden=2048, heads=16, qk_nope=128, qk_rope=64, v_head=128,
                 kv_lora=512, d_ff=10944, moe_d_ff=1408, n_experts=64,
                 top_k=6, n_shared=2, rope_theta=10000.0)


# -- ops --------------------------------------------------------------------------

def gemm(node, xs, ctx):
    x = xs[0]
    w = ctx.operand(ctx.weights[node["name"]])
    if "route" not in node:
        rows = x.reshape(-1, node["R"])
        acc = ctx.cim_mvm(ctx.operand(rows), w, ctx.cim)
        return ctx.shifted(node["name"], acc).reshape(*x.shape[:-1], node["C"])
    mask = xs[1][:, node["route"]] > 0
    acc = ctx.cim_mvm(ctx.operand(x[mask]), w, ctx.cim)
    out = np.zeros((x.shape[0], node["C"]), np.int64)
    out[mask] = ctx.shifted(node["name"], acc)
    return out


def _isqrt(n):
    r = np.floor(np.sqrt(n.astype(np.float64))).astype(np.int64)
    r = np.where(r * r > n, r - 1, r)
    return np.where((r + 1) * (r + 1) <= n, r + 1, r)


def rmsnorm(node, xs, ctx):
    x = xs[0].astype(np.int64)
    d = x.shape[-1]
    ss = (x * x).sum(axis=-1, keepdims=True)
    m = 16 * (ss // d) + (32 * (ss % d) + d) // (2 * d)
    s = _isqrt(m * 1024)
    y = (x * (1 << (Q + 8)) + s) // np.maximum(2 * s, 1)
    return np.clip(np.where(s > 0, y, 0), -128, 127)


def silu(node, xs, ctx):
    v = np.arange(-128, 128, dtype=np.float64) / (1 << Q)
    table = np.clip(np.round((v / (1.0 + np.exp(-v))) * (1 << Q)), -128, 127)
    return table.astype(np.int64)[xs[0] + 128]


def mul(node, xs, ctx):
    return ctx.shifted(node["name"], xs[0] * xs[1])


def _softmax(v, scale, out_frac, causal):
    c = scale / (1 << Q)
    i = np.arange(16, dtype=np.float64)
    hi = np.round((1 << 15) * np.exp(-16 * i * c)).astype(np.int64)
    lo = np.round((1 << 15) * np.exp(-i * c)).astype(np.int64)
    table = ((hi[:, None] * lo[None, :] + (1 << 14)) >> 15).reshape(256)
    if causal:
        t = v.shape[-1]
        keep = np.tril(np.ones((t, t), bool))
    else:
        keep = np.ones(v.shape[-1], bool)
    m = np.where(keep, v, -(1 << 20)).max(axis=-1, keepdims=True)
    e = np.where(keep, table[np.clip(m - v, 0, 255)], 0)
    s = e.sum(axis=-1, keepdims=True)
    return ((e << out_frac) + s // 2) // s


def softmax(node, xs, ctx):
    return _softmax(xs[0], node["scale"], node["out_frac"], True)


def matmul(node, xs, ctx):
    a, b = xs
    if node.get("transpose_b"):
        b = np.swapaxes(b, -1, -2)
    acc = np.matmul(a.astype(np.float64), b.astype(np.float64)).astype(np.int64)
    return ctx.shifted(node["name"], acc)


def _inv_freq(dim, theta):
    i = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** i
    orig = YARN["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))
    lo = max(math.floor(corr(YARN["beta_fast"])), 0)
    hi = min(math.ceil(corr(YARN["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    keep = 1.0 - np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return extra / YARN["factor"] * (1.0 - keep) + extra * keep


def rope(node, xs, ctx):
    x = xs[0]
    half = node["dim"] // 2
    ang = np.arange(x.shape[0], dtype=np.float64)[:, None] \
        * _inv_freq(node["dim"], node["theta"])[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c = np.round(ROPE_ONE * np.cos(ang)).astype(np.int64).reshape(shape)
    s = np.round(ROPE_ONE * np.sin(ang)).astype(np.int64).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    y = np.concatenate([(x1 * c - x2 * s + ROPE_ONE // 2) >> 14,
                        (x2 * c + x1 * s + ROPE_ONE // 2) >> 14], axis=-1)
    return np.clip(y, -128, 127)


def topk(node, xs, ctx):
    v = xs[0]
    n = v.shape[-1]
    order = np.argsort(-v, axis=-1, kind="stable")[:, :node["k"]]
    chosen = np.zeros(v.shape, bool)
    np.put_along_axis(chosen, order, True, axis=-1)
    p = _softmax(v, 1.0, 15, False)
    g = np.where(chosen, np.maximum(p, 1), 0)
    return g[:, :node["held"]]


def combine(node, xs, ctx):
    g = xs[0]
    acc = sum(g[:, j:j + 1] * y for j, y in enumerate(xs[1:]))
    return ctx.shifted(node["name"], acc)


def reshape(node, xs, ctx):
    return xs[0].reshape(node["shape"])


def transpose(node, xs, ctx):
    return xs[0].transpose(1, 0, 2)


def take(node, xs, ctx):
    """``xs[0][..., lo:hi]`` along ``axis``."""
    sl = [slice(None)] * xs[0].ndim
    sl[node["axis"]] = slice(node["lo"], node["hi"])
    return xs[0][tuple(sl)]


def concat(node, xs, ctx):
    return np.concatenate(xs, axis=node["axis"])


OPS = {"gemm": gemm, "rmsnorm": rmsnorm, "silu": silu, "mul": mul,
       "softmax": softmax, "matmul": matmul, "rope": rope, "topk": topk,
       "combine": combine, "reshape": reshape, "transpose": transpose,
       "take": take, "concat": concat}


# -- the network --------------------------------------------------------------------

def build(tokens=1024, dense_layers=1, moe_layers=4, experts_held=8,
          vocab=12800, widths=None):
    w = dict(PUBLISHED, **(widths or {}))
    D, H, dn, dr, dv = (w[k] for k in ("hidden", "heads", "qk_nope",
                                       "qk_rope", "v_head"))
    T = tokens
    b = NetBuilder("deepseek_v2_lite", (T, D))
    sh = b.shapes

    def node(name, op, inputs, out_shape, **kw):
        return b.node(name, op, inputs, out_shape, **kw)

    def fc(name, x, cin, cout, gates=None, route=None, expert=False):
        rows = int(np.prod(sh[x][:-1]))
        kw = dict(R=cin, C=cout, windows=rows)
        if expert:
            kw["expert"] = True
        if gates is None:
            return node(name, "gemm", [x], (*sh[x][:-1], cout), **kw)
        share = rows * w["top_k"] / w["n_experts"]
        return node(name, "gemm", [x, gates], (rows, cout), route=route,
                    macs=int(round(share * cin * cout)), **kw)

    def norm(name, x):
        return node(name, "rmsnorm", [x], sh[x])

    def add(name, a, c):
        return b.add(name, a, c)

    def take(name, x, axis, lo, hi):
        shape = list(sh[x])
        shape[axis] = hi - lo
        return node(name, "take", [x], tuple(shape), axis=axis, lo=lo, hi=hi)

    def swiglu(p, x, cin, width, gates=None, route=None, expert=False):
        g = fc(f"{p}gate", x, cin, width, gates, route, expert)
        u = fc(f"{p}up", x, cin, width, gates, route, expert)
        a = node(f"{p}act", "silu", [g], sh[g])
        m = node(f"{p}mul", "mul", [a, u], sh[a])
        return fc(f"{p}down", m, width, cin, gates, route, expert)

    def t3(name, x):
        s0, s1, s2 = sh[x]
        return node(name, "transpose", [x], (s1, s0, s2))

    def mla(p, h):
        x = norm(f"{p}norm", h)
        q = fc(f"{p}q", x, D, H * (dn + dr))
        kva = fc(f"{p}kv_a", x, D, w["kv_lora"] + dr)
        ckv = norm(f"{p}kv_norm", take(f"{p}ckv", kva, 1, 0, w["kv_lora"]))
        kr = take(f"{p}kr", kva, 1, w["kv_lora"], w["kv_lora"] + dr)
        kvb = fc(f"{p}kv_b", ckv, w["kv_lora"], H * (dn + dv))
        q = node(f"{p}q_heads", "reshape", [q], (T, H, dn + dr),
                 shape=(T, H, dn + dr))
        qn = take(f"{p}qn", q, 2, 0, dn)
        qr = node(f"{p}q_rope", "rope", [take(f"{p}qr", q, 2, dn, dn + dr)],
                  (T, H, dr), dim=dr, theta=w["rope_theta"])
        kr = node(f"{p}k_rope", "rope", [kr], (T, dr), dim=dr,
                  theta=w["rope_theta"])
        kr = node(f"{p}k_rope_1", "reshape", [kr], (T, 1, dr), shape=(T, 1, dr))
        kr = node(f"{p}k_rope_h", "concat", [kr] * H, (T, H, dr), axis=1)
        kvb = node(f"{p}kv_heads", "reshape", [kvb], (T, H, dn + dv),
                   shape=(T, H, dn + dv))
        kn = take(f"{p}kn", kvb, 2, 0, dn)
        v = t3(f"{p}v_t", take(f"{p}v", kvb, 2, dn, dn + dv))
        q = t3(f"{p}q_t", node(f"{p}q_cat", "concat", [qn, qr],
                               (T, H, dn + dr), axis=2))
        k = t3(f"{p}k_t", node(f"{p}k_cat", "concat", [kn, kr],
                               (T, H, dn + dr), axis=2))
        causal_pairs = T * (T + 1) // 2
        s = node(f"{p}qk", "matmul", [q, k], (H, T, T), transpose_b=True,
                 macs=H * causal_pairs * (dn + dr))
        m = 0.1 * YARN["mscale_all_dim"] * math.log(YARN["factor"]) + 1.0
        pr = node(f"{p}softmax", "softmax", [s], (H, T, T),
                  scale=(dn + dr) ** -0.5 * m * m, out_frac=15)
        o = node(f"{p}av", "matmul", [pr, v], (H, T, dv),
                 macs=H * causal_pairs * dv)
        o = node(f"{p}merge_heads", "reshape", [t3(f"{p}from_heads", o)],
                 (T, H * dv), shape=(T, H * dv))
        return add(f"{p}res", h, fc(f"{p}o", o, H * dv, D))

    def moe(p, h):
        x = norm(f"{p}norm", h)
        logits = fc(f"{p}router", x, D, w["n_experts"])
        gates = node(f"{p}topk", "topk", [logits], (T, experts_held),
                     k=w["top_k"], held=experts_held)
        outs = [swiglu(f"{p}e{j}.", x, D, w["moe_d_ff"], gates, j, True)
                for j in range(experts_held)]
        y = node(f"{p}combine", "combine", [gates] + outs, (T, D))
        if w["n_shared"]:
            s = swiglu(f"{p}shared.", x, D, w["n_shared"] * w["moe_d_ff"],
                       expert=True)
            y = add(f"{p}sum", y, s)
        return add(f"{p}res", h, y)

    h = "input"
    for layer in range(dense_layers + moe_layers):
        p = f"L{layer}."
        h = mla(f"{p}attn.", h)
        if layer < dense_layers:
            x = norm(f"{p}ffn.norm", h)
            h = add(f"{p}ffn.res", h, swiglu(f"{p}ffn.", x, D, w["d_ff"]))
        else:
            h = moe(f"{p}moe.", h)
    last = node("head.flat", "reshape", [take("head.last", h, 0, T - 1, T)],
                (D,), shape=(D,))
    out = fc("head", norm("head.norm", last), D, vocab)
    return b.net(out, ops=OPS)
