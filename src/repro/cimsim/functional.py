"""Functional simulator (§4.1): interprets the meta-operator flow.

The paper verifies its compiler by executing the generated meta-operator
flows in a functional simulator and comparing against a reference
framework (they use PyTorch; offline we use a pure-NumPy/JAX int8
fake-quant reference, ``reference_forward``).

The simulator walks the *expanded* Program op by op:

  * ``cim.write_xb`` / ``cim.write_row`` load quantized weight tiles into
    a crossbar store;
  * ``cim.read_xb`` / ``cim.read_row`` perform one analog activation —
    the bit-sliced, parallel-row-grouped, ADC-saturating MVM of
    kernels/cim_mvm (ref semantics; the Pallas kernel computes the same
    function and is swept against it in tests) — and accumulate partial
    sums;
  * ``cim.read_core`` executes a whole operator on a core (CM chips);
  * DCOM ops apply the digital operators; ``mov`` is bookkeeping.

Equality with the reference is bit-exact whenever the ADC does not
saturate (``CimMvmParams.exact``); with a narrow ADC the simulator
reports the (hardware-true) saturated results.

The ALU contract.  Every digital operator is integer arithmetic on int8
activations, so the executor computes it on the device bit for bit (no
host callback).  A node's ``frac`` attr gives the fractional bits of its
int8 input (real value = v / 2**frac) and ``out_frac`` those of its
output; the defaults (0 in, 5 out) are the historical contract.

* Elementwise Gelu / Silu / Sigmoid / Tanh: a 256-entry table built at
  lowering, ``T[v] = clip(round(2**out_frac * f(v / 2**frac)), -128,
  127)`` in float64, so both simulators index the same integers.  Error
  against ``f``: at most half a step of ``2**-out_frac`` (before clip).
* Softmax over the last axis (``causal``: key j > query i is left out;
  ``scale`` multiplies the real logit): ``d = max - v`` in [0, 255],
  ``e = E[d] = (A[d >> 4] B[d & 15] + 2**14) >> 15`` with the 16-entry
  ``A[i] = round(2**15 exp(-16 i c))``, ``B[j] = round(2**15 exp(-j c))``,
  ``c = scale / 2**frac`` (two small tables, so the device selects
  rather than gathers), ``S = sum e``, ``p = (e * 2**out_frac + S // 2)
  // S``.  int32 bound: ``e * 2**out_frac <= 2**30`` for ``out_frac <=
  15``; rows up to 2**16.  Error against the float64 softmax: half a
  step of ``2**-out_frac`` plus ``e``'s 1.5 steps of 2**-15 relative,
  under 2**-14 per entry at ``out_frac`` 15.
* RMSNorm / LayerNorm over the last axis of length D (LayerNorm first
  subtracts ``mu = (2 sum x + D) // (2 D)``, the rounded mean; weights
  are ones): ``ss = sum x**2``; the mean square in sixteenths,
  ``m = 16 (ss // D) + (32 (ss % D) + D) // (2 D)``, is
  ``round(16 ss / D)``; ``s = isqrt(m * 2**10)`` = floor(128 rms);
  ``y = clip((x * 2**(out_frac + 8) + s) // (2 s), -128, 127)`` (0 where
  s is 0).  Every step fits int32 for D <= 2**15.  ``eps`` (1e-6 of the
  real mean square) lies below the rule's resolution and is dropped.
  Error against ``x / rms``: the rms is read to 1/(128 rms) relative.
* RoPE on the last axis, rotate-half layout, at the position along the
  first (token) axis: ``c = round(2**14 cos(t theta_i))``, ``s`` likewise
  (tables built at lowering from float64, YaRN frequencies where
  ``scaling`` says so); ``y1 = (x1 c - x2 s + 2**13) >> 14``,
  ``y2 = (x2 c + x1 s + 2**13) >> 14``, clipped to int8.  Error: half
  a step plus ``|x| 2**-14``.
* TopKRouter over ``n_experts`` int8 logits (``frac`` as above): the
  ``k`` largest logits are chosen, ties to the lower expert index;
  gates are the Softmax rule above at ``out_frac`` 15 over all experts;
  a chosen expert's gate is at least 1 (so chosen means gate > 0),
  every other gate is 0.  Output: the gates of the ``held`` experts.
* MatMul (activation x activation, batched over leading head axes):
  the exact integer product, requantized by the node's shift; its left
  operand may be Softmax probabilities (to 2**15: a row sums to at most
  ``2**out_frac + K/2``), its right one is int8.
* MoECombine: ``sum_j gate_j * y_j`` over the held experts (int32: at
  most k * 2**15 * 128), requantized by the node's calibrated shift.
* A routed Gemm (attr ``route`` = j, second input the gates) multiplies
  only the rows whose gate j is > 0; its other rows are 0, and its
  shift is calibrated on the routed rows alone.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core.abstraction import CIMArch
from ..core.cg_opt import OpPlacement, SchedulePlan
from ..core.graph import Graph, Node, weight_matrix_shape
from ..core.mapping import logical_cols_per_xb
from ..core.mop import MetaOp, Program
from ..kernels.cim_mvm import cim_mvm_params, CimMvmParams
from ..kernels.cim_mvm import ref as kref


# ---------------------------------------------------------------------------
# Quantization helpers (shared verbatim by simulator and reference)
# ---------------------------------------------------------------------------

def requant(y32: np.ndarray, shift: int) -> np.ndarray:
    """int32 accumulator -> int8 tensor via arithmetic right-shift."""
    return np.clip(y32 >> shift, -128, 127).astype(np.int32)


def pick_shift(y32: np.ndarray) -> int:
    m = int(np.abs(y32).max()) if y32.size else 0
    if m <= 127:
        return 0
    return max(0, int(math.ceil(math.log2((m + 1) / 127.0))))


def make_weights(graph: Graph, seed: int = 0,
                 bits: int = 8) -> Dict[str, np.ndarray]:
    """Deterministic signed int weights (R, C) per CIM node.

    Seeded with a stable digest of ``(node name, seed)`` — ``hash()`` of
    a str is salted per process, which would silently break cross-process
    reproducibility and any cache keyed on weight content.
    """
    out = {}
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1))
    for node in graph.cim_nodes:
        r, c = weight_matrix_shape(node)
        rng = np.random.default_rng(
            zlib.crc32(f"{node.name}\x00{seed}".encode()))
        out[node.name] = rng.integers(lo, hi, (r, c)).astype(np.int32)
    return out


def make_input(graph: Graph, seed: int = 0, bits: int = 8) -> Dict[str, np.ndarray]:
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1))
    rng = np.random.default_rng(seed)
    return {name: rng.integers(lo, hi, shape).astype(np.int32)
            for name, shape in graph.inputs.items()}


def im2col(x: np.ndarray, k: int, stride: int, pad: int) -> np.ndarray:
    """(C,H,W) -> (H_out*W_out, C*k*k) patch matrix (weight-matrix order)."""
    c, h, w = x.shape
    xp = np.pad(x, ((0, 0), (pad, pad), (pad, pad)))
    oh = (h + 2 * pad - k) // stride + 1
    ow = (w + 2 * pad - k) // stride + 1
    rows = np.empty((oh * ow, c * k * k), dtype=x.dtype)
    idx = 0
    for i in range(oh):
        for j in range(ow):
            patch = xp[:, i * stride:i * stride + k, j * stride:j * stride + k]
            rows[idx] = patch.reshape(-1)
            idx += 1
    return rows


# ---------------------------------------------------------------------------
# The integer ALU contract (module docstring); the executor builds the same
# tables and computes the same integer steps on the device
# ---------------------------------------------------------------------------

#: elementwise float functions tabulated over the 256 int8 inputs
ELEMENTWISE = {
    "Gelu": lambda x: x * 0.5 * (1.0 + np.tanh(
        0.7978845608 * (x + 0.044715 * x ** 3))),
    "Silu": lambda x: x / (1.0 + np.exp(-x)),
    "Sigmoid": lambda x: 1.0 / (1.0 + np.exp(-x)),
    "Tanh": np.tanh,
}
#: fractional bits of a gate (TopKRouter output), of the RoPE tables and
#: of the Softmax rule's exponentials
GATE_FRAC = 15
ROPE_FRAC = 14
EXP_FRAC = 15


def alu_fracs(node: Node) -> Tuple[int, int]:
    """(input, output) fractional bits of an ALU node's int8 values."""
    return int(node.attrs.get("frac", 0)), int(node.attrs.get("out_frac", 5))


def elementwise_table(node: Node) -> np.ndarray:
    """(256,) int32 outputs of an elementwise op, indexed by v + 128."""
    fin, fout = alu_fracs(node)
    v = np.arange(-128, 128, dtype=np.float64) / (1 << fin)
    y = ELEMENTWISE[node.op_type](v) * (1 << fout)
    return np.clip(np.round(y), -128, 127).astype(np.int32)


def exp_factors(scale: float, frac: int) -> Tuple[np.ndarray, np.ndarray]:
    """(16,) int32 ``A[i] = round(2**15 exp(-16 i c))`` and
    ``B[j] = round(2**15 exp(-j c))``, ``c = scale / 2**frac``."""
    c = scale / (1 << frac)
    i = np.arange(16, dtype=np.float64)
    one = 1 << EXP_FRAC
    return (np.round(one * np.exp(-16 * i * c)).astype(np.int32),
            np.round(one * np.exp(-i * c)).astype(np.int32))


def exp_table(scale: float, frac: int) -> np.ndarray:
    """(256,) int32 ``E[d] = (A[d >> 4] B[d & 15] + 2**14) >> 15``, the
    Softmax rule's ``exp(-d c)`` in Q15 (``exp_factors``)."""
    a, b = (f.astype(np.int64) for f in exp_factors(scale, frac))
    prod = a[:, None] * b[None, :] + (1 << (EXP_FRAC - 1))
    return (prod >> EXP_FRAC).reshape(256).astype(np.int32)


def softmax_exp_table(node: Node) -> np.ndarray:
    """A Softmax or TopKRouter node's ``E`` (the router's scale is 1)."""
    return exp_table(float(node.attrs.get("scale", 1.0)), alu_fracs(node)[0])


def causal_mask(rows: int, cols: int) -> np.ndarray:
    """(rows, cols) True where key j <= query i."""
    return np.arange(cols)[None, :] <= np.arange(rows)[:, None]


def softmax_int(v: np.ndarray, table: np.ndarray, out_frac: int,
                causal: bool = False) -> np.ndarray:
    """The Softmax rule over the last axis of int8 ``v``."""
    v = v.astype(np.int64)
    keep = causal_mask(*v.shape[-2:]) if causal else np.ones(v.shape[-1:],
                                                             bool)
    m = np.where(keep, v, -(1 << 20)).max(axis=-1, keepdims=True)
    e = np.where(keep, table[np.clip(m - v, 0, 255)], 0).astype(np.int64)
    s = e.sum(axis=-1, keepdims=True)
    return ((e << out_frac) + s // 2) // s


def isqrt(n: np.ndarray) -> np.ndarray:
    """floor(sqrt(n)) of non-negative integers below 2**52, exactly."""
    n = np.asarray(n, np.int64)
    r = np.floor(np.sqrt(n.astype(np.float64))).astype(np.int64)
    r = np.where(r * r > n, r - 1, r)
    return np.where((r + 1) * (r + 1) <= n, r + 1, r)


def norm_int(x: np.ndarray, out_frac: int, center: bool) -> np.ndarray:
    """The RMSNorm (``center``: LayerNorm) rule over the last axis."""
    x = x.astype(np.int64)
    d = x.shape[-1]
    if center:
        x = x - (2 * x.sum(axis=-1, keepdims=True) + d) // (2 * d)
    ss = (x * x).sum(axis=-1, keepdims=True)
    m = 16 * (ss // d) + (32 * (ss % d) + d) // (2 * d)
    s = isqrt(m << 10)
    y = ((x << (out_frac + 8)) + s) // np.maximum(2 * s, 1)
    return np.clip(np.where(s > 0, y, 0), -128, 127)


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]
                  ) -> np.ndarray:
    """Rotary inverse frequencies, blended as YaRN does where ``scaling``
    (a ``rope_scaling`` dict of type yarn) is given (DeepSeek-V2)."""
    i = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** i
    if not scaling:
        return extra
    factor = scaling["factor"]
    orig = scaling["original_max_position_embeddings"]

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(corr(scaling["beta_fast"])), 0)
    hi = min(math.ceil(corr(scaling["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / factor * (1.0 - keep) + extra * keep


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(node: Node, positions: int) -> Tuple[np.ndarray, np.ndarray]:
    """(positions, dim/2) int32 fixed-point cos and sin of a RoPE node."""
    a = node.attrs
    scaling = a.get("scaling")
    inv = yarn_inv_freq(a["dim"], a.get("theta", 10000.0), scaling)
    mag = 1.0
    if scaling:
        mag = (yarn_mscale(scaling["factor"], scaling["mscale"])
               / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]))
    ang = np.arange(positions, dtype=np.float64)[:, None] * inv[None, :]
    one = 1 << ROPE_FRAC
    return (np.round(one * mag * np.cos(ang)).astype(np.int32),
            np.round(one * mag * np.sin(ang)).astype(np.int32))


def rope_int(x: np.ndarray, cos: np.ndarray, sin: np.ndarray) -> np.ndarray:
    """The RoPE rule: ``x`` is (T, ..., dim), tables (T, dim/2)."""
    x = x.astype(np.int64)
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    rnd = 1 << (ROPE_FRAC - 1)
    y = np.concatenate([(x1 * c - x2 * s + rnd) >> ROPE_FRAC,
                        (x2 * c + x1 * s + rnd) >> ROPE_FRAC], axis=-1)
    return np.clip(y, -128, 127)


def topk_select(v: np.ndarray, k: int) -> np.ndarray:
    """Boolean mask of the ``k`` largest entries of each row of int8
    ``v``, ties to the lower index (a unique key per entry)."""
    e = v.shape[-1]
    key = v.astype(np.int64) * e + (e - 1 - np.arange(e))
    kth = np.sort(key, axis=-1)[..., e - k:e - k + 1]
    return key >= kth


def router_int(v: np.ndarray, node: Node) -> np.ndarray:
    """The TopKRouter rule: the held experts' gates, (..., held)."""
    a = node.attrs
    sel = topk_select(v, a["k"])
    p = softmax_int(v, softmax_exp_table(node), GATE_FRAC)
    g = np.where(sel, np.maximum(p, 1), 0)
    return g[..., list(a.get("held", range(a["n_experts"])))]


def routed_rows(node: Node, xs: List[np.ndarray]) -> Optional[np.ndarray]:
    """Row mask of a routed crossbar node (its gate > 0), else None."""
    if "route" not in node.attrs:
        return None
    return xs[1][..., node.attrs["route"]] > 0


# ---------------------------------------------------------------------------
# Reference executor (int8 fake-quant, exact integer matmuls)
# ---------------------------------------------------------------------------

def apply_dcom(node: Node, xs: List[np.ndarray], graph: Graph,
               shifts: Dict[str, int],
               calibrating: bool) -> np.ndarray:
    """Digital operator semantics shared by simulator and reference."""
    t = node.op_type
    if t == "Relu":
        return np.maximum(xs[0], 0)
    if t == "Add":
        y = xs[0].astype(np.int64) + xs[1].astype(np.int64)
        sh = _shift_for(node, y, shifts, calibrating)
        return requant(y.astype(np.int64) >> 0, 0) if sh == 0 \
            else np.clip(y >> sh, -128, 127).astype(np.int32)
    if t == "Mul":
        y = xs[0].astype(np.int64) * xs[1].astype(np.int64)
        sh = _shift_for(node, y, shifts, calibrating)
        return np.clip(y >> sh, -128, 127).astype(np.int32)
    if t == "MaxPool":
        return _pool(xs[0], node, np.max)
    if t in ("AveragePool", "GlobalAveragePool"):
        if t == "GlobalAveragePool":
            return (xs[0].sum(axis=(1, 2), keepdims=True)
                    // (xs[0].shape[1] * xs[0].shape[2])).astype(np.int32)
        return _pool(xs[0], node, lambda a, axis: a.sum(axis=axis)
                     // (node.attrs.get("kernel", 2) ** 2))
    if t == "Flatten":
        return xs[0].reshape(-1)
    if t == "Reshape":
        return xs[0].reshape(node.attrs["shape"])
    if t == "Identity":
        return xs[0]
    if t == "Transpose":
        return xs[0].transpose(node.attrs["perm"])
    if t == "Concat":
        return np.concatenate(xs, axis=node.attrs.get("axis", -1))
    if t == "Split":
        axis = node.attrs.get("axis", -1) % xs[0].ndim
        parts = node.attrs["parts"]
        return np.split(xs[0], np.cumsum(parts[:-1]), axis=axis)
    if t == "MatMul":
        b = np.swapaxes(xs[1], -1, -2) if node.attrs.get("transpose_b") \
            else xs[1]
        y = np.matmul(xs[0].astype(np.float64), b.astype(np.float64)
                      ).astype(np.int64)
        sh = _shift_for(node, y, shifts, calibrating)
        return np.clip(y >> sh, -128, 127).astype(np.int32)
    if t in ELEMENTWISE:
        return elementwise_table(node)[xs[0].astype(np.int64) + 128]
    if t == "Softmax":
        return softmax_int(xs[0], softmax_exp_table(node), alu_fracs(node)[1],
                           bool(node.attrs.get("causal"))).astype(np.int32)
    if t in ("RMSNorm", "LayerNorm"):
        return norm_int(xs[0], alu_fracs(node)[1],
                        t == "LayerNorm").astype(np.int32)
    if t == "RoPE":
        return rope_int(xs[0], *rope_tables(node, xs[0].shape[0])
                        ).astype(np.int32)
    if t == "TopKRouter":
        return router_int(xs[0], node).astype(np.int32)
    if t == "MoECombine":
        g = xs[0].astype(np.int64)
        y = sum(g[..., j:j + 1] * x.astype(np.int64)
                for j, x in enumerate(xs[1:]))
        sh = _shift_for(node, y, shifts, calibrating)
        return np.clip(y >> sh, -128, 127).astype(np.int32)
    raise ValueError(f"no DCOM semantics for {t}")


def _shift_for(node: Node, y, shifts: Dict[str, int],
               calibrating: bool) -> int:
    if calibrating:
        shifts[node.name] = pick_shift(np.asarray(y))
    return shifts.get(node.name, 0)


def _pool(x: np.ndarray, node: Node, reducer) -> np.ndarray:
    k = node.attrs.get("kernel", 2)
    stride = node.attrs.get("stride", k)
    pad = node.attrs.get("pad", 0)
    c, h, w = x.shape
    if pad:
        fill = -(2 ** 31) if reducer is np.max else 0
        x = np.pad(x, ((0, 0), (pad, pad), (pad, pad)),
                   constant_values=fill)
    oh = (h + 2 * pad - k) // stride + 1
    ow = (w + 2 * pad - k) // stride + 1
    out = np.empty((c, oh, ow), dtype=np.int32)
    for i in range(oh):
        for j in range(ow):
            win = x[:, i * stride:i * stride + k, j * stride:j * stride + k]
            out[:, i, j] = reducer(win.reshape(c, -1), axis=-1)
    return out


def exact_mvm(x_rows: np.ndarray, w: np.ndarray) -> np.ndarray:
    """(M, R) x (R, C) integer product through float64 BLAS: exact, as
    every product and partial sum of int8 operands is far below 2**53."""
    return (x_rows.astype(np.float64) @ w.astype(np.float64)).astype(np.int64)


def reference_forward(graph: Graph, weights: Dict[str, np.ndarray],
                      inputs: Dict[str, np.ndarray],
                      shifts: Optional[Dict[str, int]] = None,
                      mvm=None) -> Tuple[Dict[str, np.ndarray], Dict[str, int]]:
    """Pure int8 fake-quant forward pass.

    ``mvm(x_rows, w) -> int32`` defaults to the exact integer matmul
    (``exact_mvm``, a float64 BLAS product);
    passing kernels/cim_mvm's signed op makes the reference share the
    crossbar compute semantics (for saturating-ADC comparisons).
    Returns (tensors, calibrated shifts).
    """
    calibrating = shifts is None
    shifts = {} if shifts is None else dict(shifts)
    if mvm is None:
        mvm = exact_mvm
    tensors: Dict[str, np.ndarray] = dict(inputs)
    for node in graph.nodes:
        xs = [tensors[t] for t in node.inputs]
        if node.is_cim:
            w = weights[node.name]
            routed = routed_rows(node, xs)
            if routed is not None:
                y = np.asarray(mvm(xs[0][routed], w))
                sh = _shift_for(node, y, shifts, calibrating)
                out = np.zeros(xs[0].shape[:-1] + (w.shape[1],), np.int32)
                out[routed] = np.clip(y >> sh, -128, 127)
                y = out
            elif node.op_type == "Conv":
                k = node.attrs["weight_shape"][2]
                rows = im2col(xs[0], k, node.attrs.get("stride", 1),
                              node.attrs.get("pad", 0))
                y = np.asarray(mvm(rows, w))
                sh = _shift_for(node, y, shifts, calibrating)
                y = np.clip(y >> sh, -128, 127).astype(np.int32)
                cout = node.attrs["weight_shape"][0]
                oh, ow = graph.shapes[node.outputs[0]][1:]
                y = y.T.reshape(cout, oh, ow)
            else:
                rows = xs[0][None] if xs[0].ndim == 1 else xs[0]
                y = np.asarray(mvm(rows, w))
                sh = _shift_for(node, y, shifts, calibrating)
                y = np.clip(y >> sh, -128, 127).astype(np.int32)
                y = y[0] if xs[0].ndim == 1 else y
            tensors[node.outputs[0]] = y
        else:
            _store_outputs(tensors, node,
                           apply_dcom(node, xs, graph, shifts, calibrating))
    return tensors, shifts


def _store_outputs(tensors: Dict[str, np.ndarray], node: Node, y) -> None:
    """Assign a DCOM result to the node's output tensors (Split is the
    one multi-output operator: apply_dcom returns one array per part)."""
    if node.op_type == "Split":
        for name, part in zip(node.outputs, y):
            tensors[name] = part
    else:
        tensors[node.outputs[0]] = y


# ---------------------------------------------------------------------------
# Crossbar tile geometry + signed MVM semantics, shared by the op-by-op
# interpreter (below) and the trace-lowered batched executor
# (cimsim.executor) — both must address the same weight sub-matrices.
# ---------------------------------------------------------------------------

def tile_ranges(p: OpPlacement, arch: CIMArch, rt: int, ct: int
                ) -> Tuple[int, int, int, int]:
    """Row/col index ranges of tile (rt, ct) of a chunk's sub-matrix."""
    m = p.mapping
    r0 = rt * arch.xb.rows
    r1 = min(r0 + arch.xb.rows, m.r)
    cpx = logical_cols_per_xb(m, arch)
    c0 = ct * cpx
    c1 = min(c0 + cpx, m.c)
    return r0, r1, c0, c1


def chunk_offsets(node: Node, p: OpPlacement) -> Tuple[int, int]:
    """Global (row, col) offset of a chunk inside the full matrix."""
    r, c = weight_matrix_shape(node)
    sub_r, sub_c = p.mapping.r, p.mapping.c
    cc = math.ceil(c / sub_c)
    ci, ri = p.chunk % cc, p.chunk // cc
    return ri * sub_r, ci * sub_c


def spread_slice(rows_in_tile: int, parallel_row: int, row_spread: int,
                 part: int) -> Optional[Tuple[int, int]]:
    """Row sub-span [s0, s1) of spread ``part`` under the VVM remap, or
    ``None`` when the part falls past the tile's rows."""
    n_grp = max(1, math.ceil(rows_in_tile / parallel_row))
    per = math.ceil(n_grp / row_spread) * parallel_row
    s0 = part * per
    if s0 >= rows_in_tile:
        return None
    return s0, min(s0 + per, rows_in_tile)


def signed_oracle_mvm(x_rows: np.ndarray, w: np.ndarray,
                      p: CimMvmParams) -> np.ndarray:
    """Signed MVM through the crossbar oracle via offset encoding.

    The standard CIM trick shared by the interpreter, the executor and
    the saturating-ADC reference: store ``x + 2^(ab-1)`` / ``w + 2^(wb-1)``
    unsigned, run the bit-sliced ADC-saturating oracle, subtract the
    rank-1 correction digitally.
    """
    import jax.numpy as jnp
    ox = 1 << (p.act_bits - 1)
    ow = 1 << (p.weight_bits - 1)
    x_u = x_rows.astype(np.int64) + ox
    w_u = w.astype(np.int64) + ow
    y_u = np.asarray(kref.cim_mvm_ref(
        jnp.asarray(x_u, jnp.int32), jnp.asarray(w_u, jnp.int32),
        act_bits=p.act_bits, weight_bits=p.weight_bits,
        dac_bits=p.dac_bits, cell_bits=p.cell_bits,
        parallel_row=p.parallel_row, adc_bits=p.adc_bits)).astype(np.int64)
    r = x_rows.shape[-1]
    sx = x_u.sum(axis=-1, keepdims=True)
    sw = w_u.sum(axis=0, keepdims=True)
    return y_u - ow * sx - ox * sw + r * ox * ow


def reference_mvm(params: CimMvmParams):
    """The MVM the int8 reference must use for these crossbar params:
    ``None`` (exact integer matmul) when the ADC provably never
    saturates, else the offset-encoded oracle — so calibration,
    simulation and verification all share one dispatch rule."""
    if params.exact:
        return None
    return lambda x_rows, w: signed_oracle_mvm(x_rows, w, params)


# ---------------------------------------------------------------------------
# The meta-operator flow interpreter
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SimStats:
    cim_reads: int = 0
    cim_writes: int = 0
    dcom_ops: int = 0
    mov_bytes: int = 0


class FunctionalSimulator:
    """Executes an expanded meta-operator flow for one inference."""

    def __init__(self, plan: SchedulePlan, program: Program,
                 weights: Dict[str, np.ndarray],
                 shifts: Dict[str, int],
                 params: Optional[CimMvmParams] = None,
                 faults=None):
        self.plan = plan
        self.graph: Graph = plan.graph
        self.arch: CIMArch = plan.arch
        self.program = program
        self.weights = weights
        self.shifts = shifts
        self.params = params or cim_mvm_params(plan.arch)
        #: optional cimsim.faults.FaultMap — every crossbar read applies
        #: its tile's weight transform + post-MVM offset (the executor
        #: applies the identical per-span functions; see faults.py)
        self.faults = faults
        self.stats = SimStats()
        self._placement: Dict[Tuple[str, int], OpPlacement] = {}
        for p in plan.placements:
            self._placement[(p.node.name, p.chunk)] = p
        self._rows_cache: Dict[str, np.ndarray] = {}
        self._acc: Dict[str, np.ndarray] = {}       # int64 accumulators
        self._acc_pending: Dict[str, bool] = {}

    # -- crossbar-level MVM with the CIM compute semantics ---------------
    def _cim_mvm(self, x_rows: np.ndarray, w: np.ndarray,
                 parallel_row: Optional[int] = None) -> np.ndarray:
        p = self.params
        if parallel_row is not None:
            p = dataclasses.replace(p, parallel_row=parallel_row)
        return signed_oracle_mvm(x_rows, w, p)

    def _faulted(self, name: str, span: Tuple[int, int, int, int],
                 wsub: np.ndarray):
        """(effective weights, post-MVM offset or None) of one tile span
        under the active fault map — identity without one."""
        if self.faults is None:
            return wsub, None
        return (self.faults.apply_tile(name, span, wsub),
                self.faults.tile_offset(name, span))

    # -- tensor store -----------------------------------------------------
    def _tensor(self, name: str) -> np.ndarray:
        prod = self.graph.producer(name)
        if prod is not None and self._acc_pending.get(prod.name):
            self._finalize(prod)
        return self._tensors[name]

    def _finalize(self, node: Node) -> None:
        y = self._acc[node.name]
        sh = self.shifts.get(node.name, 0)
        y = np.clip(y >> sh, -128, 127).astype(np.int32)
        routed = routed_rows(node, [None] + [self._tensor(t)
                                             for t in node.inputs[1:]])
        if routed is not None:
            y = np.where(routed[:, None], y, 0)
        if node.op_type == "Conv":
            cout = node.attrs["weight_shape"][0]
            oh, ow = self.graph.shapes[node.outputs[0]][1:]
            y = y.T.reshape(cout, oh, ow)
        else:
            x_shape = self.graph.shapes[node.inputs[0]]
            if len(x_shape) == 1:
                y = y[0]
        self._tensors[node.outputs[0]] = y
        self._acc_pending[node.name] = False

    def _input_rows(self, node: Node) -> np.ndarray:
        if node.name in self._rows_cache:
            return self._rows_cache[node.name]
        x = self._tensor(node.inputs[0])
        if node.op_type == "Conv":
            k = node.attrs["weight_shape"][2]
            rows = im2col(x, k, node.attrs.get("stride", 1),
                          node.attrs.get("pad", 0))
        else:
            rows = x[None] if x.ndim == 1 else x
        self._rows_cache[node.name] = rows
        return rows

    def _tile_ranges(self, p: OpPlacement, rt: int, ct: int):
        return tile_ranges(p, self.arch, rt, ct)

    def _chunk_offsets(self, node: Node, p: OpPlacement):
        return chunk_offsets(node, p)

    # -- execution ---------------------------------------------------------
    def run(self, inputs: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        self._tensors: Dict[str, np.ndarray] = dict(inputs)
        self._rows_cache.clear()
        self._acc.clear()
        for op in self.program.walk(expand_loops=True):
            self._exec(op)
        # finalize any pending accumulators and run to the graph outputs
        for node in self.graph.nodes:
            if self._acc_pending.get(node.name):
                self._finalize(node)
        return {t: self._tensor(t) for t in self.graph.outputs}

    def _exec(self, op: MetaOp) -> None:
        k = op.kind
        a = op.attrs
        if k in ("cim.write_xb", "cim.write_row"):
            self.stats.cim_writes += 1
            return                      # weights are addressed by attrs
        if k == "mov":
            self.stats.mov_bytes += int(a.get("len", 0))
            return
        if k == "cim.read_core":
            self._read_core(a)
            return
        if k in ("cim.read_xb", "cim.read_row"):
            self._read_tile(a, wlm=(k == "cim.read_row"))
            return
        # DCOM
        self.stats.dcom_ops += 1
        if k == "shift_acc":
            return                      # folded into the accumulation
        node = self.graph.node(a["node"]) if "node" in a else None
        if node is None:
            return
        xs = [self._tensor(t) for t in node.inputs]
        y = apply_dcom(node, xs, self.graph, self.shifts, calibrating=False)
        _store_outputs(self._tensors, node, y)

    def _acc_for(self, node: Node) -> np.ndarray:
        if node.name not in self._acc:
            rows = self._input_rows(node)
            r, c = weight_matrix_shape(node)
            n = rows.shape[0]
            self._acc[node.name] = np.zeros((n, c), np.int64)
        self._acc_pending[node.name] = True
        return self._acc[node.name]

    def _read_core(self, a: Dict) -> None:
        self.stats.cim_reads += 1
        node = self.graph.node(a["node"])
        p = self._placement[(node.name, a.get("chunk", 0))]
        rows = self._input_rows(node)
        acc = self._acc_for(node)
        copy, dup = a.get("copy", 0), p.dup
        idx = np.arange(copy, rows.shape[0], dup)
        if idx.size == 0:
            return
        w = self.weights[node.name]
        ro, co = self._chunk_offsets(node, p)
        wsub = w[ro:ro + p.mapping.r, co:co + p.mapping.c]
        span = (ro, ro + wsub.shape[0], co, co + wsub.shape[1])
        wsub, off = self._faulted(node.name, span, wsub)
        y = self._cim_mvm(rows[idx][:, ro:ro + p.mapping.r], wsub)
        if off is not None:
            y = y + off[None, :]
        acc[np.ix_(idx, np.arange(co, co + wsub.shape[1]))] += y

    def _read_tile(self, a: Dict, wlm: bool) -> None:
        self.stats.cim_reads += 1
        node = self.graph.node(a["op"])
        p = self._placement[(node.name, a.get("chunk", 0))]
        rows = self._input_rows(node)
        acc = self._acc_for(node)
        copy, dup = a.get("copy", 0), p.dup
        w_idx = a["window"]
        windows = np.arange(copy, rows.shape[0], dup)
        if isinstance(w_idx, int):
            if w_idx >= windows.size:
                return
            windows = windows[w_idx:w_idx + 1]
        rt, ct = a.get("row_tile", 0), a.get("col_tile", 0)
        r0, r1, c0, c1 = self._tile_ranges(p, rt, ct)
        ro, co = self._chunk_offsets(node, p)
        w = self.weights[node.name]
        wsub = w[ro + r0:ro + min(r1, p.mapping.r),
                 co + c0:co + min(c1, p.mapping.c)]
        if wsub.size == 0:
            return
        xr0, xr1 = ro + r0, ro + r0 + wsub.shape[0]
        if wlm and p.row_spread > 1:
            span = spread_slice(wsub.shape[0], self.arch.xb.parallel_row,
                                p.row_spread, a.get("spread", 0))
            if span is None:
                return
            s0, s1 = span
            wsub = wsub[s0:s1]
            xr0, xr1 = xr0 + s0, xr0 + (s1 - s0) + s0
        fspan = (xr0, xr1, co + c0, co + c0 + wsub.shape[1])
        wsub, off = self._faulted(node.name, fspan, wsub)
        y = self._cim_mvm(rows[windows][:, xr0:xr1], wsub)
        if off is not None:
            y = y + off[None, :]
        cols = np.arange(co + c0, co + c0 + wsub.shape[1])
        acc[np.ix_(windows, cols)] += y


def calibrate_shifts(graph: Graph, weights: Dict[str, np.ndarray],
                     inputs: Dict[str, np.ndarray],
                     params: CimMvmParams) -> Dict[str, int]:
    """Requantization shifts from one reference calibration pass (the
    reference shares the crossbar compute semantics when the ADC can
    saturate, so calibration sees the hardware-true dynamic range)."""
    _, shifts = reference_forward(graph, weights, inputs,
                                  mvm=reference_mvm(params))
    return shifts


def simulate(graph: Graph, arch: CIMArch, *, level=None, seed: int = 0,
             params: Optional[CimMvmParams] = None,
             use_executor: bool = False, faults=None):
    """Compile ``graph`` for ``arch``, run the reference, execute the
    meta-op flow, and return (sim_outputs, ref_outputs, stats).

    ``use_executor=True`` runs the trace-lowered batched executor
    (cimsim.executor) instead of the op-by-op interpreter — same
    semantics, one jitted dispatch (stats are then lowering stats).
    ``faults`` (a ``cimsim.faults.FaultMap``) injects device faults into
    the simulated crossbars; the reference outputs stay fault-free, so
    the pair measures fault-induced degradation.
    """
    from ..core import compiler
    weights = make_weights(graph, seed)
    inputs = make_input(graph, seed)
    p = params or cim_mvm_params(arch)

    ref_mvm = reference_mvm(p)
    _, shifts = reference_forward(graph, weights, inputs, mvm=ref_mvm)
    ref_out, _ = reference_forward(graph, weights, inputs, shifts=shifts,
                                   mvm=ref_mvm)
    if use_executor:
        from .executor import lower
        res = compiler.compile_graph(graph, arch, level=level)
        exe = lower(res.plan, res.program, params=p, faults=faults)
        sim_out = exe.run(inputs, weights, shifts)
        stats = exe.stats
    else:
        res = compiler.compile_graph(graph, arch, level=level, expand=True)
        sim = FunctionalSimulator(res.plan, res.program, weights, shifts,
                                  params=p, faults=faults)
        sim_out = sim.run(inputs)
        stats = sim.stats
    return sim_out, {t: ref_out[t] for t in graph.outputs}, stats


@dataclasses.dataclass
class VerifyReport:
    """Outcome of one functional verification (§4.1) of a compile."""

    graph: str
    arch: str
    batch: int
    max_abs_err: Dict[str, int]          # per graph output
    lower_s: float = 0.0
    run_s: float = 0.0
    #: set when verification could not run at all (compile/lowering
    #: failure) — ``max_abs_err`` is then empty and ``ok`` is False
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None and \
            all(e == 0 for e in self.max_abs_err.values())


def compile_and_verify(graph: Graph, arch: CIMArch, *, level=None,
                       seed: int = 0, batch: int = 1,
                       params: Optional[CimMvmParams] = None,
                       use_executor: bool = True, faults=None,
                       **compile_kwargs) -> VerifyReport:
    """Compile ``graph`` for ``arch`` and verify the emitted flow against
    the int8 fake-quant reference on ``batch`` random inputs.

    The fast path (default) lowers the compiled program once with the
    batched executor and verifies all inputs in a single dispatch; a
    flow the executor cannot lower bit-exactly raises (``LoweringError``,
    ``KernelUnsupportedError``).  Only ``use_executor=False`` verifies
    op by op through the interpreter.  Extra keyword arguments (``use_pipeline``,
    ``binding``, ``cache``, ...) reach ``compile_graph``, so any DSE
    design point can be verified.  With ``faults`` set the simulated
    crossbars carry the fault map while the reference stays clean, so
    ``max_abs_err`` measures fault-induced deviation (``ok`` then means
    the faults were numerically invisible).
    """
    import time
    from ..core import compiler
    weights = make_weights(graph, seed)
    p = params or cim_mvm_params(arch)
    inputs = [make_input(graph, seed + i) for i in range(batch)]
    ref_mvm = reference_mvm(p)
    _, shifts = reference_forward(graph, weights, inputs[0], mvm=ref_mvm)
    refs = [reference_forward(graph, weights, x, shifts=shifts,
                              mvm=ref_mvm)[0] for x in inputs]

    err = {t: 0 for t in graph.outputs}
    if use_executor:
        from .executor import lower
        res = compiler.compile_graph(graph, arch, level=level,
                                     **compile_kwargs)
        t0 = time.time()
        exe = lower(res.plan, res.program, params=p, faults=faults)
        packed = exe.pack(weights)
        t1 = time.time()
        batched = {name: np.stack([x[name] for x in inputs])
                   for name in graph.inputs}
        outs = exe.run_batch(batched, packed=packed, shifts=shifts)
        t2 = time.time()
        for i in range(batch):
            for t in graph.outputs:
                d = np.abs(np.asarray(outs[t][i], np.int64)
                           - refs[i][t].astype(np.int64))
                err[t] = max(err[t], int(d.max()) if d.size else 0)
        return VerifyReport(graph=graph.name, arch=arch.name,
                            batch=batch, max_abs_err=err,
                            lower_s=t1 - t0, run_s=t2 - t1)

    res = compiler.compile_graph(graph, arch, level=level, expand=True,
                                 **compile_kwargs)
    sim = FunctionalSimulator(res.plan, res.program, weights, shifts,
                              params=p, faults=faults)
    t0 = time.time()
    for i, x in enumerate(inputs):
        out = sim.run(x)
        for t in graph.outputs:
            d = np.abs(out[t].astype(np.int64) - refs[i][t].astype(np.int64))
            err[t] = max(err[t], int(d.max()) if d.size else 0)
    return VerifyReport(graph=graph.name, arch=arch.name, batch=batch,
                        max_abs_err=err, run_s=time.time() - t0)
