"""Plain int8 reference of a CIM-served network, written for the benchmark.

It imports nothing of the program under test.  A network is a list of
nodes built by a module under ``bench/nets/``; each node is a dict with
``name``, ``op``, ``inputs``, ``output`` and its sizes.  The semantics
follow the CIM-MLC paper's verification setup (§4.1): int8 fake-quant
activations and weights, exact int32 accumulation, and a per-node
arithmetic right shift back to int8 that one calibration pass picks.

This module defines the ops of ResNet (``conv``, ``fc``, ``relu``,
``add``, ``maxpool``, ``gap``, ``flatten``).  A network module may bring
ops of its own: it passes ``{op name: function}`` to ``NetBuilder.net``
and appends their nodes with ``NetBuilder.node``; ``forward`` calls
``function(node, inputs, ctx)`` with an ``OpContext``, whose ``shifted``
and ``operand`` calibrate, requantize and (in the control) degrade the
op exactly as they do ``conv`` and ``fc``.  A node that carries ``R``
and ``C`` (and ``windows``, the rows it multiplies per inference) is a
crossbar node, whatever its op: it gets seeded int8 weights (R, C) and
its multiply-accumulates count in ``work.py``.  A node may state its
own ``macs``, for a matmul that the ALU runs.

A crossbar MVM (``cim_mvm``) follows the bit-sliced crossbar of §3.2.3:
inputs and weights are offset-encoded to unsigned, the input is fed
``dac_bits`` at a time, weights are stored ``cell_bits`` per cell, at
most ``parallel_row`` rows are summed per analog read, each read is
digitised by an ``adc_bits`` ADC that saturates, and the digital side
shift-adds the reads and removes the offset.  Where the ADC cannot
saturate this is the exact integer matmul, which is what is computed.

All matmuls run in float64 on the host: every product and partial sum
here is an integer below 2**53, so they are exact.
"""
from __future__ import annotations

import math
import zlib
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

#: the ops this module defines; a network's own op table may not name one
BUILTIN_OPS = ("conv", "fc", "relu", "add", "maxpool", "gap", "flatten")


# -- network description ------------------------------------------------------

class NetBuilder:
    """Appends nodes in order and tracks each tensor's shape."""

    def __init__(self, name: str, input_shape: Tuple[int, ...]):
        self.name = name
        self.input_shape = tuple(input_shape)
        self.nodes: List[dict] = []
        self.shapes: Dict[str, Tuple[int, ...]] = {"input": self.input_shape}

    def _add(self, node: dict, out_shape) -> str:
        self.nodes.append(node)
        self.shapes[node["output"]] = tuple(out_shape)
        return node["output"]

    def conv(self, name, tin, cout, k, stride, pad) -> str:
        cin, h, _ = self.shapes[tin]
        oh = (h + 2 * pad - k) // stride + 1
        return self._add(dict(name=name, op="conv", inputs=[tin],
                              output=f"{name}.out", cin=cin, cout=cout, k=k,
                              stride=stride, pad=pad, out_hw=oh,
                              R=cin * k * k, C=cout, windows=oh * oh),
                         (cout, oh, oh))

    def fc(self, name, tin, cout) -> str:
        (cin,) = self.shapes[tin]
        return self._add(dict(name=name, op="fc", inputs=[tin],
                              output=f"{name}.out", R=cin, C=cout,
                              windows=1), (cout,))

    def relu(self, name, tin) -> str:
        return self._add(dict(name=name, op="relu", inputs=[tin],
                              output=f"{name}.out"), self.shapes[tin])

    def add(self, name, a, b) -> str:
        return self._add(dict(name=name, op="add", inputs=[a, b],
                              output=f"{name}.out"), self.shapes[a])

    def maxpool(self, name, tin, k, stride, pad) -> str:
        c, h, _ = self.shapes[tin]
        oh = (h + 2 * pad - k) // stride + 1
        return self._add(dict(name=name, op="maxpool", inputs=[tin],
                              output=f"{name}.out", k=k, stride=stride,
                              pad=pad), (c, oh, oh))

    def gap(self, name, tin) -> str:
        c = self.shapes[tin][0]
        return self._add(dict(name=name, op="gap", inputs=[tin],
                              output=f"{name}.out"), (c, 1, 1))

    def flatten(self, name, tin, out) -> str:
        n = int(np.prod(self.shapes[tin]))
        return self._add(dict(name=name, op="flatten", inputs=[tin],
                              output=out), (n,))

    def node(self, name, op, inputs, out_shape, output=None, **sizes) -> str:
        """A node of any op, with its sizes and output shape; its output
        tensor is ``<name>.out`` unless ``output`` names it."""
        return self._add(dict(name=name, op=op, inputs=list(inputs),
                              output=output or f"{name}.out", **sizes),
                         out_shape)

    def net(self, output: str, ops: Optional[Dict[str, Callable]] = None
            ) -> dict:
        """The network; ``ops`` are the network's own ops, by name, none
        of them named like an op of this module."""
        clash = sorted(set(ops or {}) & set(BUILTIN_OPS))
        if clash:
            raise ValueError(f"network ops {clash} shadow the reference's")
        return {"name": self.name, "input_shape": self.input_shape,
                "nodes": self.nodes, "output": output,
                "shapes": dict(self.shapes), "ops": dict(ops or {})}


def is_crossbar(node: dict) -> bool:
    """A crossbar node holds an int8 weight matrix of R rows, C columns."""
    return "R" in node and "C" in node


def cim_nodes(net: dict) -> List[dict]:
    return [n for n in net["nodes"] if is_crossbar(n)]


# -- seeded data ----------------------------------------------------------------

def make_weights(net: dict, seed: int) -> Dict[str, np.ndarray]:
    """Signed int8 weights (R, C) per crossbar node.  The same recipe
    as the served program's seeded weights (a stable CRC32 of the node's
    name and the seed picks each node's stream), kept here so that the
    reference makes its own."""
    out = {}
    for n in cim_nodes(net):
        rng = np.random.default_rng(zlib.crc32(f"{n['name']}\x00{seed}".encode()))
        out[n["name"]] = rng.integers(-128, 128, (n["R"], n["C"])).astype(np.int32)
    return out


def calibration_input(net: dict, seed: int) -> np.ndarray:
    """The input whose pass picks the shifts (the same draw as the
    served program's calibration input)."""
    return np.random.default_rng(seed).integers(
        -128, 128, net["input_shape"]).astype(np.int32)


# -- arithmetic -------------------------------------------------------------------

def requant(y: np.ndarray, shift: int) -> np.ndarray:
    return np.clip(y >> shift, -128, 127).astype(np.int64)


def pick_shift(y: np.ndarray) -> int:
    """Smallest right shift that brings max |y| into int8."""
    m = int(np.abs(y).max()) if y.size else 0
    if m <= 127:
        return 0
    return max(0, int(math.ceil(math.log2((m + 1) / 127.0))))


def _planes(v: np.ndarray, total_bits: int, plane_bits: int) -> List[np.ndarray]:
    mask = (1 << plane_bits) - 1
    return [(v >> (i * plane_bits)) & mask
            for i in range(math.ceil(total_bits / plane_bits))]


def cim_mvm(x: np.ndarray, w: np.ndarray, cim: dict) -> np.ndarray:
    """(M, R) int8 rows times (R, C) int8 weights through the crossbar
    described by ``cim``; returns (M, C) int64."""
    x = x.astype(np.int64)
    w = w.astype(np.int64)
    ab, wb = cim["act_bits"], cim["weight_bits"]
    db, cb = cim["dac_bits"], cim["cell_bits"]
    pr, adc = cim["parallel_row"], cim["adc_bits"]
    vmax = min(pr, x.shape[1]) * ((1 << db) - 1) * ((1 << cb) - 1)
    if vmax <= (1 << adc) - 1:          # the ADC never saturates
        return (x.astype(np.float64) @ w.astype(np.float64)).astype(np.int64)
    ox, ow = 1 << (ab - 1), 1 << (wb - 1)
    xu, wu = x + ox, w + ow
    m, r = xu.shape
    c = wu.shape[1]
    pr = min(pr, r)
    g = math.ceil(r / pr)
    if g * pr != r:
        xu = np.pad(xu, ((0, 0), (0, g * pr - r)))
        wu = np.pad(wu, ((0, g * pr - r), (0, 0)))
    # one analog read sums at most pr * (2^db - 1) * (2^cb - 1) < 2^24:
    # exact in float32 where that holds, float64 otherwise
    ft = np.float32 if vmax < (1 << 24) else np.float64
    slices = _planes(wu, wb, cb)
    n_s = len(slices)
    wg = np.concatenate(slices, axis=1).reshape(g, pr, n_s * c).astype(ft)
    yu = np.zeros((m, c), np.int64)
    for p, xp in enumerate(_planes(xu, ab, db)):
        xg = xp.reshape(m, g, pr).transpose(1, 0, 2).astype(ft)
        reads = np.minimum(np.matmul(xg, wg), (1 << adc) - 1)  # (G, M, S*C)
        per_slice = reads.astype(np.int64).sum(axis=0).reshape(m, n_s, c)
        for s in range(n_s):
            yu += per_slice[:, s] << (p * db + s * cb)
    return (yu - ow * (x + ox).sum(axis=1, keepdims=True)
            - ox * (w + ow).sum(axis=0, keepdims=True) + r * ox * ow)


def im2col(x: np.ndarray, k: int, stride: int, pad: int) -> np.ndarray:
    """(C, H, W) -> (H_out*W_out, C*k*k), rows in (C, ky, kx) order."""
    c = x.shape[0]
    xp = np.pad(x, ((0, 0), (pad, pad), (pad, pad)))
    win = sliding_window_view(xp, (k, k), axis=(1, 2))[:, ::stride, ::stride]
    oh, ow = win.shape[1:3]
    return win.transpose(1, 2, 0, 3, 4).reshape(oh * ow, c * k * k)


def _maxpool(x: np.ndarray, k: int, stride: int, pad: int) -> np.ndarray:
    xp = np.pad(x, ((0, 0), (pad, pad), (pad, pad)),
                constant_values=-(2 ** 31))
    win = sliding_window_view(xp, (k, k), axis=(1, 2))[:, ::stride, ::stride]
    return win.max(axis=(3, 4))


class OpContext(NamedTuple):
    """What ``forward`` hands a network's own op beside its node and
    inputs: the crossbar weights by node name; ``shifted(name, y)``, the
    node's calibrated requantization of its accumulator ``y``;
    ``operand(v)``, a crossbar operand at the pass's precision; the
    crossbar parameters; and ``cim_mvm``."""
    weights: Dict[str, np.ndarray]
    shifted: Callable[[str, np.ndarray], np.ndarray]
    operand: Callable[[np.ndarray], np.ndarray]
    cim: dict
    cim_mvm: Callable[[np.ndarray, np.ndarray, dict], np.ndarray]


def forward(net: dict, weights: Dict[str, np.ndarray], x: np.ndarray,
            cim: dict, shifts: Optional[Dict[str, int]] = None,
            operand_bits: int = 8) -> Tuple[np.ndarray, Dict[str, int]]:
    """One inference; returns (the network's output, the shifts).

    With ``shifts=None`` this is the calibration pass: each shifted
    node picks its shift from its own accumulator.  ``operand_bits`` below
    8 rounds every crossbar operand to that many bits (kept at the int8
    scale): the lower-precision control, never the served semantics.
    """
    calibrating = shifts is None
    shifts = {} if calibrating else dict(shifts)

    def shifted(name, y):
        if calibrating:
            shifts[name] = pick_shift(y)
        return requant(y, shifts.get(name, 0))

    def operand(v):
        if operand_bits >= 8:
            return v
        step = 1 << (8 - operand_bits)
        lim = 1 << (operand_bits - 1)
        return np.clip(np.round(v / step), -lim, lim - 1).astype(np.int64) * step

    ctx = OpContext(weights, shifted, operand, cim, cim_mvm)
    own_ops = net.get("ops", {})
    t: Dict[str, np.ndarray] = {"input": np.asarray(x, np.int64)}
    for n in net["nodes"]:
        xs = [t[i] for i in n["inputs"]]
        op = n["op"]
        if op == "conv":
            rows = im2col(xs[0], n["k"], n["stride"], n["pad"])
            y = shifted(n["name"], cim_mvm(operand(rows),
                                           operand(weights[n["name"]]), cim))
            y = y.T.reshape(n["cout"], n["out_hw"], n["out_hw"])
        elif op == "fc":
            y = shifted(n["name"], cim_mvm(operand(xs[0][None]),
                                           operand(weights[n["name"]]), cim))[0]
        elif op == "relu":
            y = np.maximum(xs[0], 0)
        elif op == "add":
            y = shifted(n["name"], xs[0] + xs[1])
        elif op == "maxpool":
            y = _maxpool(xs[0], n["k"], n["stride"], n["pad"])
        elif op == "gap":
            h, w = xs[0].shape[1:]
            y = xs[0].sum(axis=(1, 2), keepdims=True) // (h * w)
        elif op == "flatten":
            y = xs[0].reshape(-1)
        elif op in own_ops:
            y = own_ops[op](n, xs, ctx)
        else:
            raise ValueError(f"reference has no op {op!r}")
        t[n["output"]] = y
    return t[net["output"]], shifts
