"""A two-layer MLP, for the benchmark's own CPU tests only:
gemm(32) - relu - gemm(8) on a 16-wide input.

Its crossbar op is its own (``gemm``, in ``OPS``), not the reference's
``fc``: a matrix of token rows times the node's weights, each row through
the crossbar, the whole accumulator requantized by the node's one shift.
It shows that a network arrives as a file of its own.
"""
import numpy as np

from reference import NetBuilder


def gemm(node, xs, ctx):
    """(..., R) int8 rows times the node's (R, C) weights -> (..., C)."""
    x = xs[0]
    rows = x.reshape(-1, node["R"])
    w = ctx.weights[node["name"]]
    acc = ctx.cim_mvm(ctx.operand(rows), ctx.operand(w), ctx.cim)
    return ctx.shifted(node["name"], acc).reshape(*x.shape[:-1], node["C"])


OPS = {"gemm": gemm}


def build(d_in: int = 16, d_h: int = 32, d_out: int = 8) -> dict:
    b = NetBuilder("tiny_mlp", (d_in,))

    def fc(name, t, cin, cout):
        tokens = int(np.prod(b.shapes[t][:-1], dtype=np.int64))
        return b.node(name, "gemm", [t], (*b.shapes[t][:-1], cout),
                      R=cin, C=cout, windows=tokens)

    t = b.relu("relu", fc("fc1", "input", d_in, d_h))
    return b.net(fc("fc2", t, d_h, d_out), ops=OPS)
