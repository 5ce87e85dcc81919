"""Work counts of a network, from the benchmark's own description of it
(``bench/nets``), and the chips' published peaks (``peaks.json``).

They depend on nothing the served program computes, so no change to the
program can move its own yardstick.
"""
from __future__ import annotations

import json
from pathlib import Path

from reference import is_crossbar

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def node_macs(node: dict) -> int:
    """A node's multiply-accumulates per inference: its own ``macs``
    where it states them (a matmul the ALU runs), windows x R x C for a
    crossbar node, none otherwise."""
    if "macs" in node:
        return node["macs"]
    if is_crossbar(node):
        return node["windows"] * node["R"] * node["C"]
    return 0


def macs_per_inference(net: dict) -> int:
    """Multiply-accumulates of one inference, summed over the nodes."""
    return sum(node_macs(n) for n in net["nodes"])
