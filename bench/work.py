"""Work counts of a network, from the benchmark's own description of it
(``bench/nets``), and the chips' published peaks (``peaks.json``).

They depend on nothing the served program computes, so no change to the
program can move its own yardstick.
"""
from __future__ import annotations

import json
from pathlib import Path

from reference import cim_nodes

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def macs_per_inference(net: dict) -> int:
    """Multiply-accumulates of one inference: windows x R x C summed over
    the crossbar nodes (convolutions and the fully connected layer)."""
    return sum(n["windows"] * n["R"] * n["C"] for n in cim_nodes(net))
