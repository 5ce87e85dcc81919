"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

``load`` reads the device operations (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) and the harness's own host spans (the
``bench.*`` annotations the harness opened on the host), all on the
profiler's clock in nanoseconds.  Asynchronous operations are left out:
an ``async-start``/``copy-start``/``slice-start`` event (and its
``-update``/``-done``) lasts until the result is awaited, which can
span a gap in which nothing computes; the TPU puts them on a line of
their own (``Async XLA Ops``), and they are dropped from any line.  ``reduce`` clips everything to the
traced window (the ``bench.window`` span) and returns:

* ``busy_s``: per chip, the union of the intervals in which an
  operation ran, averaged over the chips;
* ``window_s``: the window's length;
* ``op_s``: device seconds per operation (keyed by the event's full
  name, which on a TPU is the HLO instruction's text), summed over
  chips;
* ``idle_by_span_s``: the idle gaps, each charged to the harness span
  that was open at the gap's middle (``bench.window`` itself where the
  harness was between spans);
* ``breakdown``: the ten operations that took most time (named by the
  HLO text before its first layout brace: name, type and shape) and the
  ten host spans under which the device was idle longest.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
TOP = 10
#: an asynchronous HLO instruction, by its name (``%copy-start.15 = ...``,
#: ``slice-done.3``, ``all-gather-start``): it spans a wait, not work
ASYNC = re.compile(r"^%?[\w.-]*?-(start|update|done)(\.\d+)?(\s*=|$)")

Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str):
    """(device ops per chip, host spans) of one trace file."""
    from jax.profiler import ProfileData
    return events_of(ProfileData.from_file(path))


def events_of(profile):
    """``{chip: [(name, start_ns, end_ns)]}`` and ``[(name, start_ns,
    end_ns)]`` for the harness's host spans."""
    device: Dict[str, List] = {}
    spans: List = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, int(e.start_ns), int(e.end_ns))
                               for e in line.events
                               if not ASYNC.match(e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns), int(e.end_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return device, spans


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class _SpanIndex:
    """The harness span open at a time.  The harness's spans other than
    the window do not overlap one another, so a bisect finds it."""

    def __init__(self, spans):
        inner = sorted((s, e, name) for name, s, e in spans if name != WINDOW)
        self.starts = [s for s, _, _ in inner]
        self.inner = inner

    def at(self, t: int) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t < self.inner[i][1]:
            return self.inner[i][2]
        return WINDOW


def short_name(op: str) -> str:
    return op.split("{", 1)[0].strip()


def reduce(device: Dict[str, List], spans: List) -> Optional[dict]:
    """The window's device numbers, or ``None`` where the trace holds no
    window or no device operation."""
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if not windows or not any(device.values()):
        return None
    lo, hi = windows[0]
    op_ns: Dict[str, int] = collections.Counter()
    idle_ns: Dict[str, int] = collections.Counter()
    busy_ns = []
    index = _SpanIndex(spans)
    for ops in device.values():
        if not ops:
            continue
        clipped = _clip([(s, e) for _, s, e in ops], lo, hi)
        busy = union(clipped)
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, e in ops:
            if e > lo and s < hi:
                op_ns[name] += min(e, hi) - max(s, lo)
        for s, e in gaps(busy, lo, hi):
            idle_ns[index.at((s + e) // 2)] += e - s
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": (hi - lo) / 1e9,
        "op_s": {k: v / 1e9 for k, v in op_ns.items()},
        "idle_by_span_s": {k: v / 1e9 for k, v in idle_ns.items()},
        "breakdown": {
            "device_ops": [[short_name(k), v / 1e9]
                           for k, v in op_ns.most_common(TOP)],
            "idle_gaps": [[k, v / 1e9] for k, v in idle_ns.most_common(TOP)],
        },
    }
