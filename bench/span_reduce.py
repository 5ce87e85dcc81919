"""Reduce a JAX profiler trace (``.xplane.pb``) to the program's own spans
and to device time per name scope.

The program opens ``cim.*`` spans (``repro.obs.trace.span`` with the
profiler sink on) on the profiler's host plane, nested inside the
harness's ``bench.*`` spans and on the same clock as the device's
operations.  ``load`` reads:

* the device operations, as ``trace_reduce`` does (the ``XLA Ops`` line
  of each TPU plane, asynchronous events left out), each with the
  ``jax.named_scope`` path of the operation.  On a TPU that path is not
  among an event's own stats but in its metadata's (the stat
  ``SCOPE_STAT``, the HLO instruction's ``op_name`` and a colon, e.g.
  ``jit(_forward)/conv3/im2col/gather:``), which
  ``ProfileData`` does not expose, so ``scopes`` reads it from the
  file's bytes.  A fusion carries the scope of its root instruction, so
  its whole time is charged there, even where XLA fused ops of another
  scope into it;
* the host spans named ``bench.*`` or ``cim.*``, with the ``#k=v#``
  suffix that ``TraceAnnotation`` gives spans with arguments stripped.

``reduce`` clips everything to the traced window (``bench.window``; the
spans' own extent where there is none) and returns:

* ``spans``: per ``cim.*`` name, the count, the durations in seconds in
  time order, and the self time (duration less that of the spans nested
  directly inside it, on one host thread);
* ``idle_s``: seconds in which no operation ran on the chip (averaged
  over the chips), and ``idle_by_span_s``: those seconds split by the
  innermost span open during each stretch of them (``bench.window``
  where nothing else was open);
* ``idle_in_program_s``: the part of ``idle_s`` during which some
  ``cim.*`` span was open;
* ``scope_s``: device seconds per name-scope path (the ``op_name`` less
  its last part, the primitive), summed over chips; ``None`` where the
  trace has no device plane;
* ``breakdown``: ``idle_gaps_program``, the ten spans under which the
  device was idle longest.
"""
from __future__ import annotations

import collections
import re
import statistics
from typing import Dict, List, Optional, Tuple

import trace_reduce

PROGRAM_PREFIX = "cim."
SPAN_PREFIXES = (trace_reduce.SPAN_PREFIX, PROGRAM_PREFIX)
WINDOW = trace_reduce.WINDOW
#: the event-metadata stat holding an operation's ``op_name`` path
SCOPE_STAT = "tf_op"
TOP = 10
_ARGS = re.compile(r"#.*#$")

Span = Tuple[str, int, int]


# -- the file's bytes: the stats of the device planes' event metadata ----------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of each field of the message in
    ``buf[lo:hi]``: an int for a varint, a ``(lo, hi)`` slice for a
    length-delimited field, the raw bytes otherwise."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf: bytes, s: Tuple[int, int]) -> str:
    return buf[s[0]:s[1]].decode("utf-8", "replace")


def scopes(data: bytes) -> Dict[str, str]:
    """``{event name: SCOPE_STAT value}`` over the event metadata of the TPU
    planes of a serialized ``XSpace`` (``XSpace.planes`` 1;
    ``XPlane.name`` 2, ``event_metadata`` 4, ``stat_metadata`` 5;
    ``XEventMetadata.name`` 2, ``stats`` 5; ``XStat.metadata_id`` 1,
    ``str_value`` 5, ``ref_value`` 7, the id of a stat metadata whose
    name is the value)."""
    out: Dict[str, str] = {}
    for f, plane in _fields(data, 0, len(data)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(data, *plane):
            if pf == 2:
                name = _text(data, v)
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                key, meta = _map_entry(data, v)
                for mf, mv in _fields(data, *meta):
                    if mf == 2:
                        stat_names[key] = _text(data, mv)
        if not name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        wanted = {k for k, n in stat_names.items() if n == SCOPE_STAT}
        for entry in events:
            _, meta = _map_entry(data, entry)
            ev_name, value = None, None
            for mf, mv in _fields(data, *meta):
                if mf == 2:
                    ev_name = _text(data, mv)
                elif mf == 5:
                    value = _stat_value(data, mv, wanted, stat_names) \
                        or value
            if ev_name is not None and value:
                out[ev_name] = value
    return out


def _map_entry(data: bytes, s: Tuple[int, int]):
    key, value = 0, (s[0], s[0])
    for f, v in _fields(data, *s):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat_value(data, s, wanted, stat_names) -> Optional[str]:
    mid, value = None, None
    for f, v in _fields(data, *s):
        if f == 1:
            mid = v
        elif f == 5:
            value = _text(data, v)
        elif f == 7:
            value = stat_names.get(v)
    return value if mid in wanted else None


# -- events --------------------------------------------------------------------

def load(path: str):
    """(device operations per chip, each ``(name, start_ns, end_ns,
    scope)``, and the ``bench.*``/``cim.*`` host spans) of one trace."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    return events_of(ProfileData.from_serialized_xspace(data), scopes(data))


def events_of(profile, op_scopes: Dict[str, str]):
    device, _ = trace_reduce.events_of(profile)
    device = {chip: [(n, s, e, op_scopes.get(n, "")) for n, s, e in ops]
              for chip, ops in device.items()}
    spans: List[Span] = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend((_ARGS.sub("", e.name), int(e.start_ns),
                          int(e.end_ns))
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIXES))
    return device, spans


def _innermost(spans: List[Span], lo: int, hi: int):
    """The window cut into stretches, each named by the innermost span
    open over it (``WINDOW`` where none is), and each span's self time.
    Spans nest: they come from one thread's annotations."""
    order = sorted((s for s in spans if s[0] != WINDOW),
                   key=lambda s: (s[1], -s[2]))
    pieces: List[Span] = []
    self_ns: Dict[int, int] = {}
    stack: List[int] = []               # indices into ``order``
    t = lo

    def cut(until):
        nonlocal t
        until = min(max(until, lo), hi)
        if until > t:
            pieces.append((order[stack[-1]][0] if stack else WINDOW, t,
                           until))
            t = until

    def close_to(start):
        while stack and order[stack[-1]][2] <= start:
            cut(order[stack[-1]][2])
            stack.pop()

    for i, (_, s, e) in enumerate(order):
        close_to(s)
        cut(s)
        self_ns[i] = e - s
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    close_to(hi + 1)
    cut(hi)
    return pieces, order, self_ns


def reduce(device: Dict[str, List], spans: List[Span]) -> Optional[dict]:
    """The program's spans and the device's idle time and scopes in the
    window, or ``None`` where the trace holds neither a program span nor
    a device operation."""
    if not any(device.values()) and \
            not any(n.startswith(PROGRAM_PREFIX) for n, _, _ in spans):
        return None
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    lo, hi = windows[0] if windows else (min(s for _, s, _ in spans),
                                         max(e for _, _, e in spans))
    inside = [sp for sp in spans if sp[1] >= lo and sp[2] <= hi]
    pieces, order, self_ns = _innermost(inside, lo, hi)

    per = collections.defaultdict(lambda: {"count": 0, "durations_s": [],
                                           "self_s": 0.0})
    for i, (name, s, e) in enumerate(order):
        if name.startswith(PROGRAM_PREFIX):
            p = per[name]
            p["count"] += 1
            p["durations_s"].append((e - s) / 1e9)
            p["self_s"] += self_ns[i] / 1e9

    chips = [ops for ops in device.values() if ops]
    idle_ns: Dict[str, float] = collections.Counter()
    scope_ns: Dict[str, int] = collections.Counter()
    for ops in chips:
        busy = trace_reduce.union(trace_reduce._clip(
            [(s, e) for _, s, e, _ in ops], lo, hi))
        _charge(trace_reduce.gaps(busy, lo, hi), pieces, idle_ns,
                1.0 / len(chips))
        for _, s, e, scope in ops:
            if e > lo and s < hi:
                scope_ns[scope.rsplit("/", 1)[0]] += min(e, hi) - max(s, lo)
    idle_s = {k: v / 1e9 for k, v in idle_ns.items()}
    return {
        "window_s": (hi - lo) / 1e9,
        "spans": dict(per),
        "idle_s": sum(idle_s.values()) if chips else None,
        "idle_by_span_s": idle_s,
        "idle_in_program_s": sum(v for k, v in idle_s.items()
                                 if k.startswith(PROGRAM_PREFIX))
        if chips else None,
        "scope_s": {k: v / 1e9 for k, v in scope_ns.items()}
        if chips else None,
        "breakdown": {"idle_gaps_program": [
            [k, v] for k, v in sorted(idle_s.items(),
                                      key=lambda kv: -kv[1])[:TOP]]},
    }


def _charge(gaps, pieces, out, weight: float) -> None:
    """Add each gap's overlap with each stretch to that stretch's span."""
    j = 0
    for s, e in gaps:
        while j < len(pieces) and pieces[j][2] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][1] < e:
            name, ps, pe = pieces[k]
            out[name] += weight * (min(e, pe) - max(s, ps))
            k += 1


# -- what the metric readers take from ``reduce``'s result ----------------------

def median_ms(reduced: Optional[dict], *names: str) -> Optional[float]:
    """The sum of the median durations, in ms, of the ``cim.*`` spans
    ``names``; ``None`` where one of them is missing."""
    spans = (reduced or {}).get("spans", {})
    if not all(spans.get(n, {}).get("durations_s") for n in names):
        return None
    return 1e3 * sum(statistics.median(spans[n]["durations_s"])
                     for n in names)


def scope_ms(reduced: Optional[dict], scope: str,
             dispatches: int) -> Optional[float]:
    """Device ms per dispatch in the operations with ``scope`` among the
    parts of their name-scope path; ``None`` where the trace has no
    device plane or no such operation."""
    paths = (reduced or {}).get("scope_s")
    if not paths or not dispatches:
        return None
    hits = [s for p, s in paths.items() if scope in p.split("/")]
    return 1e3 * sum(hits) / dispatches if hits else None
