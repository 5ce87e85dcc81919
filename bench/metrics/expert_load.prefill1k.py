"""How unevenly the router loads the held experts: per MoE layer, the
most token rows routed to one held expert over the mean of the held
experts, from the program counter ``executor_routed_rows_total`` (rows
routed per expert, summed over the traced window's dispatches); the
largest over layers.  1 is an even load."""
import collections
import re

SERIES = re.compile(r'^executor_routed_rows_total\{expert="(\d+)",node="([^"]+)"\}$')


def read(rec):
    counters = (rec.get("counters") or {}).get("counters") or {}
    rows = collections.defaultdict(list)
    for series, v in counters.items():
        m = SERIES.match(series)
        if m:
            rows[m.group(2)].append(v)
    loads = [max(v) / (sum(v) / len(v)) for v in rows.values() if sum(v) > 0]
    return max(loads) if loads else None
