"""95th percentile of the latency over every request due in the window,
from its due time to its outputs on the host (harness clock)."""
import statistics


def read(rec):
    return statistics.quantiles(rec["latencies_ms"], n=100,
                                method="inclusive")[94]
