"""Median latency over every request due in the window, from its due
time to its outputs on the host (harness clock)."""
import statistics


def read(rec):
    return statistics.median(rec["latencies_ms"])
