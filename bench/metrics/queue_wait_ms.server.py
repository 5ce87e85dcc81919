"""Median wait of a request from its due time to the start of the
``CimFleet.step`` that dispatched it (harness clock), over every request
due in the window.  Open-loop cells."""
import statistics


def read(rec):
    return statistics.median(rec["queue_wait_ms"])
