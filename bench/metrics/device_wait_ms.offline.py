"""Host time per dispatch waiting on the jitted program: the median of
the program's ``cim.executor.run`` spans, each from the call until its
outputs are ready on the device, from the profiler trace's host plane.
Backlog cells."""
import span_reduce


def read(rec):
    return span_reduce.median_ms(rec.get("spans"), "cim.executor.run")
