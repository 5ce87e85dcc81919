"""Host time per dispatch copying the outputs to the host: the median of
the program's ``cim.executor.fetch`` spans, from the profiler trace's
host plane.  Backlog cells."""
import span_reduce


def read(rec):
    return span_reduce.median_ms(rec.get("spans"), "cim.executor.fetch")
