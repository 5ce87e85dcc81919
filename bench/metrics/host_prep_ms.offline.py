"""Host time per dispatch spent getting its inputs onto the chip: the
median of the program's ``cim.service.stack`` spans (input stacking and
pad-to-bucket) plus the median of its ``cim.executor.put`` spans
(shifts and inputs to the device, until ready), from the profiler
trace's host plane.  Backlog cells."""
import span_reduce


def read(rec):
    return span_reduce.median_ms(rec.get("spans"), "cim.service.stack",
                                 "cim.executor.put")
