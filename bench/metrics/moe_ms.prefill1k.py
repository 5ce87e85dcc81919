"""Device time per dispatch in the operations under a ``moe`` name scope
(router, routed-row dispatch, the experts' rounds and the gated
combine), from the profiler trace."""
import span_reduce


def read(rec):
    return span_reduce.scope_ms(rec.get("spans"), "moe", rec["batches"])
