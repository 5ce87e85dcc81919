"""Device time per dispatch in the operations under an ``mla`` name scope
(the MLA blocks: projections, RoPE, per-head attention), from the
profiler trace."""
import span_reduce


def read(rec):
    return span_reduce.scope_ms(rec.get("spans"), "mla", rec["batches"])
