"""Device time per dispatch in the operations under an ``im2col`` name
scope (the im2col gathers of every CIM node, ``jax.named_scope`` in the
executor's traced program), from the profiler trace.  Backlog cells."""
import span_reduce


def read(rec):
    return span_reduce.scope_ms(rec.get("spans"), "im2col", rec["batches"])
