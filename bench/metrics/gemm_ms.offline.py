"""Device time per dispatch in the operations under a ``gemm`` name scope
(the crossbar matrix products of every CIM node, ``jax.named_scope`` in
the executor's traced program), from the profiler trace.  Backlog
cells."""
import span_reduce


def read(rec):
    return span_reduce.scope_ms(rec.get("spans"), "gemm", rec["batches"])
