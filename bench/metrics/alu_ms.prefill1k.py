"""Device time per dispatch in the operations under an ``alu`` name scope
(the integer ALU rules: softmax, norms, SiLU table, RoPE, routing
softmax and top-k), from the profiler trace."""
import span_reduce


def read(rec):
    return span_reduce.scope_ms(rec.get("spans"), "alu", rec["batches"])
