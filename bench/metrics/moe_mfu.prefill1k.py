"""The expert GEMMs' share of the chip's int8 peak while they run: 2 x
the multiply-accumulates of the network's expert nodes per inference
(``work.py``: shared experts whole, each routed expert at its expected
share of the tokens) x inferences completed in the traced window, over
the device seconds under the ``experts`` name scope, over the int8 peak
of ``peaks.json``."""
import span_reduce
import work


def read(rec):
    if not rec["peaks"]:
        return None
    per_dispatch_ms = span_reduce.scope_ms(rec.get("spans"), "experts",
                                           rec["batches"])
    macs = sum(work.node_macs(n) for n in rec["net"]["nodes"]
               if n.get("expert"))
    if not per_dispatch_ms or not macs:
        return None
    seconds = per_dispatch_ms * rec["batches"] / 1e3
    ops = 2 * macs * rec["completed"] / seconds
    return 100.0 * ops / rec["peaks"]["int8_ops_per_s"]
