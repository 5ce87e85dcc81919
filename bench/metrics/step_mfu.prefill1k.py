"""The whole forward step's share of the chip's int8 peak: 2 x the
network's multiply-accumulates per inference (``work.py``, from the
benchmark's own description of the network) x inferences completed per
second of the traced window, over the int8 peak of ``peaks.json``.
Backlog cells."""
import work


def read(rec):
    t = rec["trace"]
    if not t or not rec["peaks"]:
        return None
    rate = rec["completed"] / t["window_s"]
    ops = 2 * work.macs_per_inference(rec["net"]) * rate
    return 100.0 * ops / rec["peaks"]["int8_ops_per_s"]
