"""Seconds the executor spent lowering the program and packing the
weights at set-up: the sums of ``executor_lower_s`` and
``executor_pack_s`` while the fleet was built."""


def read(rec):
    h = rec["hist"]
    return h.get("executor_lower_s", 0.0) + h.get("executor_pack_s", 0.0)
