"""Seconds of the warm-up dispatches, one per bucket shape the mix uses
(JAX compile or cache load, and the first runs), on the harness clock."""


def read(rec):
    return rec["jit_warm_s"]
