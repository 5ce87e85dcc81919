"""Seconds from process start to the first timed request: plan,
compile, lower, pack, shift calibration and warm-up (harness clock)."""


def read(rec):
    return rec["setup_s"]
