"""Requests completed in the window over the seconds from the window's
start to the last completion (harness clock)."""


def read(rec):
    return rec["completed"] / rec["window_s"]
