"""Seconds the program spent calibrating the requantization shifts at
set-up (a reference forward pass on the host): the program's
``service_calibrate_s`` histogram while the fleet was built."""


def read(rec):
    return rec["hist"].get("service_calibrate_s")
