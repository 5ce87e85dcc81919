"""Seconds the CIM compiler spent at set-up: the sum of the program's
``compile_wall_s`` histogram while the fleet was built."""


def read(rec):
    return rec["hist"].get("compile_wall_s", 0.0)
