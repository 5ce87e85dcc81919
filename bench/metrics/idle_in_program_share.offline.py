"""Share of the window's device-idle time during which one of the
program's ``cim.*`` spans was open on the host (the rest falls under the
harness's own spans), from the profiler trace.  Backlog cells."""


def read(rec):
    s = rec.get("spans")
    if not s or not s["idle_s"]:
        return None
    return 100.0 * s["idle_in_program_s"] / s["idle_s"]
