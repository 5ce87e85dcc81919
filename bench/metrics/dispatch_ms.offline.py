"""Mean wall time of one dispatch in the window: the growth of
``ServiceStats.serve_s`` over the window divided by the dispatches made
in it (each ends on the outputs' host copy).  Backlog cells."""


def read(rec):
    if not rec["batches"]:
        return None
    return 1e3 * rec["serve_s"] / rec["batches"]
