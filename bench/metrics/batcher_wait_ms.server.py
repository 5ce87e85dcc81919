"""Mean wait of a request in the fleet's batcher, from its arrival to its
batch's release on the service clock: the program's
``fleet_queue_wait_s`` histogram over the window.  Open-loop cells."""


def read(rec):
    hists = (rec.get("counters") or {}).get("histograms", {})
    h = [v for k, v in hists.items()
         if k.split("{")[0] == "fleet_queue_wait_s"]
    n = sum(v["count"] for v in h)
    return 1e3 * sum(v["sum"] for v in h) / n if n else None
