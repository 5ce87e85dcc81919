"""Share of the bucket rows dispatched in the window that carried a
request, the rest being padding: the program's ``fleet_requests_total``
over its ``fleet_bucket_rows_total``.  Open-loop cells."""


def _sum(counters, name):
    return sum(v for k, v in counters.items() if k.split("{")[0] == name)


def read(rec):
    c = (rec.get("counters") or {}).get("counters", {})
    rows = _sum(c, "fleet_bucket_rows_total")
    return 100.0 * _sum(c, "fleet_requests_total") / rows if rows else None
