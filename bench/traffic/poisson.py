"""MLPerf's Server scenario: an open loop at ``rate_per_s``.  Every seed
gets the same set of inter-arrival gaps (the exponential distribution's
quantiles at ``(i + 0.5) / n`` for ``n = rate x seconds`` requests), in
an order the seed shuffles, so that each run offers the same work.  The
last request is due at the window's end; requests still queued then are
served and counted, none is dropped.  Each request is timed from its
due time.  Partial batches occur, so every bucket shape is used."""
import numpy as np

import traffic


def buckets(mix, ladder):
    return sorted(ladder)


def arrivals(mix, seed, seconds):
    """Due times (seconds from the window's start)."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = np.random.default_rng([seed, 1]).permutation(gaps)
    due = np.cumsum(gaps)
    return due * (seconds / due[-1])


def window(loop, mix, seed, seconds):
    due = arrivals(mix, seed, seconds)
    order = traffic.image_order(mix, seed, len(due))
    handles, queued = [], []
    while len(handles) < len(due) or loop.fleet.pending:
        nxt = len(handles)
        if nxt < len(due) and due[nxt] <= loop.clock():
            with loop.span("admit"):
                now = loop.clock()
                while nxt < len(due) and due[nxt] <= now:
                    h = loop.submit(nxt, order[nxt], float(due[nxt]))
                    handles.append(h)
                    queued.append(h)
                    nxt += 1
        if loop.step(force=nxt == len(due)):
            queued = [h for h in queued if h.done is None]
            continue
        # nothing released: sleep to the next arrival or the batcher's age
        wake = [due[nxt]] if nxt < len(due) else []
        if queued:
            wake.append(queued[0].due + loop.max_wait_s)
        if wake:
            loop.sleep_until(min(wake))
    return handles
