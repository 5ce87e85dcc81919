"""MLPerf Inference's Offline scenario: the queue is topped up to
``depth`` requests before every dispatch, so every dispatch is a full
largest bucket.  Only that bucket's shape is used.  Counts the requests
completed in the window."""
import traffic


def buckets(mix, ladder):
    return [max(ladder)]


def window(loop, mix, seed, seconds):
    order = traffic.image_order(mix, seed, 1 << 16)
    handles = []
    while loop.clock() < seconds:
        with loop.span("admit"):
            now = loop.clock()
            while loop.fleet.pending < mix["depth"]:
                rid = len(handles)
                handles.append(loop.submit(rid, order[rid % len(order)], now))
        loop.step()
    return [h for h in handles if h.done is not None]
