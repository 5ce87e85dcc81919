"""Find the highest open-loop rate a configuration sustains on the chip.

    python3 bench/sweep.py --workload resnet18-isaac.server --rates 30 40 50

One process builds the cell's fleet once, warms every bucket, then offers
each rate for ``--seconds`` through the cell's own open-loop mix with its
rate replaced.  Per rate it prints p50 and p95 latency, and
the p50 of the first and of the last quarter of arrivals: a backlog
that grows through the window shows as a last quarter far above the
first.  A server cell's rate is fixed from this once; the benchmark's
runs never search for one.
"""
import argparse
import json
import statistics
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="an open-loop cell")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    _, cfg, mix, _, _ = run.load_cell(args.workload)
    plan_cache = run.open_caches()
    run.find_devices(1, require_tpu=True)
    fleet, _ = run.build_fleet(cfg, args.seed, plan_cache)
    net = run.load_net(cfg)
    pool = run.traffic.images(mix, args.seed, net["input_shape"])
    span = run.Spans(False)
    run.warm(fleet, mix, cfg, pool, span)
    for rate in args.rates:
        mix["rate_per_s"] = rate
        t0 = time.perf_counter()
        loop = run.Loop(fleet, cfg, pool, span)
        handles = mix["process"].window(loop, mix, args.seed, args.seconds)
        lat = [(h.done - h.due) * 1e3 for h in handles]
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "p50_ms": statistics.median(lat),
            "p95_ms": statistics.quantiles(lat, n=100, method="inclusive")[94],
            "p50_first_quarter_ms": statistics.median(lat[:q]),
            "p50_last_quarter_ms": statistics.median(lat[-q:]),
            "dispatches": len({h.start for h in handles}),
            "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
