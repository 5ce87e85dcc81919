"""Trace a cell's window with the program's own spans and counters on, and
print what they show.

    python3 bench/profile_spans.py --workload <cell> --seed <n> --sinks 0 1 0 1

One process builds the cell's fleet as ``run.py`` does and warms it, then
traces one window of ``run.TRACE_SECONDS`` under the JAX profiler for
each entry of ``--sinks``, each with its own seed for the traffic.
With 1, the program's profiler sink (``repro.obs.trace.use_profiler``)
and a fresh metrics registry are on for the window; with 0 they are off, so that alternating windows show
what the spans cost.  Each window's trace is reduced by
``trace_reduce.py`` and ``span_reduce.py`` into the record ``run.py``
builds, plus ``spans`` (``span_reduce.reduce``) and ``counters`` (the
registry's snapshot); then every reader of ``bench/metrics/`` that is
the cell's per-layer metric or that ``BENCHMARK.json`` does not list
reads it.  One JSON line per window: those metrics, the device idle
time by innermost span, and the check of a seeded sample of the
window's served outputs against the reference.  The benchmark's runs
never run this.
"""
import argparse
import gc
import json
import shutil
import sys
import tempfile
import time

import run
import span_reduce
import trace_reduce


def readers(per_layer):
    """The cell's per-layer metrics and the readers ``BENCHMARK.json``
    does not list."""
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    files = sorted(p.stem for p in (run.BENCH / "metrics").glob("*.py"))
    return [m["name"] for m in per_layer] + \
        [n for n in files if n not in listed]


def window(fleet, cfg, mix, pool, seed, seconds, sinks):
    """One traced window; returns (handles, reduced, spans, counters,
    dispatches, serve seconds)."""
    import jax
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    svc = fleet.pool[run.TENANT]
    batches0, serve0 = svc.stats.batches, svc.stats.serve_s
    span = run.Spans(True)
    trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
    gc.collect()
    gc.freeze()
    jax.profiler.start_trace(trace_dir)
    reg = obs_metrics.enable() if sinks else None
    obs_trace.use_profiler(bool(sinks))
    try:
        with span("window"):
            loop = run.Loop(fleet, cfg, pool, span)
            handles = mix["process"].window(loop, mix, seed, seconds)
    finally:
        obs_trace.use_profiler(False)
        obs_metrics.disable()
        jax.profiler.stop_trace()
        gc.unfreeze()
    path = trace_reduce.find_xplane(trace_dir)
    reduced = spans = None
    if path:
        reduced = trace_reduce.reduce(*trace_reduce.load(path))
        spans = span_reduce.reduce(*span_reduce.load(path))
    shutil.rmtree(trace_dir, ignore_errors=True)
    counters = reg.snapshot() if reg is not None else None
    return (handles, reduced, spans, counters,
            svc.stats.batches - batches0, svc.stats.serve_s - serve0)


def profile(cfg, mix, names, *, seed, seconds, sinks, chips=1,
            require_tpu=True, plan_cache=None):
    """Yields one result per entry of ``sinks`` (see the module's
    docstring)."""
    dev = run.find_devices(chips, require_tpu)[0]
    sys.path.insert(0, str(run.ROOT / "src"))
    net = run.load_net(cfg)
    fleet, hist = run.build_fleet(cfg, seed, plan_cache)
    pool = run.traffic.images(mix, seed, net["input_shape"])
    t_warm = time.perf_counter()
    run.warm(fleet, mix, cfg, pool, run.Spans(False))
    jit_warm_s = time.perf_counter() - t_warm
    setup_s = time.perf_counter() - run.T_PROCESS
    peaks = run.work.peaks(dev.device_kind) if require_tpu else None
    for i, on in enumerate(sinks):
        handles, reduced, spans, counters, batches, serve_s = window(
            fleet, cfg, mix, pool, seed + i, seconds, on)
        record = {
            "window_s": max(h.done for h in handles),
            "completed": len(handles),
            "latencies_ms": [(h.done - h.due) * 1e3 for h in handles],
            "queue_wait_ms": [(h.start - h.due) * 1e3 for h in handles],
            "batches": batches, "serve_s": serve_s, "hist": hist,
            "jit_warm_s": jit_warm_s, "setup_s": setup_s, "net": net,
            "peaks": peaks, "trace": reduced, "spans": spans,
            "counters": counters,
        }
        metrics = {}
        for name in names:
            value = run.read_metric(name, record)
            if value is not None:
                metrics[name] = value
        sample = run.pick_sample(handles, cfg["check_requests"], seed + i)
        checks = run.check(net, cfg, seed,
                           [(h, pool[h.image].astype("int64"))
                            for h in sample])
        yield {
            "seed": seed + i, "sinks": on, "device": dev.device_kind,
            "metrics": metrics,
            "idle_gaps": (reduced or {}).get("breakdown", {})
            .get("idle_gaps"),
            "idle_gaps_program": (spans or {}).get("breakdown", {})
            .get("idle_gaps_program"),
            "correct": all(v <= lim for v, lim in checks.values()),
            "checks": {k: v for k, (v, _) in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sinks", type=int, nargs="+", choices=(0, 1),
                    default=[1])
    args = ap.parse_args(argv)
    cell, cfg, mix, _, per_layer = run.load_cell(args.workload)
    plan_cache = run.open_caches()
    for result in profile(cfg, mix, readers(per_layer), seed=args.seed,
                          seconds=run.TRACE_SECONDS, sinks=args.sinks,
                          chips=cell["chips"], plan_cache=plan_cache):
        print(json.dumps({"workload": args.workload, **result}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
