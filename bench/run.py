"""Run one benchmark cell once, on the chip, and print one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``: network, input size,
CIM chip preset, crossbar parameters, bucket ladder) and a traffic mix
(``bench/traffic/<traffic>.json``, parameters of the arrival process
``bench/traffic/<kind>.py``; see ``traffic.py``).  Every metric, end to
end or per layer, is read by ``bench/metrics/<metric>.py``, one reader
each.  Nothing here names a cell, a kind or a metric: a new
configuration, mix, arrival process or metric is a new file plus an
entry in ``BENCHMARK.json``.

One run: find the chips (exit 2 with no result where JAX finds no TPU,
or fewer chips than the cell asks for); build the ``CimFleet`` with the
seeded weights; warm every bucket shape the mix uses; measure for
``--seconds``; check a seeded sample of the served outputs against the
plain int8 reference (``reference.py``), bit for bit; print.  With
``--trace 1`` the window (at most ``TRACE_SECONDS``) runs under the JAX
profiler, with the program's own spans on the profiler's clock and a
fresh registry of its counters, and the result carries the per-layer
metrics instead of the end-to-end ones.  With ``--trace 0`` neither the
profiler nor the program's sinks are on.

JAX's compile cache is kept in ``bench/.cache/jax`` and the CIM plan
cache in ``bench/.cache/plans``, inside the checkout, so that only a
cell's first run in a checkout compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is timed from process start

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
TENANT = "net"
#: a traced run measures this many seconds at most: a longer trace would
#: overflow the profiler's buffers and take minutes to read
TRACE_SECONDS = 5.0
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- finding things by name ------------------------------------------------------

def _module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_net(cfg: dict) -> dict:
    return _module(BENCH / "nets" / f"{cfg['network']}.py").build(**cfg["sizes"])


def load_cell(name: str, root: Path = ROOT):
    """(cell, configuration, mix, end-to-end metrics, per-layer metrics)
    of one workload of ``BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    mix = traffic.load(root / "bench" / "traffic" / f"{cell['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]
    return cell, cfg, mix, mine(spec["end_to_end"]), mine(spec["per_layer"])


# -- host spans and compile counting ---------------------------------------------

class Spans:
    """The harness's spans around its calls into the program: profiler
    annotations (``bench.<name>``) while tracing, nothing otherwise.
    They never overlap one another; ``bench.window`` holds them all."""

    def __init__(self, traced: bool):
        self.traced = traced

    def __call__(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")


@contextlib.contextmanager
def program_sinks():
    """The program's own spans (``cim.*``) on the profiler's host plane
    and a fresh registry of its counters, for the traced window only;
    yields the registry."""
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    reg = obs_metrics.enable()
    obs_trace.use_profiler(True)
    try:
        yield reg
    finally:
        obs_trace.use_profiler(False)
        obs_metrics.disable()


def reduce_trace(trace_dir: str):
    """(device numbers, program spans) of the traced window, each
    ``None`` where the trace holds nothing for it; removes the trace."""
    reduced = spans = None
    path = trace_reduce.find_xplane(trace_dir)
    if path:
        reduced = trace_reduce.reduce(*trace_reduce.load(path))
        spans = span_reduce.reduce(*span_reduce.load(path))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return reduced, spans


class CompileCounter:
    """Counts JAX's trace, lowering and compile events while armed.
    One listener per process serves the newest counter."""
    current = None

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        if CompileCounter.current is None:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._on)
        CompileCounter.current = self

    @staticmethod
    def _on(event: str, _secs: float, **_kw) -> None:
        c = CompileCounter.current
        if c.armed and event.startswith("/jax/core/compile/"):
            c.count += 1


# -- the run -----------------------------------------------------------------------

def find_devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX found {len(devices)} "
                     f"{devices[0].platform} device(s)")
    return devices


def build_fleet(cfg: dict, seed: int, plan_cache):
    """The served program, as a user builds it.  Returns the fleet and
    the set-up timings the program's own telemetry recorded."""
    from repro.core.abstraction import get_arch
    from repro.obs import metrics as obs_metrics
    from repro.serving import CimFleet, TenantSpec
    from repro.workloads import get_workload

    graph = get_workload(cfg["network"], **cfg["sizes"])
    reg = obs_metrics.enable()
    try:
        fleet = CimFleet([TenantSpec(TENANT, graph)], get_arch(cfg["arch"]),
                         cache=plan_cache, seed=seed,
                         buckets=tuple(cfg["buckets"]),
                         max_wait_s=cfg["max_wait_s"])
    finally:
        obs_metrics.disable()
    svc = fleet.pool[TENANT]
    params = {k: getattr(svc.params, k) for k in cfg["cim"]}
    if params != cfg["cim"]:
        raise RuntimeError(f"the program runs crossbar parameters {params}, "
                           f"the configuration states {cfg['cim']}")
    hists = reg.snapshot()["histograms"]
    sums: dict = {}
    for series, h in hists.items():
        key = series.split("{")[0]
        sums[key] = sums.get(key, 0.0) + h["sum"]
    return fleet, sums


class Request:
    """What the harness knows of one request beside the program's."""
    __slots__ = ("req", "image", "due", "start", "done", "bucket")

    def __init__(self, req, image, due):
        self.req, self.image, self.due = req, image, due
        self.start = self.done = self.bucket = None


def _new(rid, pool, idx, due):
    from repro.serving import CimRequest
    req = CimRequest(rid=rid, model=TENANT,
                     inputs={"input": pool[idx].astype(np.int32)})
    return Request(req, int(idx), due)


def warm(fleet, mix, cfg, pool, span):
    """One dispatch of every bucket shape the mix uses."""
    rid = 0
    for b in mix["process"].buckets(mix, cfg["buckets"]):
        for _ in range(b):
            fleet.submit_request(_new(-1 - rid, pool, rid % len(pool),
                                      0.0).req, now=0.0)
            rid += 1
        with span("warm"):
            done = fleet.step(now=0.0, force=True)
        if len(done) != b:
            raise RuntimeError(f"warm-up dispatched {len(done)} of {b}")


class Loop:
    """What an arrival process (``bench/traffic/<kind>.py``) drives in the
    window: the fleet, the image pool and the harness's spans, on a clock
    that starts with the window.  It stamps each request's dispatch
    start, completion and bucket, and keeps each dispatch's interval and
    the sleeps' overshoot, to tell a stall in the program from one of
    the host."""

    def __init__(self, fleet, cfg, pool, span):
        self.fleet, self.pool, self.span = fleet, pool, span
        self.buckets = sorted(cfg["buckets"])
        self.max_wait_s = cfg["max_wait_s"]
        self.inflight = {}
        self.dispatches = []        # (start, end) of each dispatching step
        self.overshoot_s = 0.0      # the longest a sleep woke late
        self.t0 = time.perf_counter()

    def clock(self) -> float:
        return time.perf_counter() - self.t0

    def submit(self, rid, image, due) -> Request:
        """Queue a request for pool image ``image``, due at ``due``."""
        h = _new(rid, self.pool, image, due)
        self.fleet.submit_request(h.req, now=due)
        self.inflight[rid] = h
        return h

    def step(self, force=False) -> int:
        """One ``CimFleet.step``; returns how many requests it served."""
        start = self.clock()
        with self.span("step"):
            done = self.fleet.step(now=start, force=force)
        if not done:
            return 0
        end = self.clock()
        self.dispatches.append((start, end))
        bucket = next(b for b in self.buckets if b >= len(done))
        for r in done:
            h = self.inflight.pop(r.rid)
            h.start, h.done, h.bucket = start, end, bucket
        return len(done)

    def sleep_until(self, t: float) -> None:
        with self.span("idle"):
            time.sleep(max(0.0, t - self.clock()))
        self.overshoot_s = max(self.overshoot_s, self.clock() - t)

    def report(self) -> str:
        spans = [(e - s, s) for s, e in self.dispatches] or [(0.0, 0.0)]
        gaps = [(b[0] - a[1], a[1]) for a, b in zip(self.dispatches,
                                                    self.dispatches[1:])]
        gaps = gaps or [(0.0, 0.0)]
        steps = sorted(d for d, _ in spans)
        (step_max, step_at), (gap_max, gap_at) = max(spans), max(gaps)
        return (f"dispatch median {1e3 * steps[len(steps) // 2]:.3f} ms, "
                f"mean {1e3 * sum(steps) / len(steps):.3f} ms, longest "
                f"{1e3 * step_max:.3f} ms at {step_at:.3f} s; gaps between "
                f"dispatches mean "
                f"{1e3 * sum(d for d, _ in gaps) / len(gaps):.3f} ms, longest "
                f"{1e3 * gap_max:.3f} ms at {gap_at:.3f} s; longest sleep "
                f"overshoot {1e3 * self.overshoot_s:.3f} ms")


class HostWatch:
    """What the machine did while the window ran: the share of its CPU
    time stolen by the hypervisor and the share busy (``/proc/stat``),
    this process's involuntary context switches, and the garbage
    collector's pauses."""

    def __init__(self):
        self.gc_s = []
        self._gc_t0 = None
        gc.callbacks.append(self._on_gc)
        self.start = self._sample()

    def _on_gc(self, phase, _info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s.append(time.perf_counter() - self._gc_t0)
            self._gc_t0 = None

    @staticmethod
    def _sample():
        try:
            with open("/proc/stat") as f:
                cpu = [int(x) for x in f.readline().split()[1:]]
        except OSError:
            cpu = []
        return cpu, resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw

    def stop(self) -> str:
        gc.callbacks.remove(self._on_gc)
        (c0, n0), (c1, n1) = self.start, self._sample()
        d = [b - a for a, b in zip(c0, c1)]
        total = sum(d[:8]) or 1
        # user nice system idle iowait irq softirq steal
        steal = 100 * d[7] / total if len(d) >= 8 else float("nan")
        busy = 100 * (total - d[3] - d[4]) / total if len(d) >= 8 \
            else float("nan")
        return (f"machine CPU busy {busy:.2f}%, stolen {steal:.3f}%; load "
                f"{os.getloadavg()[0]:.2f}; involuntary context switches "
                f"{n1 - n0}; gc pauses {len(self.gc_s)} totalling "
                f"{1e3 * sum(self.gc_s):.3f} ms, longest "
                f"{1e3 * max(self.gc_s, default=0.0):.3f} ms")


def check(net, cfg, seed, sample, operand_bits=8):
    """Compare the sampled requests' served outputs with the reference;
    returns {name: (value, limit)}.  ``operand_bits`` below 8 puts the
    lower-precision reference in the program's place (the control)."""
    cim = cfg["cim"]
    weights = reference.make_weights(net, seed)
    _, shifts = reference.forward(net, weights,
                                  reference.calibration_input(net, seed), cim)
    worst, mismatched = 0, 0
    for h, image in sample:
        ref, _ = reference.forward(net, weights, image, cim, shifts=shifts)
        if operand_bits < 8:
            got, _ = reference.forward(net, weights, image, cim, shifts=shifts,
                                       operand_bits=operand_bits)
        else:
            got = (h.req.outputs or {}).get(net["output"])
            got = None if got is None else np.asarray(got, np.int64)
        if got is None or got.shape != ref.shape:
            worst, mismatched = max(worst, 1 << 31), mismatched + 1
            continue
        d = int(np.abs(got - ref).max())
        worst = max(worst, d)
        mismatched += int(d > 0)
    return {"max_abs_diff": (worst, 0), "mismatched_requests": (mismatched, 0)}


def pick_sample(handles, n, seed):
    """A seeded sample of the served requests: at least one from a
    padded partial bucket where there are any, and the last served."""
    rng = np.random.default_rng([seed, 4])
    idx = set(rng.choice(len(handles), min(n, len(handles)),
                         replace=False).tolist())
    per_dispatch = collections.Counter(h.start for h in handles)
    partial = [i for i, h in enumerate(handles)
               if h.bucket > per_dispatch[h.start]]
    if partial and not idx & set(partial):
        idx.add(int(rng.choice(partial)))
    idx.add(max(range(len(handles)), key=lambda i: handles[i].done))
    return [handles[i] for i in sorted(idx)]


def run_cell(cfg: dict, mix: dict, e2e: list, per_layer: list, *, seed: int,
             seconds: float, trace: bool, chips: int = 1,
             require_tpu: bool = True, plan_cache=None,
             control_bits: int = 8) -> dict:
    """One run of a cell; returns the result line's object.  Progress
    and the compared numbers go to standard error.  ``control_bits``
    below 8 compares the reference at that precision in place of the
    served outputs: the control, which must come out not correct."""
    devices = find_devices(chips, require_tpu)
    dev = devices[0]
    peaks = work.peaks(dev.device_kind) if require_tpu else None
    sys.path.insert(0, str(ROOT / "src"))
    net = load_net(cfg)
    counter = CompileCounter()
    span = Spans(trace)

    fleet, hist = build_fleet(cfg, seed, plan_cache)
    pool = traffic.images(mix, seed, net["input_shape"])
    t_warm = time.perf_counter()
    warm(fleet, mix, cfg, pool, span)
    jit_warm_s = time.perf_counter() - t_warm
    svc = fleet.pool[TENANT]
    batches0, serve0 = svc.stats.batches, svc.stats.serve_s

    # set-up's objects go to the collector's permanent generation, so
    # that no full collection walks them inside the window
    gc.collect()
    gc.freeze()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        import jax
        seconds = min(seconds, TRACE_SECONDS)
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - T_PROCESS
    host = HostWatch()
    counter.armed = True
    with program_sinks() if trace else contextlib.nullcontext() \
            as registry, span("window"):
        loop = Loop(fleet, cfg, pool, span)
        handles = mix["process"].window(loop, mix, seed, seconds)
    counter.armed = False
    host_window_s = loop.clock()
    window_s = max(h.done for h in handles)
    if trace:
        import jax
        jax.profiler.stop_trace()
    host_line = host.stop()
    gc.unfreeze()
    batches = svc.stats.batches - batches0
    serve_s = svc.stats.serve_s - serve0
    peak_bytes = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    stats = svc.executor_stats
    print(f"setup: total {setup_s:.6f} s; compile_wall_s {hist.get('compile_wall_s', 0):.6f}"
          f"; executor_lower_s {hist.get('executor_lower_s', 0):.6f}"
          f"; executor_pack_s {hist.get('executor_pack_s', 0):.6f}"
          f"; warm {jit_warm_s:.6f} (calibration is not instrumented)",
          file=sys.stderr)
    print(f"window: {len(handles)} requests in {batches} dispatches over "
          f"{window_s:.6f} s (host loop {host_window_s:.6f} s); "
          f"compiles inside the window: {counter.count}; route "
          f"{stats.kernel_mode} segments {stats.segments} swaps {stats.swaps}"
          f" kernel dispatches {stats.dispatches}", file=sys.stderr)
    print(f"host in the window: {loop.report()}; {host_line}",
          file=sys.stderr)

    reduced, spans = reduce_trace(trace_dir) if trace else (None, None)

    # the served outputs leave with the sample; the program's state goes
    sample = pick_sample(handles, cfg["check_requests"], seed)
    sample = [(h, pool[h.image].astype(np.int64)) for h in sample]
    unserved = sum(1 for h in handles if h.done is None
                   or h.req.outputs is None)
    lat_ms = [(h.done - h.due) * 1e3 for h in handles]
    wait_ms = [(h.start - h.due) * 1e3 for h in handles]
    from repro.core import compiler
    sim = compiler.compile_graph(svc.graph, fleet.plan.subarch(TENANT),
                                 cache=plan_cache).metrics()
    del fleet, svc, handles
    gc.collect()
    print("simulated (compiler cycles, crossbar activations): latency_cycles "
          f"{sim['latency_cycles']} energy_units {sim['energy_units']} "
          f"peak_power {sim['peak_power']} segments {sim['n_segments']}",
          file=sys.stderr)

    t_ref = time.perf_counter()
    checks = check(net, cfg, seed, sample, control_bits)
    checks["unserved_requests"] = (unserved, 0)
    ref_s = time.perf_counter() - t_ref
    correct = all(v <= lim for v, lim in checks.values())

    record = {
        "window_s": window_s, "completed": len(lat_ms),
        "latencies_ms": lat_ms, "queue_wait_ms": wait_ms,
        "batches": batches, "serve_s": serve_s, "hist": hist,
        "jit_warm_s": jit_warm_s, "setup_s": setup_s,
        "net": net, "peaks": peaks, "trace": reduced, "spans": spans,
        "counters": registry.snapshot() if registry is not None else None,
    }
    metrics = {}
    for m in (per_layer if trace else e2e):
        value = read_metric(m["name"], record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": correct, "attempted": len(lat_ms),
           "failed": unserved, "metrics": metrics, "device": device}
    if trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = dict(reduced["breakdown"])
        if spans:
            out["breakdown"].update(spans["breakdown"])
    print(f"reference check: {len(sample)} requests in {ref_s:.3f} s",
          file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def read_metric(name: str, record: dict):
    """The metric's value as ``bench/metrics/<name>.py`` reads it from
    the run's record, or ``None`` where it finds nothing to read."""
    return _module(BENCH / "metrics" / f"{name}.py").read(record)


def open_caches():
    """Keep JAX's compile cache and the CIM plan cache inside the
    checkout, at fixed paths; returns the plan cache.  The variable
    hands the same directory to the program's own
    ``backend.enable_compile_cache``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.dse import CompileCache
    return CompileCache(root=CACHE / "plans")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, cfg, mix, e2e, per_layer = load_cell(args.workload)

    plan_cache = open_caches()
    try:
        out = run_cell(cfg, mix, e2e, per_layer, seed=args.seed % (1 << 63),
                       seconds=args.seconds, trace=bool(args.trace),
                       chips=cell["chips"], plan_cache=plan_cache)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
