"""Stack-wide telemetry: registry determinism, zero-overhead-disabled
semantics, compile provenance coverage, and the merged Perfetto
timeline (compiler + executor + DSE + fleet + fault events)."""
import dataclasses
import json
import re

import numpy as np
import pytest

from repro.cimsim import executor
from repro.cimsim.faults import FaultModel, fault_aware_compile
from repro.cimsim.functional import make_input, make_weights
from repro.core import compiler
from repro.core.abstraction import get_arch
from repro.dse import CompileCache, DesignSpace, adaptive_search
from repro.dse.report import search_scorecard
from repro.obs import MetricsRegistry, hooks, metrics, trace
from repro.obs.explain import explain_compile
from repro.obs.trace import (TraceRecorder, load_trace,
                             validate_chrome_trace)
from repro.serving import CimFleet, CimRequest, TenantSpec
from repro.workloads import get_workload

TOY = get_arch("toy")
ISAAC = get_arch("isaac-baseline")
MLP = get_workload("tiny_mlp")


@pytest.fixture
def telemetry():
    """Enable the registry + process-wide trace; always torn down."""
    reg = metrics.enable()
    tr = trace.install()
    try:
        yield reg, tr
    finally:
        metrics.disable()
        trace.uninstall()


def _compile_run(batch=2, seed=0):
    res = compiler.compile_graph(MLP, TOY)
    exe = executor.lower(res.plan, res.program)
    w = make_weights(MLP, seed)
    singles = [make_input(MLP, seed + i) for i in range(batch)]
    x = {t: np.stack([s[t] for s in singles]) for t in singles[0]}
    return exe.run_batch(x, w)


# ------------------------------------------------------------- registry

def test_registry_instruments_and_deterministic_snapshots():
    def feed(reg):
        reg.counter("requests_total", route="xla").inc()
        reg.counter("requests_total", route="xla").inc(2)
        reg.counter("requests_total", route="pallas").inc()
        reg.gauge("pool_bytes", chip="c0").set(512)
        for v in (0.002, 0.04, 3.0):
            reg.histogram("dispatch_s").observe(v)
        return reg
    a, b = feed(MetricsRegistry()), feed(MetricsRegistry())
    assert a.to_json() == b.to_json()       # byte-identical exposition
    snap = a.snapshot()
    assert snap["counters"]['requests_total{route="xla"}'] == 3
    assert snap["counters"]['requests_total{route="pallas"}'] == 1
    assert snap["gauges"]['pool_bytes{chip="c0"}'] == 512
    h = snap["histograms"]["dispatch_s"]
    assert h["count"] == 3 and h["buckets"]["+Inf"] == 3
    assert h["buckets"]["0.01"] == 1        # cumulative le-buckets
    assert h["buckets"]["0.1"] == 2
    with pytest.raises(ValueError, match="cannot decrease"):
        a.counter("requests_total", route="xla").inc(-1)
    assert len(a) == 4                      # 3 counter/gauge series + 1 hist


def test_registry_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("compiles_total", cached=False).inc(2)
    reg.gauge("depth").set(1.5)
    reg.histogram("lat_s", bounds=(0.1, 1.0)).observe(0.05)
    text = reg.to_prometheus()
    assert "# TYPE compiles_total counter" in text
    assert 'compiles_total{cached="False"} 2' in text
    assert "# TYPE depth gauge" in text and "depth 1.5" in text
    assert "# TYPE lat_s histogram" in text
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="+Inf"} 1' in text
    assert "lat_s_count 1" in text


def test_registry_absorbs_legacy_stat_bundles(tmp_path):
    reg = MetricsRegistry()
    cache = CompileCache(tmp_path / "cc")
    compiler.compile_graph(MLP, TOY, cache=cache)
    compiler.compile_graph(MLP, TOY, cache=cache)
    reg.absorb("compile_cache", cache.stats(), owner="me")
    flat = reg.flat("compile_cache_")
    assert flat['compile_cache_hits{owner="me"}'] == 1
    assert flat['compile_cache_misses{owner="me"}'] == 1
    # executor stats: numeric + bool fields surface, strings are skipped
    res = compiler.compile_graph(MLP, TOY)
    exe = executor.lower(res.plan, res.program)
    reg.absorb("executor", dataclasses.asdict(exe.stats))
    flat = reg.flat("executor_")
    assert flat["executor_cim_nodes"] == 2
    assert flat["executor_streamed"] in (0.0, 1.0)
    assert "executor_kernel_mode" not in flat


def test_flat_prefix_filter():
    reg = MetricsRegistry()
    reg.counter("dse_rounds_total").inc()
    reg.counter("compile_cache_hits_total").inc()
    reg.counter("other_total").inc()
    both = reg.flat(prefix=("compile_cache_", "dse_"))
    assert set(both) == {"compile_cache_hits_total", "dse_rounds_total"}


# ---------------------------------------------- disabled-by-default

def test_disabled_by_default_bitexact_and_zero_events():
    assert metrics.active() is None and trace.get_trace() is None
    executor.clear_lower_cache()
    base = _compile_run()

    reg = metrics.enable()
    tr = trace.install()
    try:
        executor.clear_lower_cache()
        on = _compile_run()
        assert len(reg) > 0 and len(tr) > 0
        n_events = len(tr.events)
        snap = reg.to_json()
    finally:
        metrics.disable()
        trace.uninstall()

    executor.clear_lower_cache()
    off = _compile_run()
    for t in base:
        np.testing.assert_array_equal(base[t], on[t])
        np.testing.assert_array_equal(base[t], off[t])
    # disabled runs add zero events and zero counters to the old sinks
    assert len(tr.events) == n_events
    assert reg.to_json() == snap


# ------------------------------------------------------ one timeline

def test_unified_timeline_roundtrip(tmp_path, telemetry):
    reg, tr = telemetry
    executor.clear_lower_cache()

    # compiler + executor + fault events on the reserved tracks
    _compile_run()
    fault_aware_compile(MLP, TOY, FaultModel(seed=0, stuck_cell_rate=0.02))

    # a DSE rung batch on the dse track
    space = DesignSpace(TOY, arch_axes={"xb.xb_size": [(32, 128),
                                                       (64, 128)]})
    adaptive_search(MLP, space, cache=CompileCache(tmp_path / "cc"),
                    seed=3, batch=2)

    # serving events merge in by handing the fleet the same recorder
    fleet = CimFleet([TenantSpec("mlp", MLP, traffic=1.0)],
                     ISAAC.subarch(8, "isaac-8c"), max_wait_s=0.0,
                     trace=tr)
    reqs = [CimRequest(rid=i, model="mlp", inputs=make_input(MLP, i))
            for i in range(3)]
    assert len(fleet.serve(reqs, now=0.0)) == 3

    validate_chrome_trace(tr.to_dict())
    labels = {ev["args"]["name"]: ev["pid"] for ev in tr.events
              if ev["ph"] == "M" and ev["name"] == "process_name"}
    # distinct Perfetto process rows per tier, plus the serving chip row
    assert {"compiler", "executor", "dse", "chip:isaac-8c"} <= set(labels)
    assert len(set(labels.values())) == len(labels)
    by_pid = {}
    for ev in tr.events:
        if ev["ph"] != "M":
            by_pid.setdefault(ev["pid"], set()).add(ev.get("cat"))
    assert "compile" in by_pid[labels["compiler"]]
    assert "faults" in by_pid[labels["compiler"]]
    assert "executor" in by_pid[labels["executor"]]
    assert "dse" in by_pid[labels["dse"]]
    assert "engine" in by_pid[labels["chip:isaac-8c"]]
    # tenants get their own tids under each track
    exec_tids = {ev["tid"] for ev in tr.events
                 if ev["pid"] == labels["executor"] and ev["ph"] != "M"}
    assert exec_tids and 0 not in exec_tids

    # the compile→dispatch flow arrow shares one id across tracks
    flows = [ev for ev in tr.events if ev["ph"] in ("s", "f")]
    ids = {}
    for ev in flows:
        ids.setdefault(ev["id"], set()).add(ev["ph"])
    assert any(phases == {"s", "f"} for phases in ids.values())

    path = tr.save(tmp_path / "timeline.json")
    loaded = load_trace(path)               # validates on load
    assert loaded["traceEvents"] == tr.to_dict()["traceEvents"]
    # registry saw every tier too
    flat = reg.flat()
    assert any(k.startswith("compiles_total") for k in flat)
    assert any(k.startswith("executor_dispatches_total") for k in flat)
    assert any(k.startswith("dse_jobs_total") for k in flat)
    assert any(k.startswith("fault_compile_attempts_total") for k in flat)


def test_trace_save_is_atomic(tmp_path):
    tr = TraceRecorder()
    tr.complete("compiler", "g", "compile:g", "compile", 0.0, 0.1)
    p = tr.save(tmp_path / "t.json")
    first = p.read_text()
    tr.complete("compiler", "g", "compile:g", "compile", 0.2, 0.1)
    tr.save(p)                              # overwrite in place
    assert p.read_text() != first
    load_trace(p)
    leftovers = [q for q in p.parent.iterdir() if q.suffix == ".tmp"]
    assert leftovers == []                  # temp file renamed, not leaked


def test_validate_counter_and_flow_shapes():
    def ev(**kw):
        base = {"name": "x", "ts": 0, "pid": 1, "tid": 0}
        base.update(kw)
        return {"traceEvents": [
            {"name": "process_name", "ph": "M", "ts": 0, "pid": 1,
             "tid": 0, "args": {"name": "p"}}, base]}
    with pytest.raises(ValueError, match="counter event needs args"):
        validate_chrome_trace(ev(ph="C", args={}))
    with pytest.raises(ValueError, match="must be a number"):
        validate_chrome_trace(ev(ph="C", args={"depth": "high"}))
    with pytest.raises(ValueError, match="must be a number"):
        validate_chrome_trace(ev(ph="C", args={"up": True}))
    validate_chrome_trace(ev(ph="C", args={"depth": 3}))
    with pytest.raises(ValueError, match="needs an 'id'"):
        validate_chrome_trace(ev(ph="s", args={}))
    validate_chrome_trace(ev(ph="f", **{"id": 7, "bp": "e"}))
    tr = TraceRecorder()
    with pytest.raises(ValueError, match="flow phase"):
        tr.flow("X", "c", "t", "n", "cat", 0.0, 1)


def test_serving_shim_reexports_obs_trace():
    import repro.serving.trace as shim
    from repro.obs import trace as obs_trace
    assert shim.TraceRecorder is obs_trace.TraceRecorder
    assert shim.validate_chrome_trace is obs_trace.validate_chrome_trace


# ------------------------------------------------------------ explain

def test_explain_covers_every_resnet18_node():
    report = explain_compile(get_workload("resnet18"), ISAAC)
    assert report.coverage == 1.0           # acceptance bar: 100 %
    assert len(report.rows) == report.meta["nodes"]
    for row in report.rows:
        assert set(report.columns) <= set(row)
    cim = [r for r in report.rows if r["tier"] != "digital"]
    assert len(cim) == report.meta["cim_nodes"]
    assert all(r["xbs"] > 0 and r["grid"] != "-" for r in cim)
    assert report.meta["cache_hit"] is False
    assert report.meta["compile_wall_s"] > 0
    assert report.meta["key"]
    md = report.to_markdown()
    assert "|node" in md and "conv1" in md
    parsed = json.loads(report.to_json())
    assert parsed["meta"]["workload"] == "resnet18"


def test_explain_fault_provenance_and_cache_hit(tmp_path):
    fm = FaultModel(seed=0, stuck_cell_rate=0.02)
    report = explain_compile(MLP, TOY, fault_model=fm)
    assert report.meta["fault_retire_attempts"] >= 1
    assert report.coverage == 1.0
    cache = CompileCache(tmp_path / "cc")
    explain_compile(MLP, TOY, cache=cache)
    again = explain_compile(MLP, TOY, cache=cache)
    assert again.meta["cache_hit"] is True


def test_hooks_capture_compile_provenance_events():
    seen = []
    unsub = hooks.subscribe(lambda kind, payload: seen.append(kind))
    try:
        assert hooks.subscribed()
        compiler.compile_graph(MLP, TOY)
    finally:
        unsub()
    kinds = set(seen)
    assert {"mapping.bind", "mapping.place", "cg.plan",
            "compile.done"} <= kinds
    n = len(seen)
    compiler.compile_graph(MLP, TOY)        # after unsubscribe: silence
    assert len(seen) == n and not hooks.subscribed()


# ------------------------------------------------- satellite counters

def test_cache_and_dse_counters_reach_scorecards(tmp_path, telemetry):
    reg, _ = telemetry
    cache = CompileCache(tmp_path / "cc")
    compiler.compile_graph(MLP, TOY, cache=cache)
    compiler.compile_graph(MLP, TOY, cache=cache)
    flat = reg.flat()
    assert flat['compile_cache_hits_total{layer="memory"}'] == 1
    assert flat["compile_cache_misses_total"] == 1

    space = DesignSpace(TOY, arch_axes={"xb.xb_size": [(32, 128),
                                                       (64, 128)]})
    result = adaptive_search(MLP, space,
                             cache=CompileCache(tmp_path / "dse"),
                             seed=1, batch=2)
    flat = reg.flat()
    assert flat['dse_ask_rounds_total{workload="tiny_mlp"}'] \
        == result.ask_rounds
    assert flat['dse_promotions_total{workload="tiny_mlp"}'] >= 1
    card = search_scorecard(result, "tiny_mlp")
    obs_keys = [k for k in card.meta if k.startswith("obs_")]
    assert any("dse_ask_rounds_total" in k for k in obs_keys)
    assert any("compile_cache_" in k for k in obs_keys)
    metrics.disable()
    clean = search_scorecard(result, "tiny_mlp")
    assert not any(k.startswith("obs_") for k in clean.meta)


# ------------------------------------------------------ spans, two sinks

CNN = get_workload("tiny_cnn")


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each span's
    name and args, and its open/close order."""

    def __init__(self):
        self.log = []
        outer = self

        class Annotation:
            def __init__(self, name, **args):
                self.name, self.args = name, args
                outer.log.append(("new", name, args))

            def __enter__(self):
                outer.log.append(("enter", self.name))
                return self

            def __exit__(self, *exc):
                outer.log.append(("exit", self.name))
                return False

        self.cls = Annotation

    @property
    def names(self):
        return [e[1] for e in self.log if e[0] == "new"]


@pytest.fixture
def profiler_sink(monkeypatch):
    """The profiler sink on, with the annotation class recorded; off
    again after the test."""
    import jax
    fake = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", fake.cls)
    trace.use_profiler(True)
    try:
        yield fake
    finally:
        trace.use_profiler(False)


def _serve_cnn(n=3, seed=0):
    fleet = CimFleet([TenantSpec("cnn", CNN, traffic=1.0)], ISAAC,
                     max_wait_s=0.0, seed=seed)
    reqs = [CimRequest(rid=i, model="cnn", inputs=make_input(CNN, i))
            for i in range(n)]
    return fleet, fleet.serve(reqs, now=0.0)


def test_span_off_is_the_shared_noop_and_records_nothing(monkeypatch):
    import jax
    assert not trace.spans_on()
    a = trace.span("cim.x", trace.EXECUTOR_TRACK, "g", n=1)
    b = trace.span("cim.y", trace.SERVING_TRACK, "h")
    assert a is b and not a
    with a as inner:
        assert inner is a
    # the served path opens no annotation and records no event
    fake = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", fake.cls)
    _, done = _serve_cnn()
    assert len(done) == 3 and fake.log == []
    assert trace.get_trace() is None and metrics.active() is None


def test_span_with_a_recorder_writes_the_event_complete_wrote():
    tr = trace.install()
    try:
        with trace.span("cim.executor.pack", trace.EXECUTOR_TRACK, "g",
                        event="pack:g", cat="executor", segments=1) as sp:
            sp.args["bytes"] = 64
    finally:
        trace.uninstall()
    assert sp and sp.dur_s >= 0.0
    old = TraceRecorder()
    old.complete(trace.EXECUTOR_TRACK, "g", "pack:g", "executor",
                 sp.ts_s, sp.dur_s, bytes=64, segments=1)
    assert tr.events == old.events
    validate_chrome_trace(tr.to_dict())
    assert not trace.spans_on()


def test_spans_nest_on_both_sinks(profiler_sink):
    tr = trace.install()
    try:
        with trace.span("cim.outer", trace.SERVING_TRACK, "c", tenant="t"):
            with trace.span("cim.inner", trace.SERVING_TRACK, "c"):
                pass
    finally:
        trace.uninstall()
    assert profiler_sink.log == [
        ("new", "cim.outer", {"tenant": "t"}), ("enter", "cim.outer"),
        ("new", "cim.inner", {}), ("enter", "cim.inner"),
        ("exit", "cim.inner"), ("exit", "cim.outer")]
    spans = {ev["name"]: ev for ev in tr.events if ev["ph"] == "X"}
    outer, inner = spans["cim.outer"], spans["cim.inner"]
    assert outer["args"] == {"tenant": "t"} and outer["cat"] == "serving"
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_span_whose_body_raises_records_no_event(profiler_sink):
    tr = trace.install()
    try:
        with trace.span("cim.outer", trace.EXECUTOR_TRACK, "g"):
            with pytest.raises(ValueError):
                with trace.span("cim.inner", trace.EXECUTOR_TRACK, "g"):
                    raise ValueError("transient")
    finally:
        trace.uninstall()
    assert [ev["name"] for ev in tr.events if ev["ph"] == "X"] == [
        "cim.outer"]
    assert profiler_sink.log[-2:] == [("exit", "cim.inner"),
                                      ("exit", "cim.outer")]


def test_served_path_spans_and_counters(profiler_sink):
    executor.clear_lower_cache()
    reg = metrics.enable()
    try:
        fleet, done = _serve_cnn(n=3)
    finally:
        metrics.disable()
    assert len(done) == 3
    names = profiler_sink.names
    assert names.count("cim.service.calibrate") == 1
    # 3 requests drain as one padded bucket of 4: warm, then timed
    for name in ("cim.service.stack", "cim.executor.put",
                 "cim.executor.run", "cim.executor.fetch",
                 "cim.executor.dispatch"):
        assert names.count(name) == 2, name
    disp = [e for e in profiler_sink.log
            if e[0] == "new" and e[1] == "cim.fleet.dispatch"]
    assert [e[2] for e in disp] == [{"tenant": "cnn", "bucket": 4, "n": 3,
                                     "reason": "age", "rid0": 0,
                                     "rid1": 2}]
    assert "cim.compile" in names and "cim.executor.lower" in names
    snap = reg.snapshot()
    assert snap["counters"]['fleet_requests_total{tenant="cnn"}'] == 3
    assert snap["counters"]['fleet_bucket_rows_total{tenant="cnn"}'] == 4
    wait = snap["histograms"]['fleet_queue_wait_s{tenant="cnn"}']
    assert wait["count"] == 3 and wait["sum"] == 0.0
    cal = snap["histograms"]["service_calibrate_s"]
    assert cal["count"] == 1 and cal["sum"] > 0


def test_every_sink_on_is_bitexact(profiler_sink):
    executor.clear_lower_cache()
    _, off = _serve_cnn(n=3, seed=1)
    reg = metrics.enable()
    tr = trace.install()
    try:
        executor.clear_lower_cache()
        _, on = _serve_cnn(n=3, seed=1)
    finally:
        metrics.disable()
        trace.uninstall()
    assert len(tr) > 0 and len(reg) > 0 and profiler_sink.log
    for a, b in zip(off, on):
        for t in a.outputs:
            np.testing.assert_array_equal(a.outputs[t], b.outputs[t])


def test_device_ops_carry_node_and_phase_scopes():
    import jax.numpy as jnp
    res = compiler.compile_graph(CNN, ISAAC)
    exe = executor.lower(res.plan, res.program)
    packed = exe.pack(make_weights(CNN, 0))
    sh = {name: jnp.int32(0) for name in exe._shift_names}
    xs = {"input": jnp.zeros((2, 3, 8, 8), jnp.int32)}
    hlo = exe._jit.lower(packed, sh, xs).as_text(debug_info=True)
    for scope in ("conv1/im2col", "conv2/gemm", "fc/requant", "pool/"):
        assert scope in hlo, scope
    # conv patches come from static slices: no index gather
    assert not re.search(r'conv[^/"]*/im2col/[^"]*gather', hlo)
