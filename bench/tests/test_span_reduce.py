"""The program-span reduction on a hand-built trace and on a recorded one,
and the metric readers that take from it."""
import json

import jax
import pytest

import run
import span_reduce as sr
from repro.cimsim.functional import make_input
from repro.core.abstraction import get_arch
from repro.obs import trace as obs_trace
from repro.serving import CimFleet, CimRequest, TenantSpec
from repro.workloads import get_workload

# one chip, window 0..1000 ns.  Host: the harness's step holds one
# dispatch's program spans.  Device: an im2col gather 400-600, a GEMM
# 600-780 (its scope interned as a ref_value), an unscoped copy 780-790.
HOST = [("bench.window", 0, 1000), ("bench.step", 100, 900),
        ("cim.fleet.step", 110, 890),
        ("cim.fleet.dispatch#tenant=net,n=8#", 120, 880),
        ("cim.service.stack", 130, 230), ("cim.executor.dispatch", 240, 870),
        ("cim.executor.put", 250, 350), ("cim.executor.run", 360, 800),
        ("cim.executor.fetch", 810, 860), ("PjitFunction(_forward)", 365, 370)]


def _xspace():
    host_events = "\n".join(
        f"    events {{ metadata_id: {i + 1} offset_ps: {s * 1000} "
        f"duration_ps: {(e - s) * 1000} }}"
        for i, (_, s, e) in enumerate(HOST))
    host_meta = "\n".join(
        f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
        f'name: "{n}" }} }}' for i, (n, _, _) in enumerate(HOST))
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 400000 duration_ps: 200000 }}
    events {{ metadata_id: 2 offset_ps: 600000 duration_ps: 180000 }}
    events {{ metadata_id: 3 offset_ps: 780000 duration_ps: 10000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = s32[8]{{0}} fusion()"
    stats {{ metadata_id: 1 str_value: "jit(_forward)/conv1/im2col/gather:" }}
    stats {{ metadata_id: 2 str_value: "kLoop" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.2 = f32[8]{{0}} fusion()"
    stats {{ metadata_id: 1 ref_value: 7 }} }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%copy.3 = s32[8]{{0}} copy()" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "{sr.SCOPE_STAT}" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "hlo_category" }} }}
  stat_metadata {{ key: 7 value {{ id: 7
    name: "jit(_forward)/conv1/gemm/dot_general:" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{host_events} }}
{host_meta}
}}
"""


def _reduced():
    from jax.profiler import ProfileData
    data = ProfileData.text_proto_to_serialized_xspace(_xspace())
    return sr.events_of(ProfileData.from_serialized_xspace(data),
                        sr.scopes(data))


def test_scopes_read_from_event_metadata():
    from jax.profiler import ProfileData
    data = ProfileData.text_proto_to_serialized_xspace(_xspace())
    assert sr.scopes(data) == {
        "%fusion.1 = s32[8]{0} fusion()":
            "jit(_forward)/conv1/im2col/gather:",
        "%fusion.2 = f32[8]{0} fusion()":
            "jit(_forward)/conv1/gemm/dot_general:"}


def test_hand_built_trace():
    device, spans = _reduced()
    assert [s for _, _, _, s in device["/device:TPU:0"]][2] == ""
    names = [n for n, _, _ in spans]
    assert "cim.fleet.dispatch" in names          # the #k=v# suffix went
    assert "PjitFunction(_forward)" not in names
    r = sr.reduce(device, spans)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(1000 * ns)
    s = r["spans"]
    assert s["cim.executor.run"]["durations_s"] == [pytest.approx(440 * ns)]
    assert s["cim.executor.dispatch"]["count"] == 1
    # self time: the span less the spans directly inside it
    assert s["cim.executor.dispatch"]["self_s"] == pytest.approx(40 * ns)
    assert s["cim.fleet.dispatch"]["self_s"] == pytest.approx(30 * ns)
    assert s["cim.fleet.step"]["self_s"] == pytest.approx(20 * ns)
    assert "bench.step" not in s
    # idle 0-400 and 790-1000, each stretch charged to the innermost span
    assert r["idle_s"] == pytest.approx(610 * ns)
    assert r["idle_by_span_s"] == pytest.approx({
        "bench.window": 200 * ns, "bench.step": 20 * ns,
        "cim.fleet.step": 20 * ns, "cim.fleet.dispatch": 30 * ns,
        "cim.service.stack": 100 * ns, "cim.executor.dispatch": 40 * ns,
        "cim.executor.put": 100 * ns, "cim.executor.run": 50 * ns,
        "cim.executor.fetch": 50 * ns})
    assert r["idle_in_program_s"] == pytest.approx(390 * ns)
    assert r["breakdown"]["idle_gaps_program"][0] == [
        "bench.window", pytest.approx(200 * ns)]
    assert r["scope_s"] == pytest.approx({
        "jit(_forward)/conv1/im2col": 200 * ns,
        "jit(_forward)/conv1/gemm": 180 * ns, "": 10 * ns})


def test_no_program_span_or_device_op_reads_nothing():
    device, spans = _reduced()
    harness = [sp for sp in spans if sp[0].startswith("bench.")]
    assert sr.reduce({}, harness) is None
    only_spans = sr.reduce({}, spans)
    assert only_spans["scope_s"] is None and only_spans["idle_s"] is None
    assert only_spans["spans"]["cim.executor.put"]["count"] == 1
    # device ops, no program span: scopes and idle, nothing in the program
    r = sr.reduce(device, harness)
    assert r["spans"] == {} and r["idle_in_program_s"] == 0
    assert r["scope_s"]["jit(_forward)/conv1/gemm"] == pytest.approx(180e-9)


def test_recorded_cpu_trace_nests_the_program_spans(tmp_path):
    graph = get_workload("tiny_cnn")
    fleet = CimFleet([TenantSpec("cnn", graph)], get_arch("isaac-baseline"),
                     max_wait_s=0.0)
    for i in range(3):                        # warm the 4-bucket
        fleet.submit("cnn", make_input(graph, i), now=0.0)
    fleet.step(now=0.0, force=True)
    for i in range(3):
        fleet.submit_request(CimRequest(rid=100 + i, model="cnn",
                                        inputs=make_input(graph, i)),
                             now=0.0)
    obs_trace.use_profiler(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.step"):
                done = fleet.step(now=0.0, force=True)
    finally:
        jax.profiler.stop_trace()
        obs_trace.use_profiler(False)
    assert len(done) == 3
    device, spans = sr.load(sr.trace_reduce.find_xplane(str(tmp_path)))
    assert device == {}                       # no TPU plane on the CPU
    by_name = {n: (s, e) for n, s, e in spans}
    chain = ["bench.window", "bench.step", "cim.fleet.step",
             "cim.fleet.dispatch", "cim.executor.dispatch",
             "cim.executor.run"]
    for outer, inner in zip(chain, chain[1:]):
        assert by_name[outer][0] <= by_name[inner][0] \
            <= by_name[inner][1] <= by_name[outer][1], (outer, inner)
    r = sr.reduce(device, spans)
    for name in ("cim.service.stack", "cim.executor.put",
                 "cim.executor.fetch"):
        assert r["spans"][name]["count"] == 1
    assert r["idle_s"] is None and r["scope_s"] is None


# -- the readers -----------------------------------------------------------------

_read = run.read_metric


def _spans(**medians):
    return {"spans": {n: {"count": len(d), "durations_s": d, "self_s": 0.0}
                      for n, d in medians.items()},
            "idle_s": 0.2, "idle_in_program_s": 0.17,
            "scope_s": {"jit(_forward)/conv1/im2col": 0.6,
                        "jit(_forward)/layer1.0.conv1/im2col": 0.2,
                        "jit(_forward)/conv1/gemm": 0.3, "": 0.01}}


SPANS = _spans(**{"cim.service.stack": [0.001, 0.003, 0.002],
                  "cim.executor.put": [0.010, 0.012, 0.011],
                  "cim.executor.run": [0.100, 0.110, 0.105],
                  "cim.executor.fetch": [0.004, 0.004, 0.005]})
COUNTERS = {"counters": {'fleet_requests_total{tenant="net"}': 30.0,
                         'fleet_bucket_rows_total{tenant="net"}': 40.0,
                         "other_total": 1.0},
            "histograms": {'fleet_queue_wait_s{tenant="net"}': {
                "buckets": {}, "sum": 0.9, "count": 30}}}


@pytest.mark.parametrize("name, value", [
    ("host_prep_ms.offline", 13.0), ("device_wait_ms.offline", 105.0),
    ("fetch_ms.offline", 4.0), ("idle_in_program_share.offline", 85.0),
    ("im2col_ms.offline", 80.0), ("gemm_ms.offline", 30.0),
    ("batcher_wait_ms.server", 30.0), ("bucket_fill.server", 75.0),
    ("calibrate_s", 5.25)])
def test_readers(name, value):
    rec = {"spans": SPANS, "counters": COUNTERS, "batches": 10,
           "hist": {"service_calibrate_s": 5.25, "compile_wall_s": 0.02}}
    assert _read(name, rec) == pytest.approx(value)
    # a run of a program without the spans and counters reads nothing
    assert _read(name, {"batches": 10, "hist": {}, "trace": None}) is None


def test_readers_none_where_a_piece_is_missing():
    partial = _spans(**{"cim.executor.put": [0.01]})
    rec = {"spans": partial, "batches": 0}
    assert _read("host_prep_ms.offline", rec) is None
    assert _read("im2col_ms.offline", rec) is None        # no dispatch
    rec = {"spans": dict(partial, idle_s=0.0, scope_s=None), "batches": 3}
    assert _read("idle_in_program_share.offline", rec) is None
    assert _read("gemm_ms.offline", rec) is None
    empty = {"counters": {"counters": {}, "histograms": {}}}
    assert _read("bucket_fill.server", empty) is None
    assert _read("batcher_wait_ms.server", empty) is None


def test_every_reader_is_listed():
    """Every reader of ``bench/metrics/`` is a metric of
    ``BENCHMARK.json``: the traced path reads them all."""
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    files = {p.stem for p in (run.BENCH / "metrics").glob("*.py")}
    assert files == listed
