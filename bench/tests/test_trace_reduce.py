"""The trace reduction on a hand-built trace and on a recorded one."""
import jax
import jax.numpy as jnp
import pytest

import trace_reduce as tr

# one chip; window 100..1100 ns; ops 100-300, 250-400 (overlap), 700-900
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 250000 duration_ps: 150000 }
    events { metadata_id: 1 offset_ps: 700000 duration_ps: 200000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 800000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "cim_mvm_kernel" } }
  event_metadata { key: 3 value { id: 3 name: "jit_forward" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 350000 }
    events { metadata_id: 3 offset_ps: 450000 duration_ps: 300000 }
    events { metadata_id: 4 offset_ps: 100000 duration_ps: 5000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.idle" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(f)" } }
}
"""


def _profile():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(XSPACE)


def test_hand_built_trace():
    device, spans = tr.events_of(_profile())
    assert sorted(device) == ["/device:TPU:0"]
    assert [n for n, _, _ in spans] == ["bench.window", "bench.step",
                                        "bench.idle"]
    r = tr.reduce(device, spans)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(500e-9)      # 100-400 and 700-900
    assert r["op_s"] == pytest.approx({"fusion.1": 400e-9,
                                       "cim_mvm_kernel": 150e-9})
    # gaps: 400-700 (mid 550: bench.idle), 900-1100 (mid 1000: window)
    assert r["idle_by_span_s"] == pytest.approx({"bench.idle": 300e-9,
                                                 "bench.window": 200e-9})
    assert r["breakdown"]["device_ops"][0][0] == "fusion.1"
    assert r["breakdown"]["idle_gaps"][0] == ["bench.idle",
                                              pytest.approx(300e-9)]


# the same trace, with an asynchronous copy from 350 to 750 ns that spans
# the idle gap 400-700: once among the ops, once on the async line
ASYNC_OPS = XSPACE.replace(
    "events { metadata_id: 1 offset_ps: 700000 duration_ps: 200000 } }",
    "events { metadata_id: 1 offset_ps: 700000 duration_ps: 200000 }\n"
    "    events { metadata_id: 4 offset_ps: 350000 duration_ps: 400000 }\n"
    "    events { metadata_id: 5 offset_ps: 740000 duration_ps: 10000 } }\n"
    "  lines { id: 3 name: \"Async XLA Ops\" timestamp_ns: 0\n"
    "    events { metadata_id: 4 offset_ps: 350000 duration_ps: 400000 } }",
    1).replace(
    'event_metadata { key: 3 value { id: 3 name: "jit_forward" } }',
    'event_metadata { key: 3 value { id: 3 name: "jit_forward" } }\n'
    '  event_metadata { key: 4 value { id: 4 name: "%copy-start.15 = '
    '(s32[8]{0}, s32[8]{0}, u32[]) copy-start(s32[8]{0} %fusion.1)" } }\n'
    '  event_metadata { key: 5 value { id: 5 name: "copy-done.15" } }', 1)


def test_async_ops_do_not_fill_idle_gaps():
    from jax.profiler import ProfileData
    device, spans = tr.events_of(ProfileData.from_text_proto(ASYNC_OPS))
    assert len(device["/device:TPU:0"]) == 3
    r = tr.reduce(device, spans)
    assert r["busy_s"] == pytest.approx(500e-9)
    assert r["op_s"] == pytest.approx({"fusion.1": 400e-9,
                                       "cim_mvm_kernel": 150e-9})
    assert r["idle_by_span_s"]["bench.idle"] == pytest.approx(300e-9)


def test_async_names():
    for name in ["%copy-start.15 = (s32[8]{0}) copy-start(s32[8]{0} %a)",
                 "slice-done.3", "all-gather-start", "%async-update.2 = x"]:
        assert tr.ASYNC.match(name)
    for name in ["%fusion.2 = s32[8]{0} fusion(%copy-start.1)",
                 "%dynamic-slice_reduce_fusion.2 = s32[1]{0} fusion(%a)",
                 "%copy.2761 = s32[784]{0} copy(%bitcast.192)"]:
        assert not tr.ASYNC.match(name)


def test_no_window_or_no_device_reads_nothing():
    device, spans = tr.events_of(_profile())
    assert tr.reduce(device, [s for s in spans if s[0] != tr.WINDOW]) is None
    assert tr.reduce({}, spans) is None


def test_union_and_gaps():
    assert tr.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert tr.gaps([(0, 3), (5, 6)], 0, 10) == [(3, 5), (6, 10)]


def test_recorded_cpu_trace_has_the_harness_spans(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    device, spans = tr.load(path)
    names = [n for n, _, _ in spans]
    assert "bench.window" in names and "bench.step" in names
    # the CPU backend has no TPU plane: nothing to reduce
    assert device == {} and tr.reduce(device, spans) is None
