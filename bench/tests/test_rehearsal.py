"""A whole run of the harness on the CPU, at a tiny size.

The look for a chip is skipped here only (``require_tpu=False``); the
rest of a run is as on the chip: the fleet, the warm-up, the window,
the metric arithmetic, the reference check and the result's line.  With
the served path broken underneath, or the lower-precision control in
its place, ``correct`` has to come out false.
"""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import control
import run
from repro.core.abstraction import get_arch
from repro.kernels.cim_mvm import cim_mvm_params
from repro.serving.cim_service import CimBatchService

BENCH = Path(run.__file__).resolve().parent
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
# the cells' own mixes, with a small image pool and, in the open loop,
# the rate that tiny_cnn on the CPU sustains
BACKLOG = dict(run.traffic.load(BENCH / "traffic" / "offline.json"), pool=16)
POISSON = dict(run.traffic.load(BENCH / "traffic" / "poisson-38.json"),
               rate_per_s=150.0, pool=16)
ISAAC = "isaac-baseline"


def _cfg(arch, check_requests=4, network="tiny_cnn"):
    return {"network": network, "sizes": {}, "arch": arch,
            "cim": dataclasses.asdict(cim_mvm_params(get_arch(arch))),
            "buckets": [1, 2, 4, 8], "max_wait_s": 0.002,
            "check_requests": check_requests}


PER_LAYER = {
    "backlog": ["dispatch_ms.offline", "device_idle_share.offline",
                "step_mfu.offline", "host_prep_ms.offline",
                "device_wait_ms.offline", "fetch_ms.offline",
                "idle_in_program_share.offline", "im2col_ms.offline",
                "gemm_ms.offline"],
    "poisson": ["queue_wait_ms.server", "dispatch_ms.server",
                "batcher_wait_ms.server", "bucket_fill.server"],
}


# readers of the program's host spans and of its counters: in both mixes
# they read in a traced run and nothing in an untraced one
HOST_SPANS = {"host_prep_ms.offline", "device_wait_ms.offline",
              "fetch_ms.offline"}
COUNTED = {"batcher_wait_ms.server", "bucket_fill.server"}
DEVICE = {"device_idle_share.offline", "step_mfu.offline",
          "idle_in_program_share.offline", "im2col_ms.offline",
          "gemm_ms.offline"}


def _metrics(kind, extra=()):
    e2e = ["setup_s", "inferences_per_s"] if kind == "backlog" \
        else ["setup_s", "p50_latency_ms", "p95_latency_ms"]
    per_layer = PER_LAYER[kind] + ["compile_s", "lower_pack_s", "jit_warm_s"]
    return ([{"name": n, "unit": "-"} for n in [*e2e, *extra]],
            [{"name": n, "unit": "-"} for n in [*per_layer, *extra]])


def _run(mix, trace=False, control_bits=8, seed=2 ** 31 + 7,
         check_requests=4, network="tiny_cnn", extra=()):
    e2e, per_layer = _metrics(mix["kind"], sorted(set(extra)))
    return run.run_cell(_cfg(ISAAC, check_requests, network), mix, e2e,
                        per_layer, seed=seed,
                        seconds=1.0, trace=trace, require_tpu=False,
                        control_bits=control_bits)


@pytest.mark.parametrize("mix", [BACKLOG, POISSON], ids=["backlog", "poisson"])
def test_run_is_correct_and_well_formed(mix, capsys):
    out = _run(mix)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    names = set(out["metrics"])
    if mix["kind"] == "backlog":
        assert names == {"setup_s", "inferences_per_s"}
    else:
        assert names == {"setup_s", "p50_latency_ms", "p95_latency_ms"}
        assert out["attempted"] == 150      # rate x seconds, none dropped
    err = capsys.readouterr().err
    assert "compiles inside the window: 0" in err
    assert "host in the window: dispatch median" in err
    assert err.rstrip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("mix", [BACKLOG, POISSON], ids=["backlog", "poisson"])
def test_traced_run_reports_per_layer_metrics(mix):
    # both cells' readers of the program's spans and counters, in each mix
    out = _run(mix, trace=True, extra=HOST_SPANS | COUNTED | DEVICE)
    assert out["correct"] is True
    # the CPU has no device trace: only the readers of the harness's
    # clock, the program's host spans and its counters report
    expect = {"compile_s", "lower_pack_s", "jit_warm_s"} | HOST_SPANS | COUNTED
    expect |= {"dispatch_ms.offline"} if mix["kind"] == "backlog" else \
        {"dispatch_ms.server", "queue_wait_ms.server"}
    assert set(out["metrics"]) == expect
    assert 0 < out["metrics"]["bucket_fill.server"]["value"] <= 100
    assert out["metrics"]["batcher_wait_ms.server"]["value"] >= 0


@pytest.mark.parametrize("mix", [BACKLOG, POISSON], ids=["backlog", "poisson"])
def test_untraced_run_leaves_the_program_sinks_off(mix, monkeypatch):
    def refuse():
        raise AssertionError("program sinks opened in an untraced run")
    monkeypatch.setattr(run, "program_sinks", refuse)
    out = _run(mix, extra=HOST_SPANS | COUNTED)
    assert out["correct"] is True
    assert not (HOST_SPANS | COUNTED) & set(out["metrics"])


def test_arrivals_are_one_set_in_seeded_orders():
    arrivals = POISSON["process"].arrivals
    a, b = arrivals(POISSON, 1, 4.0), arrivals(POISSON, 2 ** 33, 4.0)
    assert len(a) == len(b) == 600
    assert a[-1] == pytest.approx(4.0) and b[-1] == pytest.approx(4.0)
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)


def test_an_unknown_kind_is_refused(tmp_path):
    (tmp_path / "bursty.json").write_text('{"kind": "bursty", "pool": 4}')
    with pytest.raises(ValueError, match="bursty.py"):
        run.traffic.load(tmp_path / "bursty.json")


@pytest.mark.parametrize("network", ["tiny_cnn", "tiny_mlp"])
def test_control_is_not_correct(network):
    out = _run(BACKLOG, control_bits=control.CONTROL_BITS, network=network)
    assert out["correct"] is False
    assert out["checks"]["max_abs_diff"]["value"] > 0


def test_a_network_with_its_own_crossbar_op_is_correct():
    """``tiny_mlp`` brings its crossbar op in its own file (``nets/``)."""
    out = _run(BACKLOG, network="tiny_mlp")
    assert out["correct"] is True and out["attempted"] > 0
    assert out["checks"]["mismatched_requests"]["value"] == 0


def _altered(batch_outputs):
    for outs in batch_outputs:
        for v in outs.values():
            v.flat[0] += 1


def _rows_shifted(batch_outputs):
    first = batch_outputs[0]
    for i in range(len(batch_outputs) - 1):
        batch_outputs[i].update(batch_outputs[i + 1])
    batch_outputs[-1].update(first)


_last = {}


def _stale(batch_outputs):
    fresh = [dict(o) for o in batch_outputs]
    if "prev" in _last:
        for o, p in zip(batch_outputs, _last["prev"]):
            o.update(p)
    _last["prev"] = fresh


def _half_left_out(batch_outputs):
    half = len(batch_outputs) // 2
    for i in range(half, 2 * half):
        batch_outputs[i].update(batch_outputs[i - half])


def _unserved(batch_outputs):
    for outs in batch_outputs:
        outs.clear()


@pytest.mark.parametrize("fault", [_altered, _rows_shifted, _stale,
                                   _half_left_out, _unserved],
                         ids=["answer_altered", "rows_shifted",
                              "state_unchanged", "half_batch_left_out",
                              "outputs_missing"])
def test_broken_served_path_is_not_correct(fault, monkeypatch):
    serve = CimBatchService._serve_batch

    def broken(self, batch, pad_to=None):
        serve(self, batch, pad_to=pad_to)
        outs = [{k: np.array(v) for k, v in r.outputs.items()}
                for r in batch]
        fault(outs)
        for r, o in zip(batch, outs):
            r.outputs = o
    _last.clear()
    monkeypatch.setattr(CimBatchService, "_serve_batch", broken)
    # every request of the window is compared, so that a fault of some
    # rows of a batch cannot slip past the sample
    out = _run(BACKLOG, check_requests=1 << 16)
    assert out["correct"] is False
    assert out["checks"]["mismatched_requests"]["value"] > 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cli_exits_nonzero_without_a_tpu():
    p = _cli(BENCH.parent)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_cli_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
