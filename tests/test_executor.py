"""Trace-lowered batched executor: bit-exact against the op-by-op
interpreter across chip modes, ADC regimes and batch sizes."""
import subprocess
import sys

import numpy as np
import pytest

from repro.cimsim.executor import LoweredExecutable, _patches, lower
from repro.cimsim.functional import (FunctionalSimulator, calibrate_shifts,
                                     compile_and_verify, im2col,
                                     make_input, make_weights, simulate)
from repro.core import compiler
from repro.core.abstraction import (CellType, ChipTier, CIMArch,
                                    ComputingMode, CoreTier, CrossbarTier)
from repro.kernels.cim_mvm import cim_mvm_params
from repro.workloads import get_workload

SMALL = CIMArch(
    name="test-wlm", mode=ComputingMode.WLM,
    chip=ChipTier(core_number=(4, 1), alu_ops_per_cycle=64, l0_bw_bits=1024),
    core=CoreTier(xb_number=(2, 1), l1_bw_bits=1024),
    xb=CrossbarTier(xb_size=(32, 32), dac_bits=1, adc_bits=8,
                    cell_type=CellType.SRAM, cell_precision=2,
                    parallel_row=8),
)
#: a 4-bit ADC saturates (exact_adc_bits needs 5 here) -> the executor
#: must take the tile-batched oracle path, not the matmul shortcut
SATURATING = SMALL.replace(name="test-sat",
                           xb=CrossbarTier(xb_size=(32, 32), dac_bits=1,
                                           adc_bits=4,
                                           cell_type=CellType.SRAM,
                                           cell_precision=2,
                                           parallel_row=8))
MODES = [ComputingMode.WLM, ComputingMode.XBM, ComputingMode.CM]


def _both(graph, arch, stream="auto"):
    """(interpreter outputs, executor outputs, executable) for one cell."""
    params = cim_mvm_params(arch)
    weights = make_weights(graph, 0)
    inputs = make_input(graph, 0)
    shifts = calibrate_shifts(graph, weights, inputs, params)
    res = compiler.compile_graph(graph, arch, expand=True)
    sim = FunctionalSimulator(res.plan, res.program, weights, shifts,
                              params=params)
    sim_out = sim.run(inputs)
    exe = lower(res.plan, res.program, params=params, stream=stream)
    exe_out = exe.run(inputs, weights, shifts)
    return sim_out, exe_out, exe


@pytest.mark.parametrize("wl", ["tiny_mlp", "tiny_cnn"])
@pytest.mark.parametrize("mode", MODES)
def test_executor_matches_interpreter(wl, mode):
    g = get_workload(wl)
    sim_out, exe_out, exe = _both(g, SMALL.replace(mode=mode))
    for t in g.outputs:
        np.testing.assert_array_equal(sim_out[t], exe_out[t])
    assert exe.stats.cim_reads > 0
    if exe.stats.streamed:
        # multi-segment plan: weight-update streaming rides the tile
        # path (the pool models crossbar residency), still bit-exact
        assert exe.stats.segments > 1 and exe.stats.swaps > 0
        assert exe.stats.matmul_nodes == 0
    else:
        assert exe.stats.matmul_nodes == exe.stats.cim_nodes  # exact ADC


@pytest.mark.parametrize("wl", ["tiny_mlp", "tiny_cnn"])
@pytest.mark.parametrize("mode", MODES)
def test_executor_matches_interpreter_saturating_adc(wl, mode):
    assert not cim_mvm_params(SATURATING).exact
    g = get_workload(wl)
    sim_out, exe_out, exe = _both(g, SATURATING.replace(mode=mode))
    for t in g.outputs:
        np.testing.assert_array_equal(sim_out[t], exe_out[t])
    assert exe.stats.matmul_nodes == 0     # tile-batched oracle path


def _strided_cnn():
    """ResNet's strided conv shapes at a small input: a 7x7/2 pad 3
    stem, a 3x3/2 pad 1 conv and a 1x1/2 pad 0 projection."""
    from repro.core.graph import Graph, Node
    nodes = [
        Node("stem", "Conv", ["input"], ["stem.out"],
             {"weight_shape": (4, 1, 7, 7), "stride": 2, "pad": 3}),
        Node("relu1", "Relu", ["stem.out"], ["relu1.out"]),
        Node("down", "Conv", ["relu1.out"], ["down.out"],
             {"weight_shape": (8, 4, 3, 3), "stride": 2, "pad": 1}),
        Node("relu2", "Relu", ["down.out"], ["relu2.out"]),
        Node("proj", "Conv", ["relu2.out"], ["proj.out"],
             {"weight_shape": (8, 8, 1, 1), "stride": 2, "pad": 0}),
        Node("flatten", "Flatten", ["proj.out"], ["flat.out"]),
        Node("fc", "Gemm", ["flat.out"], ["fc.out"],
             {"weight_shape": (8, 5)}),
    ]
    return Graph("strided_cnn", nodes, {"input": (1, 8, 8)}, ["fc.out"])


@pytest.mark.parametrize("stream", [False, True], ids=["packed", "streamed"])
@pytest.mark.parametrize("arch", [SMALL, SATURATING],
                         ids=["exact", "saturating"])
@pytest.mark.parametrize("mode", MODES)
def test_executor_matches_interpreter_strided_convs(arch, mode, stream):
    """Every MVM route (exact matmul, tile buckets, streamed pool) on
    convs with stride 2, k 7 and k 1, and pad 0."""
    g = _strided_cnn()
    sim_out, exe_out, exe = _both(g, arch.replace(mode=mode), stream)
    np.testing.assert_array_equal(sim_out["fc.out"], exe_out["fc.out"])
    assert exe.stats.streamed == stream
    assert exe.stats.matmul_nodes == (4 if arch is SMALL and not stream
                                      else 0)


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("cin,h,w,k,stride,pad", [
    (3, 16, 16, 7, 2, 3),       # ResNet stem
    (4, 8, 8, 3, 1, 1),
    (4, 8, 8, 3, 2, 1),
    (4, 8, 8, 1, 2, 0),         # projection shortcut
    (2, 6, 9, 3, 1, 1),         # non-square
    (2, 10, 8, 3, 2, 0),        # (h + 2*pad - k) not a multiple of stride
])
def test_patches_match_functional_im2col(cin, h, w, k, stride, pad, batch):
    x = np.random.default_rng(k * 10 + stride).integers(
        -128, 128, (batch, cin, h, w)).astype(np.int32)
    got = np.asarray(_patches(x, k, stride, pad))
    assert got.dtype == np.int32
    for b in range(batch):
        np.testing.assert_array_equal(got[b], im2col(x[b], k, stride, pad))


def test_executor_batch_axis_consistency():
    g = get_workload("tiny_cnn")
    arch = SMALL
    params = cim_mvm_params(arch)
    weights = make_weights(g, 0)
    shifts = calibrate_shifts(g, weights, make_input(g, 0), params)
    res = compiler.compile_graph(g, arch)
    exe = lower(res.plan, res.program, params=params)
    packed = exe.pack(weights)
    xs = [make_input(g, s) for s in range(5)]
    singles = [exe.run(x, packed=packed, shifts=shifts) for x in xs]
    batched = exe.run_batch(
        {"input": np.stack([x["input"] for x in xs])},
        packed=packed, shifts=shifts)
    for t in g.outputs:
        np.testing.assert_array_equal(
            batched[t], np.stack([s[t] for s in singles]))


def test_executor_split_graph():
    from repro.core.graph import Graph, Node
    nodes = [
        Node("fc1", "Gemm", ["input"], ["fc1.out"],
             {"weight_shape": (16, 12)}),
        Node("sp", "Split", ["fc1.out"], ["sp.a", "sp.b"],
             {"axis": -1, "parts": [4, 8]}),
        Node("ra", "Relu", ["sp.a"], ["ra.out"]),
        Node("rb", "Relu", ["sp.b"], ["rb.out"]),
        Node("cat", "Concat", ["ra.out", "rb.out"], ["cat.out"],
             {"axis": -1}),
        Node("fc2", "Gemm", ["cat.out"], ["fc2.out"],
             {"weight_shape": (12, 5)}),
    ]
    g = Graph("splitnet", nodes, {"input": (16,)}, ["fc2.out"])
    sim_out, exe_out, _ = _both(g, SMALL)
    np.testing.assert_array_equal(sim_out["fc2.out"], exe_out["fc2.out"])


@pytest.mark.parametrize("arch", [SMALL, SATURATING],
                         ids=["exact", "saturating"])
def test_executor_float_and_matmul_dcom_ops(arch):
    """Attention-style graph: MatMul (transpose_b), Softmax, LayerNorm
    and Gelu lowerings (the integer ALU rules, computed on the device)
    stay bit-exact vs the interpreter."""
    from repro.core.graph import Graph, Node
    nodes = [
        Node("fc1", "Gemm", ["input"], ["fc1.out"],
             {"weight_shape": (16, 16)}),
        Node("sm", "Softmax", ["fc1.out"], ["sm.out"]),
        Node("mm", "MatMul", ["sm.out", "fc1.out"], ["mm.out"],
             {"transpose_b": True}),
        Node("ln", "LayerNorm", ["mm.out"], ["ln.out"]),
        Node("ge", "Gelu", ["ln.out"], ["ge.out"]),
        Node("fc2", "Gemm", ["ge.out"], ["fc2.out"],
             {"weight_shape": (4, 5)}),
    ]
    g = Graph("attn_toy", nodes, {"input": (4, 16)}, ["fc2.out"])
    sim_out, exe_out, _ = _both(g, arch)
    np.testing.assert_array_equal(sim_out["fc2.out"], exe_out["fc2.out"])


def test_executor_simulate_entry_point():
    g = get_workload("tiny_cnn")
    sim_out, ref_out, _ = simulate(g, SMALL)
    exe_out, ref_out2, stats = simulate(g, SMALL, use_executor=True)
    for t in g.outputs:
        np.testing.assert_array_equal(sim_out[t], exe_out[t])
        np.testing.assert_array_equal(ref_out[t], ref_out2[t])
    assert stats.cim_reads > 0


def test_compile_and_verify_batched():
    g = get_workload("tiny_cnn")
    rep = compile_and_verify(g, SMALL, batch=3)
    assert rep.ok and rep.batch == 3
    assert set(rep.max_abs_err) == set(g.outputs)
    rep_sat = compile_and_verify(g, SATURATING, batch=2)
    assert rep_sat.ok                      # reference shares ADC semantics
    rep_interp = compile_and_verify(g, SMALL, batch=2, use_executor=False)
    assert rep_interp.ok


def test_compile_and_verify_falls_back_on_lowering_error(monkeypatch):
    """A flow the executor refuses raises ``LoweringError`` to the
    caller; op-by-op verification runs only with use_executor=False."""
    from repro.cimsim import executor as executor_mod

    def refuse(*args, **kwargs):
        raise executor_mod.LoweringError("forced for test")

    monkeypatch.setattr(executor_mod, "lower", refuse)
    with pytest.raises(executor_mod.LoweringError, match="forced"):
        compile_and_verify(get_workload("tiny_mlp"), SMALL, batch=2)
    rep = compile_and_verify(get_workload("tiny_mlp"), SMALL, batch=2,
                             use_executor=False)
    assert rep.ok and rep.lower_s == 0.0    # interpreter, as asked


def test_lower_cache_reuses_executable():
    g = get_workload("tiny_mlp")
    res1 = compiler.compile_graph(g, SMALL)
    res2 = compiler.compile_graph(g, SMALL)
    assert res1.key is not None and res1.key == res2.key
    e1 = lower(res1.plan, res1.program)
    e2 = lower(res2.plan, res2.program)
    assert e1 is e2
    assert isinstance(lower(res1.plan, res1.program, cache=False),
                      LoweredExecutable)
    # params are part of the key
    e3 = lower(res1.plan, res1.program,
               params=cim_mvm_params(SATURATING))
    assert e3 is not e1


def test_plan_key_distinguishes_baseline_policies():
    """Baseline-policy plans (different placements, same knobs) must not
    alias the compiler's plan in the executor cache."""
    from repro.core import baselines
    g = get_workload("tiny_mlp")
    compiled = compiler.compile_graph(g, SMALL)
    native = baselines.native(g, SMALL)
    assert compiler.compile_key_for_plan(native) != \
        compiler.compile_key_for_plan(compiled.plan)


def test_executor_swappable_weights_and_shifts():
    """One lowered executable serves any weight/shift set (no re-trace)."""
    g = get_workload("tiny_mlp")
    params = cim_mvm_params(SMALL)
    res = compiler.compile_graph(g, SMALL)
    exe = lower(res.plan, res.program, params=params)
    x = make_input(g, 0)
    for seed in (0, 1):
        w = make_weights(g, seed)
        sh = calibrate_shifts(g, w, x, params)
        res_e = compiler.compile_graph(g, SMALL, expand=True)
        sim = FunctionalSimulator(res_e.plan, res_e.program, w, sh,
                                  params=params)
        np.testing.assert_array_equal(
            exe.run(x, w, sh)["fc2.out"], sim.run(x)["fc2.out"])


def test_make_weights_stable_across_processes():
    """Weight seeding must not depend on the per-process str-hash salt."""
    snippet = (
        "from repro.cimsim.functional import make_weights\n"
        "from repro.workloads import get_workload\n"
        "import zlib\n"
        "w = make_weights(get_workload('tiny_mlp'), seed=3)\n"
        "print({k: zlib.crc32(v.tobytes()) for k, v in sorted(w.items())})\n"
    )
    digests = []
    for salt in ("0", "1"):
        out = subprocess.run(
            [sys.executable, "-c", snippet], check=True, text=True,
            capture_output=True,
            env={"PYTHONPATH": "src", "PYTHONHASHSEED": salt,
                 "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
            cwd=str(__import__("pathlib").Path(__file__).resolve().parent.parent))
        digests.append(out.stdout.strip())
    assert digests[0] == digests[1]


def test_cim_batch_service_matches_interpreter():
    from repro.serving.cim_service import CimBatchService, CimRequest
    g = get_workload("tiny_mlp")
    fast = CimBatchService(g, SMALL, max_batch=4)
    slow = CimBatchService(g, SMALL, max_batch=4, use_executor=False)
    reqs = [CimRequest(rid=i, inputs=make_input(g, i)) for i in range(6)]
    reqs2 = [CimRequest(rid=i, inputs=make_input(g, i)) for i in range(6)]
    fast.serve(reqs)
    slow.serve(reqs2)
    for a, b in zip(reqs, reqs2):
        for t in g.outputs:
            np.testing.assert_array_equal(a.outputs[t], b.outputs[t])
    assert fast.stats.requests == 6 and fast.stats.batches == 2


def test_cim_batch_service_falls_back_on_lowering_error(monkeypatch):
    """``LoweringError`` reaches the service's caller; the interpreter
    serves only with use_executor=False."""
    from repro.cimsim import executor as executor_mod
    from repro.serving.cim_service import CimBatchService, CimRequest

    def refuse(*args, **kwargs):
        raise executor_mod.LoweringError("forced for test")

    monkeypatch.setattr(executor_mod, "lower", refuse)
    g = get_workload("tiny_mlp")
    with pytest.raises(executor_mod.LoweringError, match="forced"):
        CimBatchService(g, SMALL, max_batch=4)
    svc = CimBatchService(g, SMALL, max_batch=4, use_executor=False)
    assert not svc.use_executor and svc.executor_stats is None
    reqs = [CimRequest(rid=i, inputs=make_input(g, i)) for i in range(2)]
    svc.serve(reqs)
    assert all(r.outputs is not None for r in reqs)


def test_campaign_verify_best():
    from repro.dse import DesignSpace, run_campaign
    g = get_workload("tiny_mlp")
    space = DesignSpace(SMALL, levels=("CM", "WLM"), bindings=("B->XBC",),
                        pipeline=(True,), duplication=(True,))
    camp = run_campaign({"tiny_mlp": g}, space, verify_best=True,
                        mode="exhaustive")
    rep = camp.workloads["tiny_mlp"].verify
    assert rep is not None and rep.ok
