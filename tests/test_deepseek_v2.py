"""DeepSeek-V2-Lite's layers at small widths (hidden 64, 4 heads, 8
experts, top-2, 16-32 tokens) on seeded weights: the interpreter, the
executor, the benchmark's own int8 reference and a float32 reference
agree; routing, the ALU rules and the perf model's routed share hold."""
import copy
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro.cimsim import executor, functional as fn
from repro.cimsim.functional import (FunctionalSimulator, calibrate_shifts,
                                     exact_mvm, make_input, make_weights,
                                     reference_forward)
from repro.configs import ARCHS
from repro.core import compiler
from repro.core.abstraction import ComputingMode, get_arch
from repro.core.graph import Graph, Node
from repro.kernels.cim_mvm import cim_mvm_params
from repro.workloads import deepseek_v2_ref, get_workload
from repro.workloads.deepseek_v2 import deepseek_v2_lite

ROOT = pathlib.Path(__file__).resolve().parents[1]
W = dict(hidden=64, heads=4, qk_nope=16, qk_rope=8, v_head=16, kv_lora=32,
         d_ff=96, moe_d_ff=32, n_experts=8, top_k=2, n_shared=2)
SIZES = dict(tokens=32, dense_layers=1, moe_layers=1, experts_held=4,
             vocab=64, widths=W)
ISAAC = get_arch("isaac-baseline")
CIM = {"act_bits": 8, "weight_bits": 8, "dac_bits": 1, "cell_bits": 2,
       "parallel_row": 8, "adc_bits": 8}
#: the program's logits against the float32 reference, relative L2: int8
#: rounding and one power-of-two scale per tensor cost ~5% a layer
#: (0.09-0.23 over ten seeds at these two layers); the int4 control
#: reads 0.45-0.74
TOLERANCE = 0.3


def _bench_net():
    """The benchmark's own reference module (bench/nets), by path."""
    import sys
    sys.path.insert(0, str(ROOT / "bench"))
    import reference
    spec = importlib.util.spec_from_file_location(
        "bench_deepseek_v2_lite", ROOT / "bench" / "nets" / "deepseek_v2_lite.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return reference, mod


def _executor_outputs(g, weights, shifts, xs, arch=ISAAC, cache=True):
    res = compiler.compile_graph(g, arch)
    exe = executor.lower(res.plan, res.program, params=cim_mvm_params(arch),
                         cache=cache)
    return exe.run_batch({"input": np.stack(xs)}, weights, shifts), exe


def _q4(v):
    return np.clip(np.round(v / 16), -8, 7) * 16


def test_interpreter_equals_executor():
    g = deepseek_v2_lite(**dict(SIZES, tokens=16))
    arch = ISAAC.replace(mode=ComputingMode.CM)
    weights, x = make_weights(g, 3), make_input(g, 3)
    shifts = calibrate_shifts(g, weights, x, cim_mvm_params(arch))
    xs = [x["input"], make_input(g, 4)["input"]]
    res = compiler.compile_graph(g, arch, expand=True, use_duplication=False,
                                 use_pipeline=False)
    sim = FunctionalSimulator(res.plan, res.program, weights, shifts)
    exe, _ = _executor_outputs(g, weights, shifts, xs, arch)
    for i, xi in enumerate(xs):
        np.testing.assert_array_equal(sim.run({"input": xi})["head.out"],
                                      exe["head.out"][i])


@pytest.mark.parametrize("seed,slack", [(0, 2), (7, 0.25), (2 ** 33 + 7, 2)],
                         ids=["one-round", "many-rounds", "large-seed"])
def test_bench_reference_equals_program(monkeypatch, seed, slack):
    """What the benchmark's check compares: the served executor's logits
    against bench/nets/deepseek_v2_lite.py, each calibrated on its own.
    At a slack of 0.25 each held expert's rows take several rounds."""
    reference, net_mod = _bench_net()
    monkeypatch.setattr(executor, "_ROUTE_SLACK", slack)
    g = deepseek_v2_lite(**SIZES)
    net = net_mod.build(**SIZES)
    weights = make_weights(g, seed)
    shifts = calibrate_shifts(g, weights, make_input(g, seed), cim_mvm_params(ISAAC))
    rng = np.random.default_rng(seed + 1)
    xs = [rng.integers(-128, 128, (32, 64)).astype(np.int32) for _ in range(2)]
    out, exe = _executor_outputs(g, weights, shifts, xs, cache=False)
    rows = exe._route_rows(64, 2 / 8)
    assert (rows < 16) == (slack < 1)
    ref_w = reference.make_weights(net, seed)
    _, ref_sh = reference.forward(net, ref_w,
                                  reference.calibration_input(net, seed), CIM)
    for i, xi in enumerate(xs):
        want, _ = reference.forward(net, ref_w, xi, CIM, shifts=ref_sh)
        np.testing.assert_array_equal(out["head.out"][i], want)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_program_within_tolerance_of_float32(seed):
    g = deepseek_v2_lite(**SIZES)
    weights, x = make_weights(g, seed), make_input(g, seed)
    got, shifts = reference_forward(g, weights, x)
    control, _ = reference_forward(
        g, weights, x, shifts=shifts,
        mvm=lambda a, b: exact_mvm(_q4(a), _q4(b)))
    want = np.asarray(deepseek_v2_ref.forward(weights, shifts, x["input"],
                                              **SIZES))

    def rel(v):
        return np.linalg.norm(v - want) / np.linalg.norm(want)
    assert rel(got["head.out"]) <= TOLERANCE < rel(control["head.out"])


def test_rank_shares_add_up_to_the_uncut_layer():
    """The shared experts counted once plus every rank's routed experts
    give the uncut layer: exactly on the program's pre-combine sums, and
    on the float32 reference's."""
    one = dict(SIZES, dense_layers=0, moe_layers=1)
    seed = 5
    full = deepseek_v2_lite(**dict(one, experts_held=8))
    weights, x = make_weights(full, seed), make_input(full, seed)

    def parts(held, rank):
        g = deepseek_v2_lite(**dict(one, experts_held=held, rank=rank))
        w = {k: weights[k] for k in make_weights(g, seed)}
        t, shifts = reference_forward(g, w, x)
        gates = t["L0.moe.topk.out"].astype(np.int64)
        acc = sum(gates[:, j:j + 1] * t[f"L0.moe.e{rank * held + j}.down.out"]
                  for j in range(held))
        f = {}
        deepseek_v2_ref.forward(w, shifts, x["input"], parts=f,
                                **dict(one, experts_held=held, rank=rank))
        return acc, t["L0.moe.shared.down.out"], f[0]

    acc, shared, (facc, fshared) = parts(8, 0)
    ranks = [parts(4, r) for r in (0, 1)]
    np.testing.assert_array_equal(acc, sum(r[0] for r in ranks))
    for r in ranks:
        np.testing.assert_array_equal(r[1], shared)
        np.testing.assert_allclose(np.asarray(r[2][1]), np.asarray(fshared))
    np.testing.assert_allclose(np.asarray(facc),
                               sum(np.asarray(r[2][0]) for r in ranks),
                               rtol=1e-5, atol=1e-2)


def test_routing_ties_go_to_the_lower_index():
    _, net_mod = _bench_net()
    n_e, k = 8, 2
    node = Node("topk", "TopKRouter", ["input"], ["topk.out"],
                {"n_experts": n_e, "k": k, "frac": 4})
    g = Graph("ties", [Node("relu", "Relu", ["x"], ["input"]), node],
              {"x": (4, n_e)}, ["topk.out"])
    logits = np.array([[5, 5, 5, 5, 0, 0, 0, 0],
                       [0, 0, 0, 9, 9, 9, 0, 0],
                       [3, 0, 3, 0, 3, 0, 3, 0],
                       [1, 1, 1, 1, 1, 1, 1, 1]], np.int32)
    want = [[0, 1], [3, 4], [0, 2], [0, 1]]
    interp = fn.router_int(logits, node)
    res = compiler.compile_graph(g, ISAAC)
    exe = executor.lower(res.plan, res.program).run({"x": logits}, {}, {})
    bench = net_mod.topk({"k": k, "held": n_e}, [logits.astype(np.int64)],
                         None)
    for gates in (interp, exe["topk.out"], bench):
        assert [list(np.flatnonzero(row)) for row in gates] == want
    np.testing.assert_array_equal(interp, exe["topk.out"])
    np.testing.assert_array_equal(interp, bench)


def test_cell_program_holds_no_host_callback():
    g = deepseek_v2_lite(**SIZES)
    res = compiler.compile_graph(g, ISAAC)
    exe = executor.lower(res.plan, res.program)
    packed = exe.pack(make_weights(g, 0))
    sh = {n: np.int32(0) for n in exe._shift_names}
    jaxpr = str(jax.make_jaxpr(exe._forward)(
        packed, sh, {"input": np.zeros((2, 32, 64), np.int32)}))
    assert "callback" not in jaxpr
    assert "while" in jaxpr                   # the routed rounds


def _silu_table_error():
    node = Node("a", "Silu", ["x"], ["y"], {"frac": 4, "out_frac": 4})
    v = np.arange(-128, 128) / 16
    return np.abs(fn.elementwise_table(node) / 16 - v / (1 + np.exp(-v))).max()


def _softmax_error():
    rng = np.random.default_rng(0)
    v = rng.integers(-128, 128, (64, 40))
    node = Node("s", "Softmax", ["x"], ["y"],
                {"frac": 4, "out_frac": 15, "scale": 0.5})
    got = fn.softmax_int(v, fn.softmax_exp_table(node), 15) / 2 ** 15
    z = v / 16 * 0.5
    e = np.exp(z - z.max(-1, keepdims=True))
    return np.abs(got - e / e.sum(-1, keepdims=True)).max()


def _rmsnorm_error():
    rng = np.random.default_rng(1)
    x = rng.integers(-128, 128, (64, 96))
    got = fn.norm_int(x, 4, center=False) / 16
    want = x / np.sqrt((x.astype(float) ** 2).mean(-1, keepdims=True))
    return np.abs(got - np.clip(want, -8, 127 / 16)).max()


def _rope_error():
    node = Node("r", "RoPE", ["x"], ["y"], {"dim": 8, "theta": 10000.0})
    x = np.random.default_rng(2).integers(-90, 90, (16, 3, 8))
    cos, sin = fn.rope_tables(node, 16)
    got = fn.rope_int(x, cos, sin)
    ang = np.arange(16)[:, None, None] * fn.yarn_inv_freq(8, 1e4, None)
    x1, x2 = x[..., :4], x[..., 4:]
    want = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
    return np.abs(got - np.clip(want, -128, 127)).max()


def _gate_error():
    v = np.random.default_rng(3).integers(-128, 128, (64, 8))
    node = Node("t", "TopKRouter", ["x"], ["y"],
                {"n_experts": 8, "k": 8, "frac": 4})
    z = v / 16
    e = np.exp(z - z.max(-1, keepdims=True))
    return np.abs(fn.router_int(v, node) / 2 ** 15
                  - e / e.sum(-1, keepdims=True)).max()


@pytest.mark.parametrize("error,bound", [
    (_silu_table_error, 0.5 / 16 + 1e-12),     # half a Q4 step
    (_softmax_error, 2 ** -14),                 # Q15 rounding + E's 1.5 steps
    (_rmsnorm_error, 0.5 / 16 + 0.02),          # Q4 step + rms read to 1/128
    (_rope_error, 0.5 + 128 * 2 ** -14 * 2),    # half a step + table rounding
    (_gate_error, 2 ** -14),
], ids=["silu", "softmax", "rmsnorm", "rope", "router-gates"])
def test_alu_rule_error_against_float64(error, bound):
    assert error() <= bound


def test_device_isqrt_is_exact():
    """The device's integer root (bit by bit, no float root) is
    floor(sqrt(n)) at perfect squares and their neighbours to 2**30."""
    r = np.arange(0, 1 << 15, 7, dtype=np.int64)
    n = np.clip(np.concatenate([r * r - 1, r * r, r * r + 1, [1 << 30]]),
                0, 1 << 30)
    got = np.asarray(executor._isqrt(jax.numpy.asarray(n, np.int32)))
    np.testing.assert_array_equal(got, fn.isqrt(n))


def test_tall_gemm_sums_split_plane_chunks():
    """A crossbar Gemm taller than one exact f32 plane GEMM (the dense
    FFN's 10944 rows) sums chunks in int32, exact at the extremes."""
    r = executor._F32_SPLIT_MAX_R + 808
    g = Graph("tall", [Node("fc", "Gemm", ["input"], ["fc.out"],
                            {"weight_shape": (r, 4)})],
              {"input": (2, r)}, ["fc.out"])
    res = compiler.compile_graph(g, ISAAC)
    exe = executor.lower(res.plan, res.program)
    x = np.full((1, 2, r), 127, np.int32)
    x[0, 1] = -128
    w = np.full((r, 4), -128, np.int32)
    w[:, 1] = 127
    shift = int(np.ceil(np.log2(r * 128 * 128 / 127)))
    got = exe.run_batch({"input": x}, {"fc": w}, {"fc": shift})["fc.out"]
    want = (x[0].astype(np.int64) @ w) >> shift
    np.testing.assert_array_equal(got[0], np.clip(want, -128, 127))


def test_routed_experts_cost_their_share():
    """top-k of n routed experts fire for k/n of the tokens in the perf
    model: fewer crossbar activations and compute cycles than every
    expert on every token (latency follows the attention here)."""
    g = deepseek_v2_lite(**dict(SIZES, dense_layers=0))
    d = copy.deepcopy(g.to_dict())
    for n in d["nodes"]:
        if "route_share" in n["attrs"]:
            n["attrs"]["route_share"] = 1.0
    dense = Graph.from_dict(d)
    routed = compiler.compile_graph(g, ISAAC).metrics()
    every = compiler.compile_graph(dense, ISAAC).metrics()
    assert routed["energy_units"] < every["energy_units"]
    assert routed["compute_cycles"] < every["compute_cycles"]
    assert routed["latency_cycles"] <= every["latency_cycles"]


def test_per_head_matmul_alu_cost():
    """An attention product costs heads x queries x keys x head width on
    the ALU, and its standalone stage is charged that many MACs."""
    from repro.core.graph import macs
    g = deepseek_v2_lite(**SIZES)
    qk, av = g.node("L0.attn.qk"), g.node("L0.attn.av")
    assert macs(qk, g.shapes) == 4 * 32 * 32 * (16 + 8)
    assert macs(av, g.shapes) == 4 * 32 * 32 * 16


def test_matmul_contraction_is_checked():
    with pytest.raises(ValueError, match="contracts 16 against 8"):
        Graph("bad", [Node("mm", "MatMul", ["a", "b"], ["c"])],
              {"a": (2, 4, 16), "b": (2, 8, 4)}, ["c"])


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_lm_block_heads_and_experts(arch):
    """Every assigned architecture's block is per head, and a MoE layer
    routes to experts of its own (none summed whole)."""
    cfg = ARCHS[arch]
    g = get_workload(f"lmblock:{arch}", seq=16)
    assert g.inputs == {"input": (16, cfg.d_model)}
    for n in g.nodes:
        if n.op_type == "MatMul" and n.attrs.get("transpose_b"):
            assert g.shapes[n.outputs[0]] == (cfg.n_heads, 16, 16)
        if n.op_type == "MoECombine":
            assert len(n.inputs) == 1 + cfg.n_experts
    moe = any(s.mlp == "moe" for s in cfg.pre + cfg.unit)
    assert moe == any(n.op_type == "TopKRouter" for n in g.nodes)
