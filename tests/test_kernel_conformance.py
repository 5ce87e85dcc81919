"""Differential conformance suite for the CIM MVM kernels.

Locks down the backend-capability registry contract: every execution
route of ``cim_mvm`` / ``cim_mvm_tiles`` / ``cim_mvm_signed`` —
compiled ``pallas_call`` (TPU/GPU), Pallas interpreter, XLA-compiled
oracle — must be **bit-exact** against the others on every case.
Three layers of evidence:

  * hypothesis property sweeps (via ``hypcompat``; skipped when
    hypothesis is absent) across dac/adc bit-widths, parallel-row
    grouping, tile counts and batch shapes;
  * committed golden vectors (``tests/golden/cim_mvm/*.npz``,
    regenerated by ``tools/make_golden_cim_mvm.py``) replayable on any
    platform without hypothesis — the cross-release, cross-backend
    contract;
  * registry regression tests: resolution precedence, forced
    unsupported routes raising to the caller of the executor and the
    serving stack (no silent interpreter run), and multi-segment
    schedules staying on the executor path (weight-update streaming).

``accel``-marked cases exercise the genuinely compiled route and are
auto-skipped off-accelerator (tests/conftest.py).
"""
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.kernels import backend
from repro.kernels.cim_mvm import (CimMvmParams, cim_mvm, cim_mvm_signed,
                                   cim_mvm_tiles)

GOLDEN_DIR = pathlib.Path(__file__).resolve().parent / "golden" / "cim_mvm"
GOLDEN = sorted(GOLDEN_DIR.glob("*.npz"))
ENTRY = {"cim_mvm": cim_mvm, "cim_mvm_tiles": cim_mvm_tiles,
         "cim_mvm_signed": cim_mvm_signed}

PLATFORM = backend.detect_platform()


def _modes(kernel: str):
    return backend.REGISTRY[kernel].modes_on(PLATFORM)


def _load(path):
    z = np.load(path)
    p = z["params"]
    params = CimMvmParams(act_bits=int(p[0]), weight_bits=int(p[1]),
                          dac_bits=int(p[2]), cell_bits=int(p[3]),
                          parallel_row=int(p[4]), adc_bits=int(p[5]))
    return str(z["kind"]), z["x"], z["w"], z["y"], params


# ------------------------------------------------------------ registry

def test_registry_covers_public_kernels():
    assert set(backend.REGISTRY) == {"cim_mvm", "cim_mvm_tiles",
                                     "cim_mvm_signed"}
    matrix = backend.capability_matrix()
    for name, row in matrix.items():
        assert row["auto_mode"] in backend.MODES
        # interpret + xla run anywhere; compiled only on TPU/GPU
        assert "interpret" in row["platforms"]["cpu"]
        assert "xla" in row["platforms"]["cpu"]
        assert "compiled" not in row["platforms"]["cpu"]
        assert "compiled" in row["platforms"]["tpu"]
        assert "compiled" in row["platforms"]["gpu"]


def test_auto_never_picks_interpret():
    for platform in ("cpu", "gpu", "tpu"):
        route = backend.resolve("cim_mvm", mode="auto", platform=platform)
        assert route.mode != "interpret"
        assert route.mode == ("compiled" if platform in ("gpu", "tpu")
                              else "xla")


def test_resolution_precedence(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    assert backend.resolve("cim_mvm").mode == "interpret"   # env beats auto
    with backend.override("xla"):                  # blanket beats env
        assert backend.resolve("cim_mvm").mode == "xla"
        with backend.override("interpret", kernel="cim_mvm"):
            # named beats blanket; per-call beats everything
            assert backend.resolve("cim_mvm").mode == "interpret"
            assert backend.resolve("cim_mvm_tiles").mode == "xla"
            assert backend.resolve("cim_mvm", mode="xla").mode == "xla"
    assert backend.resolve("cim_mvm").mode == "interpret"   # override popped


def test_unsupported_route_raises():
    with pytest.raises(backend.KernelUnsupportedError):
        backend.resolve("cim_mvm", mode="compiled", platform="cpu")
    with pytest.raises(KeyError):
        backend.resolve("nope")
    with pytest.raises(ValueError):
        backend.resolve("cim_mvm", mode="warp")


def test_platform_env_override(monkeypatch):
    # CI's forced-mode legs export REPRO_KERNEL_MODE; clear it so the
    # auto route (what this test exercises) is actually taken
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    monkeypatch.setenv("REPRO_KERNEL_PLATFORM", "tpu")
    assert backend.detect_platform() == "tpu"
    assert backend.resolve("cim_mvm").mode == "compiled"


# ---------------------------------------------- golden-vector replay

@pytest.mark.parametrize("path", GOLDEN, ids=lambda p: p.stem)
def test_golden_replay_all_routes(path):
    """Every committed fixture replays bit-exactly on every route the
    active platform supports (no hypothesis, no RNG)."""
    kind, x, w, y, params = _load(path)
    for mode in _modes(kind):
        got = np.asarray(ENTRY[kind](jnp.asarray(x), jnp.asarray(w),
                                     params, mode=mode))
        np.testing.assert_array_equal(
            got, y, err_msg=f"{path.stem} diverges on mode={mode!r}")


def test_golden_fixtures_present():
    assert len(GOLDEN) >= 6       # committed contract, not a generated dir
    kinds = {_load(p)[0] for p in GOLDEN}
    assert kinds == set(ENTRY)    # every entry point has golden coverage


@pytest.mark.accel
@pytest.mark.parametrize("path", GOLDEN, ids=lambda p: p.stem)
def test_golden_replay_compiled(path):
    """The genuinely compiled pallas_call route (TPU/GPU only)."""
    kind, x, w, y, params = _load(path)
    got = np.asarray(ENTRY[kind](jnp.asarray(x), jnp.asarray(w), params,
                                 mode="compiled"))
    np.testing.assert_array_equal(got, y)


@pytest.mark.parametrize("dac,cell,pr,adc", [(8, 2, 128, 1), (8, 2, 16, 8),
                                             (8, 8, 128, 4)],
                         ids=["puma", "toy", "dac8-cell8"])
def test_wide_planes_tiles_interpret_matches_xla(dac, cell, pr, adc):
    """8-bit planes take the kernel's bf16/f32 MXU path; the Pallas body
    stays bit-exact against the int32 oracle at the ADC limits."""
    params = CimMvmParams(act_bits=8, weight_bits=8, dac_bits=dac,
                          cell_bits=cell, parallel_row=pr, adc_bits=adc)
    rng = np.random.default_rng(dac * 1000 + pr + adc)
    x = rng.integers(0, 256, (3, 10, 2 * pr + 5)).astype(np.int32)
    w = rng.integers(0, 256, (3, 2 * pr + 5, 20)).astype(np.int32)
    _pairwise_equal("cim_mvm_tiles", cim_mvm_tiles, x, w, params)


def test_plane_range_beyond_f32_refused():
    """A group sum that f32 cannot hold exactly is refused, not rounded."""
    params = CimMvmParams(dac_bits=8, cell_bits=8, parallel_row=512,
                          adc_bits=8)
    x = jnp.ones((1, 4, 512), jnp.int32)
    w = jnp.ones((1, 512, 8), jnp.int32)
    with pytest.raises(backend.KernelUnsupportedError, match="exact"):
        cim_mvm_tiles(x, w, params, mode="interpret")


# ---------------------------------------------- property sweeps

def _pairwise_equal(kernel, fn, x, w, params):
    """All supported routes agree; returns one result for further checks."""
    outs = {mode: np.asarray(fn(jnp.asarray(x), jnp.asarray(w), params,
                                mode=mode))
            for mode in _modes(kernel)}
    base_mode, base = next(iter(outs.items()))
    for mode, got in outs.items():
        np.testing.assert_array_equal(
            got, base, err_msg=f"{kernel}: {mode!r} != {base_mode!r}")
    return base


@settings(max_examples=30, deadline=None)
@given(m=st.integers(1, 24), r=st.integers(1, 150), c=st.integers(1, 100),
       pr=st.sampled_from([4, 8, 32, 128]),
       db=st.sampled_from([1, 2, 4]), cb=st.sampled_from([1, 2, 4]),
       adcb=st.sampled_from([3, 4, 6, 8, 20]))
def test_conformance_cim_mvm(m, r, c, pr, db, cb, adcb):
    params = CimMvmParams(act_bits=8, weight_bits=8, dac_bits=db,
                          cell_bits=cb, parallel_row=pr, adc_bits=adcb)
    rng = np.random.default_rng(m * 7919 + r * 131 + c * 17 + adcb)
    x = rng.integers(0, 256, (m, r)).astype(np.int32)
    w = rng.integers(0, 256, (r, c)).astype(np.int32)
    y = _pairwise_equal("cim_mvm", cim_mvm, x, w, params)
    if params.exact:      # wide ADC: ground truth is the integer matmul
        np.testing.assert_array_equal(
            y, x.astype(np.int64) @ w.astype(np.int64))


@settings(max_examples=20, deadline=None)
@given(t=st.integers(1, 5), m=st.integers(1, 16), r=st.integers(1, 96),
       c=st.integers(1, 48), pr=st.sampled_from([4, 8, 32]),
       db=st.sampled_from([1, 2]), adcb=st.sampled_from([4, 8, 20]))
def test_conformance_cim_mvm_tiles(t, m, r, c, pr, db, adcb):
    params = CimMvmParams(act_bits=8, weight_bits=8, dac_bits=db,
                          cell_bits=2, parallel_row=pr, adc_bits=adcb)
    rng = np.random.default_rng(t * 104729 + m * 7919 + r * 131 + c)
    x = rng.integers(0, 256, (t, m, r)).astype(np.int32)
    w = rng.integers(0, 256, (t, r, c)).astype(np.int32)
    y = _pairwise_equal("cim_mvm_tiles", cim_mvm_tiles, x, w, params)
    if params.exact:
        np.testing.assert_array_equal(
            y, np.einsum("tmr,trc->tmc", x.astype(np.int64),
                         w.astype(np.int64)))


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 16), r=st.integers(1, 120), c=st.integers(1, 40),
       pr=st.sampled_from([4, 8, 32]), db=st.sampled_from([1, 2]))
def test_conformance_cim_mvm_signed(m, r, c, pr, db):
    params = CimMvmParams(act_bits=8, weight_bits=8, dac_bits=db,
                          cell_bits=2, parallel_row=pr, adc_bits=20)
    rng = np.random.default_rng(m * 31 + r * 7 + c)
    x = rng.integers(-128, 128, (m, r)).astype(np.int32)
    w = rng.integers(-128, 128, (r, c)).astype(np.int32)
    y = _pairwise_equal("cim_mvm_signed", cim_mvm_signed, x, w, params)
    # wide ADC: offset encoding is exact, so signed int matmul
    np.testing.assert_array_equal(
        y, x.astype(np.int64) @ w.astype(np.int64))


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 8), r=st.integers(1, 64), c=st.integers(1, 24),
       rows_used=st.integers(1, 64))
def test_conformance_rows_used_clamps_parallel_row(m, r, c, rows_used):
    """cim_mvm_params(rows_used=...) clamps the analog row group — the
    clamped params must stay route-consistent too."""
    from repro.core.abstraction import get_arch
    from repro.kernels.cim_mvm import cim_mvm_params
    params = cim_mvm_params(get_arch("isaac-baseline"), rows_used=rows_used)
    assert params.parallel_row <= max(1, rows_used)
    rng = np.random.default_rng(m * 13 + r * 3 + c + rows_used)
    x = rng.integers(0, 256, (m, r)).astype(np.int32)
    w = rng.integers(0, 256, (r, c)).astype(np.int32)
    _pairwise_equal("cim_mvm", cim_mvm, x, w, params)


# ------------------------------------- fallback / executor regressions

def _small_arch():
    from repro.core.abstraction import (CellType, ChipTier, CIMArch,
                                        ComputingMode, CoreTier,
                                        CrossbarTier)
    return CIMArch(
        name="conf-small", mode=ComputingMode.WLM,
        chip=ChipTier(core_number=(4, 1), alu_ops_per_cycle=64,
                      l0_bw_bits=1024),
        core=CoreTier(xb_number=(2, 1), l1_bw_bits=1024),
        xb=CrossbarTier(xb_size=(32, 32), dac_bits=1, adc_bits=8,
                        cell_type=CellType.SRAM, cell_precision=2,
                        parallel_row=8),
    )


def _force_unsupported(monkeypatch):
    """Make every route of cim_mvm_tiles unsatisfiable: no compiled
    platforms + a forced 'compiled' override."""
    monkeypatch.setitem(
        backend.REGISTRY, "cim_mvm_tiles",
        backend.KernelCapability("cim_mvm_tiles", compiled_platforms=()))
    monkeypatch.setitem(backend._OVERRIDES, "cim_mvm_tiles", "compiled")


def test_forced_unsupported_route_falls_back_bit_exact(monkeypatch):
    """Registry says no -> KernelUnsupportedError reaches the callers of
    ``lower`` and ``compile_and_verify``; the interpreter runs only when
    asked for, and then still verifies bit-exactly."""
    from repro.cimsim.executor import lower
    from repro.cimsim.functional import compile_and_verify
    from repro.core import compiler
    from repro.workloads import get_workload
    g = get_workload("tiny_mlp")
    arch = _small_arch()
    _force_unsupported(monkeypatch)
    res = compiler.compile_graph(g, arch)
    with pytest.raises(backend.KernelUnsupportedError, match="not supported"):
        lower(res.plan, res.program, cache=False)
    with pytest.raises(backend.KernelUnsupportedError, match="not supported"):
        compile_and_verify(g, arch, batch=2)
    rep = compile_and_verify(g, arch, batch=2, use_executor=False)
    assert rep.ok and rep.lower_s == 0.0     # interpreter, as asked


def test_forced_unsupported_route_degrades_service(monkeypatch):
    """An unsupportable kernel route fails the service and the fleet at
    construction; only ``use_executor=False`` serves op by op, and its
    outputs equal the executor's."""
    from repro.cimsim.functional import make_input
    from repro.serving import CimFleet, TenantSpec
    from repro.serving.cim_service import CimBatchService, CimRequest
    from repro.workloads import get_workload
    g = get_workload("tiny_mlp")
    arch = _small_arch()
    reference = CimBatchService(g, arch, max_batch=4)
    assert reference.use_executor and reference.executor_stats is not None
    _force_unsupported(monkeypatch)
    with pytest.raises(backend.KernelUnsupportedError):
        CimBatchService(g, arch, max_batch=4)
    with pytest.raises(backend.KernelUnsupportedError):
        CimFleet([TenantSpec("mlp", g, traffic=1.0)], arch)
    degraded = CimBatchService(g, arch, max_batch=4, use_executor=False)
    assert degraded.executor_stats is None
    reqs_a = [CimRequest(rid=i, inputs=make_input(g, i)) for i in range(3)]
    reqs_b = [CimRequest(rid=i, inputs=make_input(g, i)) for i in range(3)]
    reference.serve(reqs_a)
    degraded.serve(reqs_b)
    for a, b in zip(reqs_a, reqs_b):
        for t in g.outputs:
            np.testing.assert_array_equal(a.outputs[t], b.outputs[t])


def test_multi_segment_schedule_takes_executor_path():
    """Multi-segment compiles stream weight updates through the trace —
    they must NOT fall back to the interpreter."""
    from repro.cimsim.executor import lower
    from repro.cimsim.functional import compile_and_verify
    from repro.core import compiler
    from repro.core.abstraction import CoreTier
    from repro.workloads import get_workload
    g = get_workload("tiny_cnn")
    arch = _small_arch().replace(core=CoreTier(xb_number=(1, 1),
                                               l1_bw_bits=1024))
    res = compiler.compile_graph(g, arch)
    assert len(res.plan.segments) > 1
    exe = lower(res.plan, res.program)
    assert exe.stats.streamed and exe.stats.swaps > 0
    assert exe.stats.segments == len(res.plan.segments)
    assert exe.stats.matmul_nodes == 0     # pool path, no matmul shortcut
    rep = compile_and_verify(g, arch, batch=2)
    assert rep.ok and rep.lower_s > 0.0    # executor path, bit-exact


def test_time_multiplexed_tenant_streams_weight_updates():
    """A tenant demoted to time-multiplexed residency compiles to a
    multi-segment plan and serves through the streaming executor."""
    from repro.serving import TenantSpec, plan_tenancy
    from repro.serving.engine import EnginePool
    from repro.workloads import get_workload
    arch = _small_arch()
    tenants = [TenantSpec("cnn", get_workload("tiny_cnn"), traffic=1.0),
               TenantSpec("mlp", get_workload("tiny_mlp"), traffic=3.0)]
    plan = plan_tenancy(tenants, arch, force_multiplexed=["cnn"])
    assert not plan.tenants["cnn"].resident
    pool = EnginePool(plan)
    svc = pool["cnn"]
    assert svc.use_executor                # no interpreter fallback
    stats = svc.executor_stats
    assert stats.segments > 1 and stats.streamed and stats.swaps > 0
    assert stats.kernel_mode in backend.MODES


# ------------------------------------------- fault-map conformance

def _fault_conformance(g, arch, model):
    """Interpreter and trace-lowered executor must agree bit for bit
    under the same seeded fault map (and the map must actually bite)."""
    from repro.cimsim.executor import lower
    from repro.cimsim.faults import FaultMap
    from repro.cimsim.functional import (FunctionalSimulator,
                                         calibrate_shifts, make_input,
                                         make_weights)
    from repro.core import compiler
    from repro.kernels.cim_mvm import cim_mvm_params
    params = cim_mvm_params(arch)
    weights, inputs = make_weights(g, 0), make_input(g, 0)
    shifts = calibrate_shifts(g, weights, inputs, params)
    res = compiler.compile_graph(g, arch, expand=True)
    sim = FunctionalSimulator(res.plan, res.program, weights, shifts,
                              params=params,
                              faults=FaultMap(model, arch))
    sim_out = sim.run(inputs)
    res2 = compiler.compile_graph(g, arch)
    exe = lower(res2.plan, res2.program, params=params,
                faults=FaultMap(model, arch), cache=False)
    exe_out = exe.run(inputs, weights, shifts)
    clean = lower(res2.plan, res2.program, params=params, cache=False) \
        .run(inputs, weights, shifts)
    changed = False
    for t in g.outputs:
        np.testing.assert_array_equal(sim_out[t], exe_out[t])
        changed = changed or not np.array_equal(clean[t], exe_out[t])
    assert changed, "fault map left every output untouched"
    return exe


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 40))
def test_conformance_faults_every_class(seed):
    """Every fault class at once (stuck cells/columns, dead lines,
    drift, ADC offset) on the saturating small arch: the interpreter
    and the executor stay bit-exact for any seed."""
    from repro.cimsim.faults import FaultModel
    from repro.workloads import get_workload
    model = FaultModel(seed=seed, stuck_cell_rate=0.01,
                       stuck_col_rate=0.01, dead_row_rate=0.01,
                       dead_col_rate=0.01, drift_sigma=0.3,
                       adc_offset_sigma=0.5)
    _fault_conformance(get_workload("tiny_mlp"), _small_arch(), model)


def test_conformance_faults_streamed_and_exact_paths():
    """The same fault map folds identically into the exact-matmul path
    (wide isaac tiles) and the streamed multi-segment path (1-xb
    cores), with the post-MVM offset term applied per span in both."""
    from repro.core.abstraction import CoreTier, get_arch
    from repro.cimsim.faults import FaultModel
    from repro.workloads import get_workload
    model = FaultModel(seed=11, stuck_cell_rate=0.01, stuck_col_rate=0.01,
                       dead_row_rate=0.01, adc_offset_sigma=0.5)
    g = get_workload("tiny_cnn")
    exe = _fault_conformance(g, get_arch("isaac-baseline"), model)
    assert exe.stats.matmul_nodes > 0          # exact path exercised
    arch = _small_arch().replace(
        core=CoreTier(xb_number=(1, 1), l1_bw_bits=1024))
    exe = _fault_conformance(g, arch, model)
    assert exe.stats.streamed                  # streamed path exercised
