"""Smoke run of the served CIM path on one TPU chip.

One process, these phases in order:

  1. device  — JAX must find a TPU; any other platform exits non-zero.
  2. golden  — the committed ``tests/golden/cim_mvm/*.npz`` fixtures
               replay bit-exactly on the compiled Pallas route.
  3. isaac   — ResNet-18 at 224x224 on ``isaac-baseline`` (exact-ADC
               matmul path), served through ``CimFleet``.
  4. puma    — the same on ``puma`` (saturating-ADC Pallas kernel,
               multi-segment weight streaming).

Each served phase sends 16 seeded requests to one tenant (batches of 8)
and checks every output bit for bit against ``reference_forward``, the
NumPy int8 reference (its saturating-ADC oracle runs on the host CPU).
It also checks that the executor served (not the interpreter) on the
``compiled`` route.  Weights and inputs come from seeds.

Times are printed for information only: set-up (plan, compile, lower,
pack), the first dispatch of the batch shape (jit compile included)
and the mean steady dispatch, each ending on the outputs' host copy.

The last line of standard output is one JSON object::

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage: ``python chip_smoke.py`` (keeps JAX's compile cache where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/``).
"""
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# the reference runs on the host CPU backend: keep it available where the
# platform list is pinned (an explicit list still fails loudly when its
# accelerator cannot start; the device phase checks the default device)
_PLATFORMS = os.environ.get("JAX_PLATFORMS", "")
if _PLATFORMS and "cpu" not in _PLATFORMS.split(","):
    os.environ["JAX_PLATFORMS"] = _PLATFORMS + ",cpu"

IN_HW = 224
N_REQUESTS = 16
MAX_BATCH = 8
SEED = 0


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_phase():
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"devices: {devices}")
    print(f"platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX platform is {dev.platform!r}, not "
                 "'tpu': no accelerator found")
    return dev


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def golden_phase() -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.cim_mvm import (CimMvmParams, cim_mvm,
                                       cim_mvm_signed, cim_mvm_tiles)
    entry = {"cim_mvm": cim_mvm, "cim_mvm_tiles": cim_mvm_tiles,
             "cim_mvm_signed": cim_mvm_signed}
    paths = sorted((ROOT / "tests" / "golden" / "cim_mvm").glob("*.npz"))
    check(len(paths) == 6, f"expected 6 golden fixtures, found {len(paths)}")
    for path in paths:
        z = np.load(path)
        params = CimMvmParams(*(int(v) for v in z["params"]))
        t0 = time.perf_counter()
        got = np.asarray(entry[str(z["kind"])](
            jnp.asarray(z["x"]), jnp.asarray(z["w"]), params,
            mode="compiled"))
        dt = time.perf_counter() - t0
        check(got.shape == z["y"].shape and np.array_equal(got, z["y"]),
              f"golden {path.stem}: compiled route differs from the fixture")
        print(f"golden {path.stem}: bit-exact on compiled "
              f"({dt:.3f} s incl. compile)")


def serve_phase(preset: str):
    """Serve seeded ResNet-18 requests through ``CimFleet`` on ``preset``
    and check them against the int8 reference; returns the executor
    stats and timings."""
    import jax
    import numpy as np
    from repro.cimsim.functional import (make_input, make_weights,
                                         reference_forward, reference_mvm)
    from repro.core.abstraction import get_arch
    from repro.kernels.cim_mvm import cim_mvm_params
    from repro.obs import metrics as obs_metrics
    from repro.serving import CimFleet, CimRequest, TenantSpec
    from repro.workloads import get_workload

    graph = get_workload("resnet18", in_hw=IN_HW)
    arch = get_arch(preset)
    reg = obs_metrics.enable()
    try:
        t0 = time.perf_counter()
        fleet = CimFleet([TenantSpec("resnet18", graph)], arch, seed=SEED,
                         buckets=(MAX_BATCH,), max_wait_s=0.0)
        setup_s = time.perf_counter() - t0
    finally:
        obs_metrics.disable()
    svc = fleet.pool["resnet18"]
    stats = svc.executor_stats
    check(stats is not None, f"{preset}: the executor did not serve")

    reqs = [CimRequest(rid=i, model="resnet18",
                       inputs=make_input(graph, SEED + 1 + i))
            for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    done = fleet.serve(reqs, now=0.0)
    serve_wall_s = time.perf_counter() - t0
    st = svc.stats
    check(len(done) == N_REQUESTS and st.batches == N_REQUESTS // MAX_BATCH,
          f"{preset}: served {len(done)} requests in {st.batches} batches")

    # the reference: seeded weights, shifts calibrated on the seed input
    # as compile_and_verify does, the ADC oracle on the host CPU
    params = cim_mvm_params(arch)
    check(params == svc.params, f"{preset}: service params {svc.params}")
    weights = make_weights(graph, SEED)
    mvm = reference_mvm(params)
    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        _, shifts = reference_forward(graph, weights,
                                      make_input(graph, SEED), mvm=mvm)
        check(shifts == svc.shifts, f"{preset}: calibration differs")
        for r in done:
            ref, _ = reference_forward(graph, weights, r.inputs,
                                       shifts=shifts, mvm=mvm)
            for t in graph.outputs:
                got = np.asarray(r.outputs[t])
                check(got.shape == ref[t].shape
                      and np.array_equal(got, ref[t]),
                      f"{preset}: request {r.rid} output {t} differs from "
                      "reference_forward")
    ref_s = time.perf_counter() - t0

    hists = reg.snapshot()["histograms"]

    def hist_sum(name):
        return sum(h["sum"] for series, h in hists.items()
                   if series.split("{")[0] == name)

    times = {
        "setup_s": setup_s,
        "compile_s": hist_sum("compile_wall_s"),
        "lower_s": hist_sum("executor_lower_s"),
        "pack_s": hist_sum("executor_pack_s"),
        "first_dispatch_s": serve_wall_s - st.serve_s,
        "steady_dispatch_s": st.serve_s / st.batches,
        "reference_s": ref_s,
    }
    return stats, times


def main() -> None:
    dev = device_phase()

    from repro.kernels import backend
    print(f"jax compile cache: {backend.enable_compile_cache()}")

    golden_phase()
    print(f"peak_bytes_in_use after golden: {peak_bytes(dev)}")

    for preset in ("isaac-baseline", "puma"):
        stats, times = serve_phase(preset)
        check(stats.kernel_mode == "compiled",
              f"{preset}: executor route is {stats.kernel_mode!r}")
        print(f"{preset}: resnet18@{IN_HW} x {N_REQUESTS} requests via "
              f"CimFleet bit-exact vs reference_forward; route="
              f"{stats.kernel_mode} segments={stats.segments} "
              f"swaps={stats.swaps} dispatches={stats.dispatches} "
              f"matmul_nodes={stats.matmul_nodes}")
        print(f"{preset}: seconds (informational) "
              + " ".join(f"{k}={v:.3f}" for k, v in times.items()))
        print(f"peak_bytes_in_use after {preset}: {peak_bytes(dev)}")

    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
