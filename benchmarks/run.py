"""Benchmark aggregator — one section per paper table/figure.  Prints
``name,value,note`` CSV."""
from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))


def main() -> None:
    from repro.kernels.backend import enable_compile_cache
    enable_compile_cache()

    import dse_sweep
    import faults_bench
    import fig20_generality
    import fig21_ablation
    import fig22_sensitivity
    import kernel_bench
    import obs_bench
    import serving_bench
    import simulator_bench

    sections = [
        ("fig20 (generality: Jia/PUMA/Jain/Poly-Schedule)",
         fig20_generality.rows),
        ("fig21 (ResNet multi-level ablation)", fig21_ablation.rows),
        ("fig22 (architecture sensitivity, ViT)", fig22_sensitivity.rows),
        ("kernels (cim_mvm)", kernel_bench.rows),
        ("simulator (interpreter vs trace-lowered executor)",
         simulator_bench.rows),
        ("dse (cross-tier sweep + compile cache)", dse_sweep.rows),
        ("serving (multi-tenant fleet vs sequential services)",
         serving_bench.rows),
        ("faults (injection accuracy + chip-kill failover)",
         faults_bench.rows),
        ("obs (telemetry overhead + explain coverage)", obs_bench.rows),
    ]
    print("name,value,note")
    for title, fn in sections:
        print(f"# --- {title} ---")
        t0 = time.time()
        for name, val, note in fn():
            print(f"{name},{val:.4g},{note}")
        print(f"# ({time.time()-t0:.1f}s)")


if __name__ == "__main__":
    main()
