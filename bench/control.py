"""The control of the benchmark's correctness check, at each cell's size.

    python3 bench/control.py --workloads <cell> [<cell> ...] --seeds 1 2 3

For each cell and seed, one process runs the cell as ``run.py`` does, with
a short window at the cell's own load, then puts the reference computed
with int4 crossbar operands (the precision below the configuration's
int8) in the served outputs' place and compares it like a served output.
Every such run has to come out not correct; it prints the compared
numbers, which are the control's readings.  The benchmark's own runs
never run this.
"""
import argparse
import json
import sys

import run

#: the operand precision of the control: the step below int8
CONTROL_BITS = 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workloads", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    plan_cache = run.open_caches()
    failures = 0
    for name in args.workloads:
        cell, cfg, mix, e2e, per_layer = run.load_cell(name)
        for seed in args.seeds:
            out = run.run_cell(cfg, mix, e2e, per_layer, seed=seed,
                               seconds=args.seconds, trace=False,
                               chips=cell["chips"], plan_cache=plan_cache,
                               control_bits=CONTROL_BITS)
            failures += out["correct"] is not False
            print(json.dumps({"workload": name, "seed": seed,
                              "control_bits": CONTROL_BITS,
                              "correct": out["correct"],
                              "checks": out["checks"]}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
