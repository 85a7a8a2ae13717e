#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python3 benchmarks/chip/calibrate.py --workload mistral-7b-l8.longdoc \
        --seeds 11,12,13 --seconds 10 --controls int8,fp8

For each seed, in one process: a short window of the cell's own traffic
at its own load and sizes, then the program's gap against the plain
reference and, for each control precision, the gap of the reference
computed at that precision in the program's place (``chipbench.check``).
Prints one JSON line per seed, with each control's verdict: its gap
put in the program's place and held to the cell's limits by the same
``check.verdict``, so a sound limit reads ``"correct": false`` for every
control.  The benchmark's own runs never run the control.  The limit in
``cells/<cell>.json`` lies between the largest program reading and the
smallest control reading (PERF.md gives both).
"""

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args()
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    sys.argv = sys.argv[:1]
    import run as bench_run
    from chipbench import cell as C, spec
    bench_run.setup_jax()
    cell = spec.find_cell(ROOT, args.workload)
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = C.run_cell(cell, seed, args.seconds, False,
                         t_start=time.time(), work_dir=bench_run.WORK_DIR,
                         controls=controls)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "logit_gap": out["checks"]["logit_gap"]["value"],
                          "controls": out.get("controls", {}),
                          **out["readings"],
                          "attempted": out["attempted"],
                          "failed": out["failed"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
