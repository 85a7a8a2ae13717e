#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload mistral-7b-l8.longdoc \
        --seed 1234 --seconds 30 --trace 0

from the root of a checkout.  The cell, its configuration, traffic mix,
limits and per-layer metric readers are found by name from
``BENCHMARK.json`` (see ``chipbench/spec.py``).  ``--trace 0`` prints the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics, from a
profiler trace of the window.  Every run checks the served tokens
against the plain reference and prints each number compared beside its
limit, last on standard error and last in the result line.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device``.  Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits
with 3.  JAX's persistent compilation cache is kept in
``benchmarks/chip/.jax_cache`` of the checkout, so only a checkout's
first run of a cell compiles.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = HERE / ".jax_cache"
WORK_DIR = HERE / ".work"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax() -> None:
    """The compile cache inside the checkout, for every program."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    from chipbench import cell as cellmod, spec
    cell = spec.find_cell(ROOT, args.workload)
    setup_jax()
    import shutil
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    WORK_DIR.mkdir(parents=True)
    try:
        out = cellmod.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               work_dir=WORK_DIR)
    except cellmod.NoChip as e:
        print(f"[chipbench] {e}", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    print_checks(out["checks"])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
