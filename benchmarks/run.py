"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Sections:
  table1      — Table 1 training throughput (eager vs compiled)
  dispatch    — eager fast path: dispatch cache cold/warm, elementwise
                fusion on/off, foreach vs per-leaf optimizer
  runtime     — Fig. 1 async dispatch, Fig. 2 caching allocator,
                §5.5 refcount memory, §5.4 dataloader transport
  serving     — scheduler/executor engine vs the legacy monolith on the
                mixed workload + kernel wall-times (CPU interpret)

Output: ``name,us_per_call,derived`` CSV on stdout.
"""

import argparse

from .common import header


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", default=True)
    ap.add_argument("--sections",
                    default="table1,dispatch,runtime,serving")
    args = ap.parse_args()
    sections = set(args.sections.split(","))

    header()
    if "table1" in sections:
        from . import bench_table1
        bench_table1.run(quick=args.quick)
    if "dispatch" in sections:
        from . import bench_dispatch
        bench_dispatch.run(quick=args.quick)
    if "runtime" in sections:
        from . import bench_runtime
        bench_runtime.run(quick=args.quick)
    if "serving" in sections:
        from . import bench_serving
        bench_serving.run(quick=args.quick)


if __name__ == "__main__":
    main()
