#!/usr/bin/env python3
"""Compile a cell's serving step for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/aot.py --workload mistral-7b-l8.longdoc

Builds the program's ``Executor`` on abstract weights of the cell's
configuration and lowers its ``unified_step`` for the largest (tokens,
pages) bucket the cell's traffic reaches, against one chip of a
described ``v5e:2x2``, with the Pallas kernels compiled for the chip.
Prints the compiler's memory analysis beside the pool and weight bytes
of the configuration: what the TPU compiler refuses here costs no chip
time.
"""

import argparse
import functools
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import cell as cellmod, spec, weights
    from repro.kernels import ops
    from repro.serving import executor as ex_mod

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.find_cell(ROOT, args.workload)
    config, traffic = cell.config, cell.traffic
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    ops._interpret = lambda: False           # lower the kernel for the chip
    split = ex_mod.serving_params
    ex_mod.serving_params = lambda c, p: jax.eval_shape(
        functools.partial(split, c), p)
    cfg = cellmod.lm_config(config)
    dims = weights.Dims.of(config)
    abstract = jax.eval_shape(lambda: weights.program_params(dims, 0))
    ex = ex_mod.Executor(cfg, abstract)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    s = config["serve"]
    slots = traffic["engine"]["max_batch"]
    t = 1 << (max(traffic["engine"]["chunk_size"], 2 * slots) - 1).bit_length()
    p = 1 << (cellmod.max_pages(config, traffic) - 1).bit_length()
    pool = [sds((s["num_pages"], s["page_size"], cfg.n_kv_heads, cfg.hd),
                cfg.param_dtype) for _ in range(cfg.n_layers)]
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                    ex._params)
    compiled = ex._step.lower(
        p, params, pool, list(pool), [], [], sds((t,)), sds((t,)),
        sds((t,)), sds((t,)), sds((slots, p)), sds((slots, 1)),
        sds((slots,)), sds((slots,), jnp.float32), sds((slots,)),
        sds((slots,), jnp.float32), sds((slots,), jnp.uint32)).compile()
    ma = compiled.memory_analysis()

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree))
    weight_bytes = nbytes(abstract)
    layer_bytes = nbytes(abstract["groups"])
    pool_bytes = 2 * nbytes(pool)
    print(json.dumps({
        "workload": cell.name, "bucket": [t, p],
        "kernel_in_step": "paged_attention_fwd" in compiled.as_text(),
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "weight_bytes": weight_bytes, "pool_bytes": pool_bytes,
        # the engine holds the stacked layers and their split at once
        "build_peak_bytes": weight_bytes + layer_bytes + pool_bytes},
        indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
