"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Interpret mode (the CPU path every other test takes) never runs the TPU
compiler, so a BlockSpec the chip refuses passes every parity test.
These tests lower each main-path kernel at real widths for one chip of
a ``v5e:2x2`` topology that is described, not attached, and compile it
with the installed TPU compiler.  Nothing runs; a refused block shape,
an over-budget VMEM request or a program that does not fit the chip
fails here at no chip time.

The topology is described inside a module-scoped fixture (never at
import, in a ``skipif`` or in ``parametrize``): only one process may
load the TPU library at a time, and pytest-xdist workers import every
test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# device memory one TPU v5e chip makes available: ``bytes_limit`` of
# ``jax.devices()[0].memory_stats()`` on a v5e (a little under 16 GiB)
HBM_BYTES = 16909336064

# gemma-2b attention widths (src/repro/configs/gemma_2b.py): MQA, 8
# query heads on 1 KV head of 256; and a GQA shape with 8 KV heads of
# 128 (llama-style), the case a one-head page block cannot express
GEMMA = dict(hkv=1, g=8, d=256)
GQA8 = dict(hkv=8, g=4, d=128)
# GQA8 at the bucket every step of the benchmark's mistral-7b-l8.longdoc
# cell runs in: 128 flat tokens, 256 pages of 16 per sequence, 16 slots
LONGDOC = dict(GQA8, t=128, p=256, slots=16)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without one; keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("widths", [GEMMA, GQA8, LONGDOC],
                         ids=["gemma2b", "gqa8", "longdoc"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("ppt", [1, 8])
def test_paged_attention_compiles(one_chip, no_cache, widths, kv_dtype,
                                  ppt):
    from repro.kernels.decode_attention import paged_attention_fwd
    hkv, g, d = widths["hkv"], widths["g"], widths["d"]
    t, n_pages, ps = widths.get("t", 64), 2048, 16
    slots, p = widths.get("slots", 8), widths.get("p", 32)
    pool_dt = jnp.bfloat16 if kv_dtype == "bf16" else jnp.int8
    args = [_sds((t, hkv, g, d), jnp.bfloat16, one_chip),
            _sds((n_pages, ps, hkv, d), pool_dt, one_chip),
            _sds((n_pages, ps, hkv, d), pool_dt, one_chip),
            _sds((slots, p), jnp.int32, one_chip),
            _sds((t,), jnp.int32, one_chip),
            _sds((t,), jnp.int32, one_chip)]
    if kv_dtype == "int8":
        args += [_sds((n_pages, ps, hkv), jnp.float32, one_chip)] * 2

        def fn(q, kp, vp, tb, seg, pos, ks, vs):
            return paged_attention_fwd(q, kp, vp, tb, seg, pos,
                                       scale=d ** -0.5, k_scale=ks,
                                       v_scale=vs, pages_per_tile=ppt)
    else:
        def fn(q, kp, vp, tb, seg, pos):
            return paged_attention_fwd(q, kp, vp, tb, seg, pos,
                                       scale=d ** -0.5,
                                       pages_per_tile=ppt)
    compiled = _compile(fn, *args)
    assert "paged_attention_fwd" in compiled.as_text()


def test_mixed_attention_compiles(one_chip, no_cache):
    from repro.kernels.decode_attention import mixed_attention_fwd
    hkv, g, d = GEMMA["hkv"], GEMMA["g"], GEMMA["d"]
    t, slots, length = 64, 8, 512
    args = [_sds((t, hkv, g, d), jnp.bfloat16, one_chip),
            _sds((slots, hkv, length, d), jnp.bfloat16, one_chip),
            _sds((slots, hkv, length, d), jnp.bfloat16, one_chip),
            _sds((t,), jnp.int32, one_chip),
            _sds((t,), jnp.int32, one_chip)]
    compiled = _compile(
        lambda q, k, v, seg, pos: mixed_attention_fwd(
            q, k, v, seg, pos, scale=d ** -0.5), *args)
    assert "mixed_attention_fwd" in compiled.as_text()


def test_gemma_2b_step_compiles_and_fits(one_chip, no_cache, monkeypatch):
    """The whole serving step as ``launch/serve.py --config gemma-2b``
    builds it (18 layers, d_model 2048, vocab 256000, bf16), with a
    2048-page pool, weights as operands, compiled from abstract shapes:
    it runs the paged kernel, and its arguments, outputs and
    temporaries fit one chip's HBM."""
    import functools

    from repro.kernels import ops
    from repro.launch.serve import model_config
    from repro.models.lm import init_params
    from repro.serving import executor as ex_mod

    # lower the kernels for the chip, not interpreted for this backend
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    # the executor splits the stacked weights per layer; on abstract
    # weights the split has to be traced
    split = ex_mod.serving_params
    monkeypatch.setattr(ex_mod, "serving_params", lambda c, p: jax.eval_shape(
        functools.partial(split, c), p))
    cfg = model_config(None, "gemma-2b")
    ex = ex_mod.Executor(cfg, jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.key(0)))

    def sds(shape, dtype=jnp.int32):
        return _sds(shape, dtype, one_chip)

    n_pages, ps, t, slots, p = 2048, 16, 8, 4, 32
    pool = [sds((n_pages, ps, cfg.n_kv_heads, cfg.hd), cfg.param_dtype)
            for _ in range(cfg.n_layers)]
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                    ex._params)
    compiled = ex._step.lower(
        p, params, pool, list(pool), [], [], sds((t,)), sds((t,)),
        sds((t,)), sds((t,)), sds((slots, p)), sds((slots, 1)),
        sds((slots,)), sds((slots,), jnp.float32), sds((slots,)),
        sds((slots,), jnp.float32), sds((slots,))).compile()
    assert "paged_attention_fwd" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, total


def test_fused_elementwise_compiles_at_vocab(one_chip, no_cache):
    """A fused elementwise chain (the eager fusion queue's kernel) over
    rows as wide as gemma-2b's vocab."""
    from repro.kernels.ops import fused_elementwise
    rows, vocab = 8, 256000
    args = [_sds((rows, vocab), jnp.float32, one_chip)] * 2
    compiled = _compile(
        lambda lg, u: fused_elementwise(
            lambda a, b: a + -jnp.log(-jnp.log(b)), lg, u,
            interpret=False), *args)
    assert "tpu_custom_call" in compiled.as_text()
