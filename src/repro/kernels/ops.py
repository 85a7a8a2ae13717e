"""Public, jit-ready wrappers around the Pallas kernels.

Each op:
  * normalizes layouts (GQA head grouping, lane-width padding),
  * runs the Pallas kernel: natively on TPU, in interpret mode on the
    CPU backend only (tests), so any other backend reaches the Pallas
    compiler and fails loudly instead of interpreting in silence,
  * exposes a ``jax.custom_vjp``: forward = kernel, backward = JAX AD
    through the ``ref.py`` oracle with recomputation (flash-style
    recompute; a fused backward kernel is a further optimization noted in
    DESIGN.md).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .decode_attention import (decode_attention_fwd, mixed_attention_fwd,
                               paged_attention_fwd, query_block_size)
from .flash_attention import flash_attention_fwd
from .mamba import mamba_scan_fwd
from .rwkv6 import rwkv6_scan_fwd

LANE = 128


@functools.cache
def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_last(x: jnp.ndarray, to: int) -> jnp.ndarray:
    d = x.shape[-1]
    if d % to == 0:
        return x
    pad = to - d % to
    cfg = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, cfg)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, scale: Optional[float] = None,
                    window: Optional[int] = None) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D).  GQA-aware."""
    return _flash_fwd_impl(q, k, v, causal, scale, window)


def _flash_fwd_impl(q, k, v, causal, scale, window):
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    eff_scale = scale if scale is not None else d ** -0.5

    qp = _pad_last(q, LANE)
    kp = _pad_last(k, LANE)
    vp = _pad_last(v, LANE)
    dp = qp.shape[-1]

    out = flash_attention_fwd(
        qp.reshape(b * hq, sq, dp),
        kp.reshape(b * hkv, skv, dp),
        vp.reshape(b * hkv, skv, dp),
        causal=causal, scale=eff_scale, window=window,
        interpret=_interpret())
    return out.reshape(b, hq, sq, dp)[..., :d]


def _flash_fwd(q, k, v, causal, scale, window):
    return _flash_fwd_impl(q, k, v, causal, scale, window), (q, k, v)


def _flash_bwd(causal, scale, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention(
            q_, k_, v_, causal=causal, scale=scale, window=window),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ----------------------------------------------------------------------
# decode attention
# ----------------------------------------------------------------------

def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cache_len,
                     scale: Optional[float] = None,
                     window: Optional[int] = None) -> jnp.ndarray:
    """q: (B, Hq, 1, D) vs cache (B, Hkv, Smax, D), cache_len scalar or
    (B,).  Inference-only (no vjp)."""
    b, hq, one, d = q.shape
    _, hkv, smax, _ = k_cache.shape
    g = hq // hkv
    eff_scale = scale if scale is not None else d ** -0.5

    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1),
                            (b,))
    qg = _pad_last(q.reshape(b, hkv, g, d), LANE)
    kp = _pad_last(k_cache, LANE)
    vp = _pad_last(v_cache, LANE)

    out = decode_attention_fwd(qg, kp, vp, lens, scale=eff_scale,
                               window=window, interpret=_interpret())
    return out[..., :d].reshape(b, hq, 1, d)


# ----------------------------------------------------------------------
# mixed prefill/decode attention (serving unified step)
# ----------------------------------------------------------------------

def mixed_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, seg_ids: jnp.ndarray,
                    positions: jnp.ndarray,
                    scale: Optional[float] = None,
                    window: Optional[int] = None) -> jnp.ndarray:
    """q: (T, Hq, D) flat token batch vs per-slot caches (S, Hkv, L, D);
    seg_ids/positions (T,) int32.  Inference-only (no vjp)."""
    t, hq, d = q.shape
    _, hkv, smax, _ = k_cache.shape
    g = hq // hkv
    eff_scale = scale if scale is not None else d ** -0.5

    qg = _pad_last(q.reshape(t, hkv, g, d), LANE)
    kp = _pad_last(k_cache, LANE)
    vp = _pad_last(v_cache, LANE)

    out = mixed_attention_fwd(
        qg, kp, vp, jnp.asarray(seg_ids, jnp.int32),
        jnp.asarray(positions, jnp.int32), scale=eff_scale,
        window=window, interpret=_interpret())
    return out[..., :d].reshape(t, hq, d)


# ----------------------------------------------------------------------
# paged attention (serving unified step, block table on device)
# ----------------------------------------------------------------------

def default_pages_per_tile(page_size: int, p_pages: int) -> int:
    """Static multi-page tile width: pack pages until a tile covers
    ~DEFAULT_BLOCK_K key positions (capped at 8 refs to bound the
    unrolled kernel body), so small-page configs don't pay one grid
    step per page.  fp32 outputs are bitwise-identical across tile
    sizes (the kernel unrolls the exact per-page update sequence)."""
    from .decode_attention import DEFAULT_BLOCK_K
    return max(1, min(8, DEFAULT_BLOCK_K // max(page_size, 1), p_pages))


def paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                    v_pages: jnp.ndarray, tables: jnp.ndarray,
                    seg_ids: jnp.ndarray, positions: jnp.ndarray,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    k_scale: Optional[jnp.ndarray] = None,
                    v_scale: Optional[jnp.ndarray] = None,
                    pages_per_tile: Optional[int] = None) -> jnp.ndarray:
    """q: (T, Hq, D) flat token batch vs the PHYSICAL page pool
    (N, ps, Hkv, D); tables (S, P), seg_ids/positions (T,) int32.

    The kernel cuts each slot's tokens into query blocks of
    :func:`query_block_size` tokens, one grid row each, and fetches a
    page once per block.  ``Scheduler._pad`` lays each slot's tokens
    out as ONE contiguous span of the flat batch (speculative drafts
    included) and the padding after them, so a block is a run of
    consecutive tokens and the kernel launches ``cdiv(len, BQ)``
    blocks per span; any other layout is still attended correctly.
    The table and each block's descriptors ride as scalar-prefetch
    operands so the kernel's index maps resolve slot -> page id before
    each body runs.  A quantized pool (int8 /
    fp8_e4m3 codes) passes (N, ps, Hkv) fp32 ``k_scale``/``v_scale``;
    dequantization happens inside the kernel.  ``pages_per_tile``
    (default: :func:`default_pages_per_tile`) packs several pages per
    grid step.  Inference-only (no vjp)."""
    t, hq, d = q.shape
    _, ps, hkv, _ = k_pages.shape
    g = hq // hkv
    eff_scale = scale if scale is not None else d ** -0.5
    if pages_per_tile is None:
        pages_per_tile = default_pages_per_tile(ps, tables.shape[1])

    qg = _pad_last(q.reshape(t, hkv, g, d), LANE)
    kp = _pad_last(k_pages, LANE)         # zero codes: dequant to 0
    vp = _pad_last(v_pages, LANE)

    out = paged_attention_fwd(
        qg, kp, vp, jnp.asarray(tables, jnp.int32),
        jnp.asarray(seg_ids, jnp.int32),
        jnp.asarray(positions, jnp.int32), scale=eff_scale,
        window=window, k_scale=k_scale, v_scale=v_scale,
        pages_per_tile=pages_per_tile, interpret=_interpret())
    return out[..., :d].reshape(t, hq, d)


# ----------------------------------------------------------------------
# rwkv6
# ----------------------------------------------------------------------

@jax.custom_vjp
def rwkv6_scan(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               w: jnp.ndarray, u: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r/k/v/w: (B, H, S, D); u: (H, D) bonus.
    Returns (out (B,H,S,D), state (B,H,D,D))."""
    return _rwkv6_impl(r, k, v, w, u)


def _rwkv6_impl(r, k, v, w, u):
    b, h, s, d = r.shape
    dp = ((d + LANE - 1) // LANE) * LANE

    def prep(x):
        return _pad_last(x, LANE).reshape(b * h, s, dp)

    rp, kp, vp = prep(r), prep(k), prep(v)
    # pad decay with ONES so padded state stays zero but stable
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, dp - d)),
                 constant_values=1.0).reshape(b * h, s, dp)
    up = jnp.broadcast_to(_pad_last(u, LANE)[None], (b, h, dp)) \
        .reshape(b * h, dp)

    out, state = rwkv6_scan_fwd(rp, kp, vp, wp, up,
                                interpret=_interpret())
    out = out.reshape(b, h, s, dp)[..., :d]
    state = state.reshape(b, h, dp, dp)[..., :d, :d]
    return out, state


def _rwkv6_fwd(r, k, v, w, u):
    return _rwkv6_impl(r, k, v, w, u), (r, k, v, w, u)


def _rwkv6_bwd(res, g):
    r, k, v, w, u = res
    _, vjp = jax.vjp(lambda *a: ref.rwkv6_scan(*a), r, k, v, w, u)
    return vjp(g)


rwkv6_scan.defvjp(_rwkv6_fwd, _rwkv6_bwd)


# ----------------------------------------------------------------------
# mamba selective scan
# ----------------------------------------------------------------------

@jax.custom_vjp
def mamba_scan(x: jnp.ndarray, dt: jnp.ndarray, B: jnp.ndarray,
               C: jnp.ndarray, A: jnp.ndarray,
               D: jnp.ndarray) -> jnp.ndarray:
    """x/dt: (B, S, Di); B/C: (B, S, N); A: (Di, N); D: (Di,)."""
    return mamba_scan_fwd(x, dt, B, C, A, D, interpret=_interpret())


def _mamba_fwd(x, dt, B, C, A, D):
    return mamba_scan_fwd(x, dt, B, C, A, D, interpret=_interpret()), \
        (x, dt, B, C, A, D)


def _mamba_bwd(res, g):
    x, dt, B, C, A, D = res
    _, vjp = jax.vjp(lambda *a: ref.mamba_scan(*a), x, dt, B, C, A, D)
    return vjp(g)


mamba_scan.defvjp(_mamba_fwd, _mamba_bwd)


# ----------------------------------------------------------------------
# fused elementwise chain (the fusion-queue lowering target)
# ----------------------------------------------------------------------

_EW_SUBLANE = 8       # f32 sublane granularity
_EW_BLOCK_ROWS = 256  # 256x128xf32 = 128KB per operand tile in VMEM


def fused_elementwise(fn, *xs, interpret: Optional[bool] = None):
    """Run an elementwise composite ``fn(*xs)`` as ONE Pallas kernel.

    ``fn`` may return one array or a tuple (a fusion-queue chain
    materializes every step output).  All operands and outputs must share
    a shape; the composite is applied blockwise over a (rows, 128)
    lane-major view of the raveled data — the padded tail goes through
    ``fn`` and is sliced off (elementwise, so garbage in the pad never
    contaminates real lanes).  Falls back to a plain call for
    scalars/odd layouts.
    """
    from jax.experimental import pallas as pl

    interpret = _interpret() if interpret is None else interpret
    x0 = xs[0]
    shape = x0.shape
    n = int(np.prod(shape)) if shape else 1
    out_avals = jax.eval_shape(fn, *xs)
    single = not isinstance(out_avals, tuple)
    outs = (out_avals,) if single else out_avals
    if (n == 0 or any(x.shape != shape for x in xs)
            or any(o.shape != shape for o in outs)):
        return fn(*xs)

    rows = -(-n // LANE)
    block_rows = min(_EW_BLOCK_ROWS,
                     -(-rows // _EW_SUBLANE) * _EW_SUBLANE)
    rows_p = -(-rows // block_rows) * block_rows
    pad = rows_p * LANE - n

    def prep(x):
        flat = x.reshape(-1)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return flat.reshape(rows_p, LANE)

    n_in = len(xs)

    def kernel(*refs):
        vals = fn(*[r[...] for r in refs[:n_in]])
        vals = (vals,) if not isinstance(vals, tuple) else vals
        for out_ref, v in zip(refs[n_in:], vals):
            out_ref[...] = v

    grid = (rows_p // block_rows,)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out2d = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * n_in,
        out_specs=[spec] * len(outs),
        out_shape=[jax.ShapeDtypeStruct((rows_p, LANE), o.dtype)
                   for o in outs],
        interpret=interpret,
    )(*[prep(x) for x in xs])
    result = tuple(o.reshape(-1)[:n].reshape(shape) for o in out2d)
    return result[0] if single else result


def make_fused_elementwise(fn):
    """Dispatch-cache ``wrap`` hook: jitted Pallas lowering of an
    elementwise composite (used by the fusion queue on TPU)."""
    return jax.jit(functools.partial(fused_elementwise, fn))
