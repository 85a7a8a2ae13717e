"""Decode + mixed-batch + paged attention kernels (Pallas/TPU).

``decode_attention_fwd``: one new query token per sequence attends over a
(B, Hkv, Smax, D) KV cache filled to ``cache_len[b]`` positions.
``mixed_attention_fwd``: a FLAT padded token batch (prefill chunks mixed
with decode tokens — the serving executor's unified step) where token t
selects its sequence's cache row via a scalar-prefetched segment id and
masks keys past its own position.
``paged_attention_fwd``: the same flat mixed batch, but attending the
PHYSICAL KV page pool directly — the block table rides in as a
scalar-prefetch operand and the KV BlockSpec index map resolves
(slot, page-position) -> physical page id before the body runs, so no
contiguous per-slot cache is ever gathered.  TPU adaptation of
flash-decoding:

  * grid = (B, Hkv, Smax/block_k) with the KV sweep as the sequential
    dimension; online-softmax stats live in VMEM scratch,
  * all G = Hq/Hkv query heads of a KV group are processed together as a
    (G, D) tile — the score matmul is (G, D)x(D, block_k), keeping the MXU
    busy even at batch 1,
  * ``cache_len`` is a scalar-prefetch operand (SMEM): block index maps and
    masks read it before the kernel body runs, so out-of-range KV tiles
    are masked with zero MXU waste.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 256
NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, window: Optional[int], block_k: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cache_len = len_ref[b]
    k_start = ki * block_k
    run = k_start < cache_len
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k > cache_len - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                               # (G, D)
        k = k_ref[0, 0]                               # (bk, D)
        v = v_ref[0, 0]
        scores = pl.dot(q, k, trans_b=True).astype(jnp.float32) * scale

        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        mask = k_pos < cache_len
        if window is not None:
            mask = jnp.logical_and(mask, k_pos >= cache_len - window)
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + pl.dot(
            p.astype(v.dtype), v).astype(jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def decode_attention_fwd(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, cache_len: jnp.ndarray, *,
                         scale: float, window: Optional[int] = None,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hkv, G, D) — query heads grouped by their KV head;
    k_cache/v_cache: (B, Hkv, Smax, D); cache_len: (B,) int32.
    Returns (B, Hkv, G, D)."""
    b, hkv, g, d = q.shape
    smax = k_cache.shape[2]
    block_k = min(block_k, smax)
    nk = pl.cdiv(smax, block_k)

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               block_k=block_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b, h, ki, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, ki, lens: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, ki, lens: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b, h, ki, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
        name="decode_attention_fwd",
    )(jnp.asarray(cache_len, jnp.int32), q, k_cache, v_cache)


def _mixed_kernel(seg_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  scale: float, window: Optional[int], block_k: int):
    t = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[t]
    k_start = ki * block_k
    # keys at <= pos are live; padding tokens (seg<0) read slot 0 but the
    # caller discards their output
    run = k_start <= pos
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k > pos - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                               # (G, D)
        k = k_ref[0, 0]                               # (bk, D)
        v = v_ref[0, 0]
        scores = pl.dot(q, k, trans_b=True).astype(jnp.float32) * scale

        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        mask = k_pos <= pos
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > pos - window)
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + pl.dot(
            p.astype(v.dtype), v).astype(jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def mixed_attention_fwd(q: jnp.ndarray, k_cache: jnp.ndarray,
                        v_cache: jnp.ndarray, seg_ids: jnp.ndarray,
                        positions: jnp.ndarray, *, scale: float,
                        window: Optional[int] = None,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False) -> jnp.ndarray:
    """q: (T, Hkv, G, D) — per-token query heads grouped by KV head;
    k_cache/v_cache: (S, Hkv, L, D) per-slot contiguous caches;
    seg_ids/positions: (T,) int32 scalar-prefetch operands.  The block
    index map routes each token's KV tiles from ITS slot's cache row —
    the paged-gather analogue of flash-decoding.  Returns (T, Hkv, G, D).
    """
    t, hkv, g, d = q.shape
    smax = k_cache.shape[2]
    block_k = min(block_k, smax)
    nk = pl.cdiv(smax, block_k)
    nslots = k_cache.shape[0]

    kernel = functools.partial(_mixed_kernel, scale=scale, window=window,
                               block_k=block_k)

    def kv_map(ti, h, ki, seg, pos):
        return (jnp.clip(seg[ti], 0, nslots - 1), h, ki, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t, hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda ti, h, ki, seg, pos: (ti, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
            pl.BlockSpec((1, 1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda ti, h, ki, seg, pos: (ti, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, hkv, g, d), q.dtype),
        interpret=interpret,
        name="mixed_attention_fwd",
    )(jnp.asarray(seg_ids, jnp.int32), jnp.asarray(positions, jnp.int32),
      q, k_cache, v_cache)


def _paged_kernel(tbl_ref, seg_ref, pos_ref, q_ref, *refs,
                  scale: float, window: Optional[int], page_size: int,
                  ppt: int, n_kv_heads: int, quantized: bool):
    # refs layout (set up by paged_attention_fwd): ppt K page refs,
    # ppt V page refs, [ppt K-scale refs, ppt V-scale refs when
    # quantized], then o_ref and the three VMEM scratch refs.  Each
    # page ref holds ALL KV heads of one page, (1, ps, Hkv, D); the
    # head loop below is static.
    k_refs = refs[:ppt]
    v_refs = refs[ppt:2 * ppt]
    if quantized:
        ks_refs = refs[2 * ppt:3 * ppt]
        vs_refs = refs[3 * ppt:4 * ppt]
        o_ref, m_scr, l_scr, acc_scr = refs[4 * ppt:]
    else:
        o_ref, m_scr, l_scr, acc_scr = refs[2 * ppt:]

    t = pl.program_id(0)
    ti_ = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(ti_ == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[t]
    # the tile packs ppt consecutive pages of token t's sequence; each
    # page j runs, per KV head, the SAME sequential online-softmax
    # update the single-page grid would, in the same order — fp32
    # outputs are bitwise-equal for any tile size.  Only pages at or
    # before the token's own position hold live keys (causal); a tile
    # page past the table width is index-clamped in the BlockSpec map
    # and its k_start > pos predicate skips the compute.  Padding
    # tokens (seg<0) route to page-table row 0 and the caller discards
    # their output.
    for j in range(ppt):
        k_start = (ti_ * ppt + j) * page_size
        run = k_start <= pos
        if window is not None:
            run = jnp.logical_and(run,
                                  k_start + page_size > pos - window)

        @pl.when(run)
        def _body(j=j, k_start=k_start):
            for h in range(n_kv_heads):
                q = q_ref[0, h]                       # (G, D)
                k = k_refs[j][0, :, h, :]             # (ps, D)
                v = v_refs[j][0, :, h, :]
                if quantized:
                    # dequantize IN KERNEL: codes × per-(token, head)
                    # scales — the fp32 pool never materializes in HBM
                    q = q.astype(jnp.float32)
                    k = k.astype(jnp.float32) \
                        * ks_refs[j][0, :, h][:, None]
                    v = v.astype(jnp.float32) \
                        * vs_refs[j][0, :, h][:, None]
                scores = pl.dot(q, k, trans_b=True).astype(jnp.float32) \
                    * scale

                k_pos = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, scores.shape, 1)
                mask = k_pos <= pos
                if window is not None:
                    mask = jnp.logical_and(mask, k_pos > pos - window)
                scores = jnp.where(mask, scores, NEG_INF)

                m_prev = m_scr[h, :, :1]
                m_new = jnp.maximum(
                    m_prev, jnp.max(scores, axis=-1, keepdims=True))
                p = jnp.exp(scores - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_scr[h] = jnp.broadcast_to(
                    alpha * l_scr[h, :, :1]
                    + jnp.sum(p, axis=-1, keepdims=True),
                    l_scr.shape[1:])
                acc_scr[h] = acc_scr[h] * alpha + pl.dot(
                    p.astype(v.dtype), v).astype(jnp.float32)
                m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])

    @pl.when(ti_ == nt - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, :, :1], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention_fwd(q: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, tables: jnp.ndarray,
                        seg_ids: jnp.ndarray, positions: jnp.ndarray, *,
                        scale: float, window: Optional[int] = None,
                        k_scale: Optional[jnp.ndarray] = None,
                        v_scale: Optional[jnp.ndarray] = None,
                        pages_per_tile: int = 1,
                        interpret: bool = False) -> jnp.ndarray:
    """q: (T, Hkv, G, D) — per-token query heads grouped by KV head;
    k_pages/v_pages: (N, ps, Hkv, D) — the PHYSICAL page pool, not a
    gathered per-slot cache; tables: (S, P) int32 block tables;
    seg_ids/positions: (T,) int32.  All three index operands are
    scalar-prefetched: the KV BlockSpec index map reads
    ``tables[seg_ids[t], pi]`` before the body runs, so each grid step
    DMAs exactly the physical pages it attends into VMEM — the gather
    disappears into the memory system.

    The grid is (T, page tiles).  A page block carries every KV head of
    its page, (1, ps, Hkv, D): its last two dims equal the array's, the
    form the TPU compiler accepts for any Hkv (a one-head block,
    (1, ps, 1, D), is refused whenever Hkv > 1).  The kernel loops over
    the heads statically, so each page is fetched once per token.

    Quantized pools pass ``k_scale``/``v_scale``: (N, ps, Hkv) fp32
    per-(token, head) scales.  They ride the SAME table-prefetch
    routing as the pages — their BlockSpecs share the page index, so
    the scale rows for a page arrive with the page and dequantization
    happens in VMEM, never materializing an fp32 pool.

    ``pages_per_tile`` statically packs several pages into one grid
    step (ppt K refs + ppt V refs resolved per-page in the index maps);
    the kernel unrolls the identical per-page online-softmax update, so
    fp32 outputs are BITWISE-equal across tile sizes while small-page
    configs stop paying per-page grid overhead.  Returns (T, Hkv, G, D).
    """
    t, hkv, g, d = q.shape
    n_pages, ps = k_pages.shape[0], k_pages.shape[1]
    s_slots, p_pages = tables.shape
    ppt = max(1, min(pages_per_tile, p_pages))
    n_tiles = pl.cdiv(p_pages, ppt)
    quantized = k_scale is not None

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               page_size=ps, ppt=ppt, n_kv_heads=hkv,
                               quantized=quantized)

    def page_index(j, ti, tj, tbl, seg):
        slot = jnp.clip(seg[ti], 0, s_slots - 1)
        # pages past the table width clamp to the last column; the
        # kernel's k_start <= pos predicate masks their compute
        return tbl[slot, jnp.minimum(tj * ppt + j, p_pages - 1)]

    def page_map(j):
        def kv_map(ti, tj, tbl, seg, pos):
            return (page_index(j, ti, tj, tbl, seg), 0, 0, 0)
        return kv_map

    def scale_map(j):
        def sc_map(ti, tj, tbl, seg, pos):
            return (page_index(j, ti, tj, tbl, seg), 0, 0)
        return sc_map

    tok_spec = pl.BlockSpec((1, hkv, g, d),
                            lambda ti, tj, tbl, seg, pos: (ti, 0, 0, 0))
    in_specs = [tok_spec]
    in_specs += [pl.BlockSpec((1, ps, hkv, d), page_map(j))
                 for j in range(ppt)]
    in_specs += [pl.BlockSpec((1, ps, hkv, d), page_map(j))
                 for j in range(ppt)]
    operands = [q] + [k_pages] * ppt + [v_pages] * ppt
    if quantized:
        in_specs += [pl.BlockSpec((1, ps, hkv), scale_map(j))
                     for j in range(ppt)]
        in_specs += [pl.BlockSpec((1, ps, hkv), scale_map(j))
                     for j in range(ppt)]
        operands += [k_scale] * ppt + [v_scale] * ppt

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t, n_tiles),
        in_specs=in_specs,
        out_specs=tok_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 128), jnp.float32),
            pltpu.VMEM((hkv, g, 128), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, hkv, g, d), q.dtype),
        interpret=interpret,
        name="paged_attention_fwd",
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(seg_ids, jnp.int32),
      jnp.asarray(positions, jnp.int32), *operands)
