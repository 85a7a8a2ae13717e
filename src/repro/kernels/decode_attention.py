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
contiguous per-slot cache is ever gathered.  Its grid is
(query blocks, page tiles): a query block is up to ``BQ = 128 // G``
consecutive tokens of ONE sequence's span (a decode is a block of one
token, a prefill chunk or speculative span fills several), so each page
is fetched once per block and all ``BQ·G`` query rows of a KV head are
scored against it in one matmul.  TPU adaptation of flash-decoding:

  * the KV sweep is the sequential (last) grid dimension; online-softmax
    stats live in VMEM scratch,
  * all G = Hq/Hkv query heads of a KV group are processed together —
    the decode kernel's score matmul is (G, D)x(D, block_k), the paged
    kernel's (BQ·G, D)x(D, page), keeping the MXU busy even at batch 1,
  * lengths, positions and block descriptors are scalar-prefetch
    operands (SMEM): block index maps and masks read them before the
    kernel body runs, so out-of-range KV tiles are skipped with zero
    MXU waste and fetch nothing.
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
# rows of the MXU: the paged kernel's query block holds this many
# (token, query head) rows
MXU_ROWS = 128
# a query block with at most this many live rows (a decode, a short
# speculative span) updates only this slab of the block's rows
SMALL_ROWS = 16


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


def query_block_size(g: int, t: int) -> int:
    """Query tokens per grid row of :func:`paged_attention_fwd`: enough
    that a block's ``BQ·G`` query rows fill the MXU's 128 rows, capped
    at the flat batch's width ``t``."""
    return max(1, min(MXU_ROWS // max(g, 1), t))


def query_blocks(seg_ids: jnp.ndarray, positions: jnp.ndarray, *,
                 block_q: int, n_slots: int, n_blocks: int):
    """Split a flat token batch into query blocks of one sequence each.

    Tokens are grouped by the slot they read (``seg_ids`` clipped to
    ``[0, n_slots)``, as the reference clips them); the padding rows
    (``seg < 0``) form one more group, which reads slot 0.  Each
    group's tokens, in batch order, are cut into blocks of up to
    ``block_q``, so every token lands in exactly one block row and at
    most ``cdiv(T, block_q) + n_slots + 1`` blocks are used.  Where a
    slot's tokens are one contiguous span of the batch (the scheduler's
    layout), its blocks are that span's consecutive rows.  Returns

      * ``rows`` (n_blocks, block_q) int32: the token index of each
        block row, -1 where the row is unused,
      * ``row_pos`` (n_blocks, block_q) int32: each row's position, -1
        where unused,
      * ``slot``/``first``/``last`` (n_blocks,) int32: the block's slot
        and its least/greatest position (an unused block: 0, 0, -1),
      * ``tok_row`` (T,) int32: the flat ``b·block_q + r`` row of each
        token."""
    t = seg_ids.shape[0]
    group = jnp.where(seg_ids < 0, n_slots, jnp.minimum(seg_ids, n_slots - 1))
    onehot = (group[:, None] == jnp.arange(n_slots + 1)[None, :]
              ).astype(jnp.int32)                     # (T, S + 1)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), group[:, None],
                               axis=1)[:, 0] - 1      # rank in its group
    n_blk = -(-jnp.sum(onehot, axis=0) // block_q)    # blocks per group
    base = jnp.cumsum(n_blk) - n_blk
    blk = base[group] + rank // block_q
    r = rank % block_q
    idx = jnp.arange(t, dtype=jnp.int32)
    rows = jnp.full((n_blocks, block_q), -1, jnp.int32) \
        .at[blk, r].set(idx, mode="drop")
    used = rows >= 0
    row_pos = jnp.where(used, positions[jnp.maximum(rows, 0)], -1)
    slot = jnp.where(used[:, 0], group[jnp.maximum(rows[:, 0], 0)], 0)
    slot = jnp.where(slot == n_slots, 0, slot)
    first = jnp.min(jnp.where(used, row_pos, jnp.iinfo(jnp.int32).max),
                    axis=1)
    first = jnp.where(used[:, 0], first, 0)
    last = jnp.max(row_pos, axis=1)
    return rows, row_pos, slot, first, last, blk * block_q + r


def _paged_kernel(tbl_ref, slot_ref, first_ref, last_ref, nrow_ref,
                  q_ref, rpos_ref, cpos_ref, *refs,
                  scale: float, window: Optional[int], page_size: int,
                  ppt: int, n_kv_heads: int, quantized: bool,
                  small_rows: int):
    # refs layout (set up by paged_attention_fwd): ppt K page refs,
    # ppt V page refs, [ppt K-scale refs, ppt V-scale refs when
    # quantized], then o_ref and the VMEM scratch refs.  Each page ref
    # holds ALL KV heads of one page, (1, ps, Hkv, D); the head loop
    # below is static.  q_ref holds one query block, (1, Hkv, R, D)
    # with R = BQ·G rows (token-major, head-minor); rpos_ref holds each
    # row's position as a column, (1, R, 1), cpos_ref as a row, (1, 1, R).
    k_refs = refs[:ppt]
    v_refs = refs[ppt:2 * ppt]
    if quantized:
        ks_refs = refs[2 * ppt:3 * ppt]
        vs_refs = refs[3 * ppt:4 * ppt]
        rest = refs[4 * ppt:]
    else:
        rest = refs[2 * ppt:]
    o_ref, m_scr, l_scr, acc_scr = rest[:4]
    full = len(rest) > 4
    if full:
        mt_scr, lt_scr, acct_scr = rest[4:]

    b = pl.program_id(0)
    ti_ = pl.program_id(1)
    nt = pl.num_programs(1)
    first = first_ref[b]
    last = last_ref[b]
    nrows = nrow_ref[b]
    is_small = nrows <= small_rows

    @pl.when(ti_ == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if full:
            mt_scr[...] = jnp.full_like(mt_scr, NEG_INF)
            lt_scr[...] = jnp.zeros_like(lt_scr)
            acct_scr[...] = jnp.zeros_like(acct_scr)

    def page(j, h, n):
        q = q_ref[0, h, :n]                           # (n, D)
        k = k_refs[j][0, :, h, :]                     # (ps, D)
        v = v_refs[j][0, :, h, :]
        if quantized:
            # dequantize IN KERNEL: codes × per-(token, head) scales —
            # the fp32 pool never materializes in HBM
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32) * ks_refs[j][0, :, h][:, None]
            v = v.astype(jnp.float32) * vs_refs[j][0, :, h][:, None]
        return q, k, v

    def mask_of(k_pos, pos):
        mask = k_pos <= pos
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > pos - window)
        return mask

    def update_rows(j, h, k_start):
        # a small block: its first small_rows rows, one per sublane,
        # against the page's keys on lanes
        n = small_rows
        q, k, v = page(j, h, n)
        scores = pl.dot(q, k, trans_b=True).astype(jnp.float32) \
            * scale                                   # (n, ps)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(mask_of(k_pos, rpos_ref[0, :n]), scores,
                           NEG_INF)
        m_prev = m_scr[h, :n, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h, :n] = jnp.broadcast_to(
            alpha * l_scr[h, :n, :1] + jnp.sum(p, axis=-1, keepdims=True),
            (n, l_scr.shape[2]))
        acc_scr[h, :n] = acc_scr[h, :n] * alpha + pl.dot(
            p.astype(v.dtype), v).astype(jnp.float32)
        m_scr[h, :n] = jnp.broadcast_to(m_new, (n, m_scr.shape[2]))

    def update_cols(j, h, k_start):
        # a full block: the page's keys on sublanes against all R rows
        # on lanes, so the score tile is lane-dense; the accumulator is
        # held transposed, (D, R)
        q, k, v = page(j, h, q_ref.shape[2])
        scores = pl.dot(k, q, trans_b=True).astype(jnp.float32) \
            * scale                                   # (ps, R)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 0)
        scores = jnp.where(mask_of(k_pos, cpos_ref[0]), scores, NEG_INF)
        m_prev = mt_scr[h]                            # (1, R)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=0, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        lt_scr[h] = alpha * lt_scr[h] + jnp.sum(p, axis=0, keepdims=True)
        acct_scr[h] = acct_scr[h] * alpha + jax.lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (D, R)
        mt_scr[h] = m_new

    # the tile packs ppt consecutive pages of the block's sequence; each
    # page j runs, per KV head, the SAME sequential online-softmax
    # update in the same order for any tile size — fp32 outputs are
    # bitwise-equal across tile sizes.  A page runs when it holds a key
    # at or before the block's last position (and, windowed, after its
    # first position's window start); rows it does not concern are
    # masked row by row.  A block with few live rows (a decode, a short
    # speculative span) updates only its first small_rows rows.
    for j in range(ppt):
        k_start = (ti_ * ppt + j) * page_size
        run = k_start <= last
        if window is not None:
            run = jnp.logical_and(run, k_start + page_size > first - window)

        @pl.when(jnp.logical_and(run, is_small))
        def _small(j=j, k_start=k_start):
            for h in range(n_kv_heads):
                update_rows(j, h, k_start)

        if full:
            @pl.when(jnp.logical_and(run, jnp.logical_not(is_small)))
            def _full(j=j, k_start=k_start):
                for h in range(n_kv_heads):
                    update_cols(j, h, k_start)

    @pl.when(jnp.logical_and(ti_ == nt - 1, is_small))
    def _finalize_small():
        # rows past small_rows are unused in a small block: never read
        o_ref[0, :, :small_rows] = (
            acc_scr[...] / jnp.maximum(l_scr[:, :, :1], 1e-30)
        ).astype(o_ref.dtype)

    if full:
        @pl.when(jnp.logical_and(ti_ == nt - 1, jnp.logical_not(is_small)))
        def _finalize_full():
            for h in range(n_kv_heads):
                o_ref[0, h] = (acct_scr[h] / jnp.maximum(lt_scr[h], 1e-30)
                               ).T.astype(o_ref.dtype)


# jitted so the layers of a step share one trace and one lowering of the
# kernel: a process lowers every bucket's step program on its first call,
# even where the persistent compile cache holds the executable
@functools.partial(jax.jit, static_argnames=("scale", "window",
                                             "pages_per_tile", "interpret"))
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
    seg_ids/positions: (T,) int32.  Returns (T, Hkv, G, D).  Padding
    tokens (``seg < 0``) read slot 0 at their position, as the reference
    does; callers discard them.

    The grid is (query blocks, page tiles): a query block is up to
    ``BQ = query_block_size(G, T)`` tokens of one slot
    (:func:`query_blocks`; the scheduler lays each slot's tokens out
    as one contiguous span, speculative drafts included, so a block is
    a run of consecutive tokens), and ``NB = min(T, cdiv(T, BQ) + S +
    1)`` blocks always suffice; unused blocks run no tile.  The wrapper
    gathers q into (NB, Hkv, BQ·G, D) blocks, and the outputs back to
    tokens, with the same index arrays.

    The block descriptors (slot, first and last position, live rows)
    and the table are scalar-prefetched: the KV BlockSpec index map
    reads ``tables[slot[b], col]`` before the body runs, so each grid
    step DMAs exactly the physical pages it attends into VMEM, and all
    ``BQ·G`` query rows of a KV head are scored against a page in one
    matmul — one page fetch serves the block, not one token.  A tile
    past the block's last live page (or, windowed, before its first)
    clamps its page index to the nearest live page: Pallas sees a
    repeated block index and starts no DMA.

    A page block carries every KV head of its page, (1, ps, Hkv, D):
    its last two dims equal the array's, the form the TPU compiler
    accepts for any Hkv (a one-head block, (1, ps, 1, D), is refused
    whenever Hkv > 1).  The kernel loops over the heads statically.

    Quantized pools pass ``k_scale``/``v_scale``: (N, ps, Hkv) fp32
    per-(token, head) scales.  They ride the SAME table-prefetch
    routing as the pages — their BlockSpecs share the page index, so
    the scale rows for a page arrive with the page and dequantization
    happens in VMEM, never materializing an fp32 pool.

    ``pages_per_tile`` statically packs several pages into one grid
    step (ppt K refs + ppt V refs resolved per-page in the index maps);
    the kernel unrolls the identical per-page online-softmax update, so
    fp32 outputs are BITWISE-equal across tile sizes while small-page
    configs stop paying per-page grid overhead.
    """
    t, hkv, g, d = q.shape
    n_pages, ps = k_pages.shape[0], k_pages.shape[1]
    s_slots, p_pages = tables.shape
    ppt = max(1, min(pages_per_tile, p_pages))
    n_tiles = pl.cdiv(p_pages, ppt)
    quantized = k_scale is not None
    bq = query_block_size(g, t)
    n_blocks = min(t, pl.cdiv(t, bq) + s_slots + 1)
    n_rows = bq * g
    small_rows = min(SMALL_ROWS, n_rows)
    full = n_rows > small_rows

    rows, row_pos, slot, first, last, tok_row = query_blocks(
        jnp.asarray(seg_ids, jnp.int32), jnp.asarray(positions, jnp.int32),
        block_q=bq, n_slots=s_slots, n_blocks=n_blocks)
    live_rows = jnp.sum(rows >= 0, axis=1, dtype=jnp.int32) * g
    q_blk = jnp.take(q, jnp.maximum(rows, 0), axis=0) \
        .transpose(0, 2, 1, 3, 4).reshape(n_blocks, hkv, n_rows, d)
    rpos = jnp.repeat(row_pos, g, axis=1)                # (NB, R)

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               page_size=ps, ppt=ppt, n_kv_heads=hkv,
                               quantized=quantized, small_rows=small_rows)

    def page_index(j, bi, tj, tbl, slt, fst, lst):
        # the block's live columns are [lo, hi]: a dead page clamps to
        # the nearest live one, so consecutive dead tiles repeat the
        # block index and fetch nothing; the kernel's run predicate
        # (on the unclamped k_start) skips their compute
        hi = jnp.minimum(jnp.maximum(lst[bi], 0) // ps, p_pages - 1)
        lo = 0 if window is None else jnp.clip(
            (fst[bi] - window) // ps, 0, hi)
        return tbl[slt[bi], jnp.clip(tj * ppt + j, lo, hi)]

    def page_map(j):
        def kv_map(bi, tj, tbl, slt, fst, lst, nrw):
            return (page_index(j, bi, tj, tbl, slt, fst, lst), 0, 0, 0)
        return kv_map

    def scale_map(j):
        def sc_map(bi, tj, tbl, slt, fst, lst, nrw):
            return (page_index(j, bi, tj, tbl, slt, fst, lst), 0, 0)
        return sc_map

    blk_spec = pl.BlockSpec((1, hkv, n_rows, d),
                            lambda bi, tj, *_: (bi, 0, 0, 0))
    in_specs = [blk_spec,
                pl.BlockSpec((1, n_rows, 1), lambda bi, tj, *_: (bi, 0, 0)),
                pl.BlockSpec((1, 1, n_rows), lambda bi, tj, *_: (bi, 0, 0))]
    in_specs += [pl.BlockSpec((1, ps, hkv, d), page_map(j))
                 for j in range(ppt)]
    in_specs += [pl.BlockSpec((1, ps, hkv, d), page_map(j))
                 for j in range(ppt)]
    operands = [q_blk, rpos[:, :, None], rpos[:, None, :]] \
        + [k_pages] * ppt + [v_pages] * ppt
    if quantized:
        in_specs += [pl.BlockSpec((1, ps, hkv), scale_map(j))
                     for j in range(ppt)]
        in_specs += [pl.BlockSpec((1, ps, hkv), scale_map(j))
                     for j in range(ppt)]
        operands += [k_scale] * ppt + [v_scale] * ppt

    # online-softmax state: m, l, acc of a small block's rows, and (when
    # the block can hold more rows) their transposes for a full block
    scratch = [pltpu.VMEM((hkv, small_rows, 128), jnp.float32),
               pltpu.VMEM((hkv, small_rows, 128), jnp.float32),
               pltpu.VMEM((hkv, small_rows, d), jnp.float32)]
    if full:
        scratch += [pltpu.VMEM((hkv, 1, n_rows), jnp.float32),
                    pltpu.VMEM((hkv, 1, n_rows), jnp.float32),
                    pltpu.VMEM((hkv, d, n_rows), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_blocks, n_tiles),
        in_specs=in_specs,
        out_specs=blk_spec,
        scratch_shapes=scratch,
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, hkv, n_rows, d), q.dtype),
        interpret=interpret,
        name="paged_attention_fwd",
    )(jnp.asarray(tables, jnp.int32), slot, first, last, live_rows,
      *operands)
    out = out.reshape(n_blocks, hkv, bq, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(n_blocks * bq, hkv, g, d)
    return jnp.take(out, tok_row, axis=0)
