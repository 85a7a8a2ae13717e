"""Attention computation layer (jnp-level, kernel-selectable).

``sdpa`` is the single entry point used by both the eager ``nn.functional``
path and the functional LM models.  It handles:

  * GQA/MQA: k/v with fewer heads than q are broadcast per group,
  * causal masking, sliding-window (local) masking, explicit masks,
  * backend selection: "ref" (pure jnp, the oracle), "pallas" (the
    Pallas kernel), "auto" (the kernel for every input it is built for,
    by shape — see each entry point; on TPU the paged path always takes
    it).  A kernel error propagates: no path catches it and falls back
    to the reference, so a chip run never times the wrong code.

All reference math upcasts softmax statistics to f32, matching the Pallas
kernels bit-for-bit in structure so allclose checks are tight.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_PALLAS_MIN_SEQ = 128  # below this the ref path is cheaper than tiling


def repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, Hkv, S, D) -> (B, Hkv*n_rep, S, D)."""
    if n_rep == 1:
        return k
    b, h, s, d = k.shape
    k = jnp.broadcast_to(k[:, :, None], (b, h, n_rep, s, d))
    return k.reshape(b, h * n_rep, s, d)


def _build_mask(q_len: int, kv_len: int, is_causal: bool,
                window: Optional[int], dtype) -> Optional[jnp.ndarray]:
    if not is_causal and window is None:
        return None
    # query i attends key j where j <= i + (kv_len - q_len)  (causal)
    # and j > i + (kv_len - q_len) - window                  (sliding)
    q_pos = jnp.arange(q_len)[:, None] + (kv_len - q_len)
    k_pos = jnp.arange(kv_len)[None, :]
    ok = jnp.ones((q_len, kv_len), dtype=bool)
    if is_causal:
        ok = ok & (k_pos <= q_pos)
    if window is not None:
        ok = ok & (k_pos > q_pos - window)
    return ok


def sdpa_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
             mask: Optional[jnp.ndarray] = None,
             is_causal: bool = False,
             scale: Optional[float] = None,
             window: Optional[int] = None) -> jnp.ndarray:
    """Pure-jnp oracle. q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D)."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        k = repeat_kv(k, hq // hkv)
        v = repeat_kv(v, hq // hkv)
    scale = scale if scale is not None else d ** -0.5

    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    structural = _build_mask(sq, k.shape[2], is_causal, window, q.dtype)
    if structural is not None:
        logits = jnp.where(structural[None, None], logits,
                           jnp.finfo(jnp.float32).min)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def context_sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 scale: Optional[float], causal: bool,
                 window: Optional[int]) -> jnp.ndarray:
    """Manual context-parallel attention (used when heads don't divide TP
    and the residual stream is sequence-sharded).

    GSPMD cannot derive ring attention: left alone it all-gathers the
    full f32 (B, H, S, D) q/k/v per layer (§Perf yi iteration log).
    Here each model rank keeps its LOCAL query slice and all-gathers only
    the (much smaller, GQA-reduced, bf16) K/V — the KV-gather variant of
    context parallelism.  Causal masking uses global query offsets.
    """
    from ..distributed import act_sharding as AS
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    import functools

    scope = AS._get()
    mesh = scope.mesh
    axis = scope.model
    b, hq, s_full, d = q.shape
    batch_ax = scope.batch if (b > 1 and b % scope.data_size == 0) \
        else None
    qspec = P(batch_ax, None, axis, None)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(qspec, qspec, qspec), out_specs=qspec,
        check_rep=False)
    def _inner(q_l, k_l, v_l):
        idx = jax.lax.axis_index(axis)
        k_g = jax.lax.all_gather(k_l, axis, axis=2, tiled=True)
        v_g = jax.lax.all_gather(v_l, axis, axis=2, tiled=True)
        s_loc = q_l.shape[2]
        q_pos = idx * s_loc + jnp.arange(s_loc)[:, None]
        k_pos = jnp.arange(k_g.shape[2])[None, :]
        ok = jnp.ones((s_loc, k_g.shape[2]), bool)
        if causal:
            ok = ok & (k_pos <= q_pos)
        if window is not None:
            ok = ok & (k_pos > q_pos - window)
        return sdpa_ref(q_l, k_g, v_g, mask=ok[None, None], scale=scale)

    return _inner(q, k, v)


def sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
         mask: Optional[jnp.ndarray] = None,
         is_causal: bool = False,
         scale: Optional[float] = None,
         window: Optional[int] = None,
         backend: str = "auto") -> jnp.ndarray:
    if backend == "ref":
        import os
        from ..distributed import act_sharding as AS
        scope = AS._get()
        if (os.environ.get("REPRO_SEQ_SHARD") == "1" and scope is not None
                and scope.model is not None and mask is None
                and q.shape[1] % scope.model_size != 0
                and q.shape[2] % scope.model_size == 0
                and q.shape[2] == k.shape[2]):
            return context_sdpa(q, k, v, scale, is_causal, window)
        return sdpa_ref(q, k, v, mask, is_causal, scale, window)
    if backend in ("auto", "pallas"):
        # the flash kernel takes no explicit mask, and below
        # _PALLAS_MIN_SEQ the reference is cheaper than tiling
        if mask is None and q.shape[2] >= _PALLAS_MIN_SEQ:
            from ..kernels import ops as kops
            return kops.flash_attention(
                q, k, v, causal=is_causal, scale=scale, window=window)
        return sdpa_ref(q, k, v, mask, is_causal, scale, window)
    raise ValueError(f"unknown sdpa backend {backend!r}")


def mixed_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, seg_ids: jnp.ndarray,
                    positions: jnp.ndarray,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    backend: str = "auto") -> jnp.ndarray:
    """Attention for a FLAT token batch mixing prefill chunks and decode
    tokens (the serving executor's unified step).

    q: (T, Hq, D) — one query per scheduled token; k_cache/v_cache:
    (S, Hkv, L, D) — per-slot contiguous KV (gathered from pages, already
    containing this step's scatter); seg_ids: (T,) slot index per token
    (<0 = padding); positions: (T,) absolute position of the token in its
    sequence.  Token t attends slot seg_ids[t]'s cache at key positions
    <= positions[t] (its own K/V included) — causal both against history
    and within its prefill chunk.  Returns (T, Hq, D).
    """
    t, hq, d = q.shape
    s, hkv, l, _ = k_cache.shape
    scale = scale if scale is not None else d ** -0.5

    if backend in ("auto", "pallas"):
        from ..kernels import ops as kops
        return kops.mixed_attention(q, k_cache, v_cache, seg_ids,
                                    positions, scale=scale, window=window)

    seg = jnp.clip(seg_ids, 0, s - 1)
    k = jnp.take(k_cache, seg, axis=0)                  # (T, Hkv, L, D)
    v = jnp.take(v_cache, seg, axis=0)
    if hkv != hq:
        k = repeat_kv(k, hq // hkv)
        v = repeat_kv(v, hq // hkv)
    logits = jnp.einsum("thd,thld->thl", q, k,
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(l)[None, :]
    valid = k_pos <= positions[:, None]
    if window is not None:
        valid = valid & (k_pos > positions[:, None] - window)
    logits = jnp.where(valid[:, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("thl,thld->thd", probs, v)


def paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                    v_pages: jnp.ndarray, tables: jnp.ndarray,
                    seg_ids: jnp.ndarray, positions: jnp.ndarray,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    k_scale: Optional[jnp.ndarray] = None,
                    v_scale: Optional[jnp.ndarray] = None,
                    pages_per_tile: Optional[int] = None,
                    backend: str = "auto") -> jnp.ndarray:
    """Mixed prefill/decode attention DIRECTLY over the physical KV page
    pool — no per-slot contiguous cache is materialized.

    q: (T, Hq, D) — one query per scheduled token; k_pages/v_pages:
    (N, ps, Hkv, D) — the page arrays exactly as ``PagedKVCache`` stores
    them; tables: (S, P) int32 device block tables (row s = slot s's
    physical page ids, padded with 0); seg_ids: (T,) slot per token
    (<0 = padding); positions: (T,) absolute position in the sequence.
    Token t attends slot seg_ids[t]'s pages at key positions <=
    positions[t].  Returns (T, Hq, D).

    A QUANTIZED pool (int8 / fp8_e4m3 codes) passes ``k_scale``/
    ``v_scale`` — (N, ps, Hkv) fp32 per-(token, head) scales stored
    beside the pages (see ``serving.quant``).  The Pallas path
    dequantizes inside the kernel (scales ride the same table-routed
    BlockSpec path as their pages); the ref path dequantizes the pool
    before its gather — same math, the tolerance oracle.
    ``pages_per_tile`` statically packs several pages per kernel grid
    step (fp32 output bitwise-independent of the tile size).

    Backends: "pallas" runs the block-table-prefetching kernel (the
    production TPU path: the table lookup happens in the BlockSpec index
    map, so only live pages are ever DMA'd); "ref" (and "auto" on CPU
    with an unaligned head_dim) gathers (S, P*ps) page rows with one
    ``jnp.take`` and reduces to ``mixed_attention`` — the oracle, and
    the XLA-fused CPU path.
    """
    t, hq, d = q.shape
    n_pages, ps, hkv, _ = k_pages.shape
    s, p = tables.shape
    scale = scale if scale is not None else d ** -0.5

    if paged_uses_kernel(backend, d):
        from ..kernels import ops as kops
        return kops.paged_attention(q, k_pages, v_pages, tables,
                                    seg_ids, positions, scale=scale,
                                    window=window, k_scale=k_scale,
                                    v_scale=v_scale,
                                    pages_per_tile=pages_per_tile)

    if k_scale is not None:
        # ref dequant: codes × scales materialize an fp32 pool view
        # (oracle/CPU path only — the kernel path never does this)
        k_pages = (k_pages.astype(jnp.float32)
                   * k_scale[..., None]).astype(q.dtype)
        v_pages = (v_pages.astype(jnp.float32)
                   * v_scale[..., None]).astype(q.dtype)
    gidx = (tables[:, :, None] * ps
            + jnp.arange(ps)[None, None, :]).reshape(s, p * ps)
    return _paged_attention_ref(q, k_pages, v_pages, gidx, seg_ids,
                                positions, scale=scale, window=window,
                                backend=backend)


def _paged_attention_ref(q, k_pages, v_pages, gidx, seg_ids, positions,
                         *, scale, window, backend):
    t, hq, d = q.shape
    n_pages, ps, hkv, _ = k_pages.shape
    kf = k_pages.reshape(n_pages * ps, hkv, d)
    vf = v_pages.reshape(n_pages * ps, hkv, d)
    k_cache = jnp.take(kf, gidx, axis=0).transpose(0, 2, 1, 3)
    v_cache = jnp.take(vf, gidx, axis=0).transpose(0, 2, 1, 3)
    # keep the caller's backend: under "auto" with a non-lane-aligned
    # head_dim the gather feeds the Pallas mixed_attention kernel —
    # exactly the pre-paged executor path
    return mixed_attention(q, k_cache, v_cache, seg_ids, positions,
                           scale=scale, window=window, backend=backend)


def paged_uses_kernel(backend: str, head_dim: int) -> bool:
    """Whether :func:`paged_attention` under ``backend`` runs the Pallas
    kernel.  "auto" takes it on TPU always; elsewhere (the CPU
    interpret path) only when head_dim is lane-aligned — for
    d % 128 != 0 the wrapper lane-pads (copies) the ENTIRE page pool
    per layer per step, costing more than the gather it saves."""
    return backend == "pallas" or (backend == "auto" and (
        head_dim % 128 == 0 or jax.default_backend() == "tpu"))


def select_paged_backend(requested: str, *, sharded: bool) -> str:
    """Kernel-vs-ref selection for the paged executor.

    The Pallas paged-attention kernel prefetches block-table SCALARS to
    resolve slot→page inside its BlockSpec index map — a whole-array,
    single-device view.  Under a vmapped replica axis or a GSPMD mesh
    the kernel would see a SHARD of the page pool with global table ids
    (and pallas_call batching over the scalar-prefetch grid is not
    supported), so sharded execution pins the jnp reference path; GSPMD
    partitions its gather + softmax like any other XLA op.  Single
    replica on one device keeps whatever the caller asked for."""
    return requested if not sharded else "ref"


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cache_len,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     backend: str = "auto") -> jnp.ndarray:
    """Single-position decode: q (B, Hq, 1, D) against a (B, Hkv, Smax, D)
    cache filled up to ``cache_len`` (int or (B,) array)."""
    b, hq, _, d = q.shape
    hkv = k_cache.shape[1]
    smax = k_cache.shape[2]
    scale = scale if scale is not None else d ** -0.5

    if backend in ("auto", "pallas"):
        from ..kernels import ops as kops
        return kops.decode_attention(q, k_cache, v_cache, cache_len,
                                     scale=scale, window=window)

    k = repeat_kv(k_cache, hq // hkv)
    v = repeat_kv(v_cache, hq // hkv)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(smax)[None, None, None, :]
    clen = jnp.asarray(cache_len)
    clen = jnp.broadcast_to(clen.reshape(-1), (b,)).reshape(b, 1, 1, 1)
    valid = pos < clen
    lo = (clen - window) if window is not None else None
    if lo is not None:
        valid = valid & (pos >= jnp.maximum(lo, 0))
    logits = jnp.where(valid, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
