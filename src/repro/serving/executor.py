"""Serving data plane — ONE jitted ``unified_step`` per shape bucket.

The executor consumes a ``StepPlan`` (host-built by the Scheduler) and
runs the whole step's compute as a single XLA executable:

  * a padded FLAT token batch (T,) mixing prefill-chunk tokens and decode
    tokens — the §5.2 "all data flow in one compiled program" applied to
    serving,
  * per-layer K/V appends are ONE flat scatter per layer INSIDE the jit
    (``write_idx`` precomputed on host; out-of-bounds rows drop — the
    padding/reused-prefix skip), replacing the O(prompt_len × layers)
    host round-trips of the old ``_prefill``,
  * attention reads the KV pages DIRECTLY through the device block-table
    mirror via ``paged_attention`` (per-token segment ids/positions; on
    TPU the Pallas kernel scalar-prefetches the table and DMAs only live
    pages — no per-slot contiguous cache is ever gathered),
  * the KV page arrays are DONATED: ``unified_step`` consumes them and
    returns the updated pair; while the step runs the host holds no
    alias (``PagedKVCache.take_kv``/``put_kv`` enforce this),
  * the weights are OPERANDS of the step (not donated), never closure
    constants: the lowered module carries no weight bytes (a multi-GB
    model would pass the 2 GB protobuf limit), and the compile-cache
    key depends on shapes only,
  * SAMPLING runs in the same executable (``serving.sampling``):
    greedy / temperature / top-k / top-p with per-slot params as tiny
    operand arrays and position-keyed PRNG — plus the K speculative
    verify rows per slot — so the (rows, vocab) logits NEVER cross to
    host; the step's only outputs are (S, K+1) token ids and (S,)
    fault flags.

Shapes are bucketed (powers of two: token batch up to ``token_budget``,
pages per sequence up to ``max_pages_per_seq``; slot count fixed at
``max_batch``), so the executable compiles O(log) variants total instead
of one per live batch size — ``compile_count`` must stay ≤
``Scheduler.bucket_count`` (the CI gate).
"""

from __future__ import annotations

import math
import warnings
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import layers as L
from ..kernels.ops import query_block_size
from ..models.attention import (paged_attention, paged_uses_kernel,
                                select_paged_backend)
from ..models import lm as LM
from . import quant, sampling
from .kv_cache import PagedKVCache
from .scheduler import StepPlan
from .tracing import OFF, Tracer

# buffer donation is a TPU/GPU optimization; CPU (tests) just warns
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def serving_params(cfg: LM.LMConfig, params) -> dict:
    """The step's weight operand: the model's top-level arrays (embed,
    final norm, lm_head) plus ``"layers"``, the per-layer list."""
    out = {k: v for k, v in params.items() if k not in ("groups", "tail")}
    out["layers"] = split_layer_params(cfg, params)
    return out


def split_layer_params(cfg: LM.LMConfig, params) -> list:
    """Flatten the scan-stacked group params (+ unrolled tail) into one
    per-layer list — serving iterates layers in Python, not lax.scan."""
    layers = []
    for gi in range(cfg.n_groups):
        for j in range(len(cfg.pattern)):
            layers.append(jax.tree_util.tree_map(
                lambda a: a[gi], params["groups"][j]))
    for j in range(len(cfg.tail)):
        layers.append(params["tail"][j])
    return layers


class Executor:
    """Owns the jitted step; stateless between calls except the compile
    bookkeeping."""

    def __init__(self, cfg: LM.LMConfig, params, *, mesh=None,
                 n_replicas: int = 1, kv_sharding=None,
                 kv_quant=None, scale_sharding=None,
                 tracer: Optional[Tracer] = None):
        self.cfg = cfg
        self.tracer = tracer if tracer is not None else Tracer()
        # quantized KV: the step quantizes k/v per (token, head) right
        # before the flat scatter (codes into the pool, scales into the
        # parallel arrays at the SAME write_idx) and attention
        # dequantizes in-kernel — None keeps the fp32/bf16 trace
        # byte-identical to the unquantized executor
        self._kv_quant = quant.canonical(kv_quant)
        self.mesh = mesh
        if mesh is not None:
            n_replicas = dict(mesh.shape).get("data", 1)
            from ..distributed.sharding import serving_param_shardings
            params = jax.tree_util.tree_map(
                jax.device_put, params,
                serving_param_shardings(cfg, params, mesh))
        self.n_replicas = n_replicas
        self._params = serving_params(cfg, params)
        # a replica axis (vmap) or a mesh pins the jnp ref attention
        # path — the Pallas kernel's scalar-prefetch table lookup is a
        # single-device whole-pool construct (see select_paged_backend)
        self._attn_backend = select_paged_backend(
            cfg.attn_backend, sharded=(mesh is not None or n_replicas > 1))
        # query blocks the paged kernel launched and the tokens they
        # carried, summed over steps (both stay 0 on the ref path)
        self._attn_kernel = paged_uses_kernel(self._attn_backend, cfg.hd)
        self.attn_query_blocks = 0
        self.attn_query_tokens = 0
        # KV pages keep THIS sharding across steps: constrained on the
        # step outputs so donation round-trips never reshard
        self._kv_sharding = kv_sharding
        self._scale_sharding = scale_sharding
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._plan_sh = {
                2: NamedSharding(mesh, P("data", None)),
                3: NamedSharding(mesh, P("data", None, None)),
            }
        else:
            self._plan_sh = None
        # p_bucket is static: the full-width device table mirror is
        # narrowed to the step's page bucket INSIDE the jit (free), so
        # the host never slices/re-uploads tables per step
        jit_kw = {}
        if kv_sharding is not None:
            # pin the returned pool to the sharding it was placed with:
            # left to the compiler, an equivalent spec can come back
            # normalized (P() for size-1 axes) and the next step with
            # the same bucket would miss the jit cache and recompile
            layers = cfg.n_layers
            sc = [] if self._kv_quant is None else [scale_sharding] * layers
            jit_kw["out_shardings"] = (None, None, [kv_sharding] * layers,
                                       [kv_sharding] * layers, sc, sc)
        self._step = jax.jit(self._unified_step, static_argnums=(0,),
                             donate_argnums=(2, 3, 4, 5), **jit_kw)
        # (t_bucket, p_bucket) of each step that compiled, in order
        self.compiled_buckets: List[Tuple[int, int]] = []

    @property
    def compile_count(self) -> int:
        return self._step._cache_size()

    # -- host entry -------------------------------------------------------
    def execute(self, plan: StepPlan, kv: PagedKVCache
                ) -> Tuple[np.ndarray, np.ndarray]:
        """Run one unified step; returns ((max_batch, K+1) sampled
        tokens — column 0 is the step's next token, columns 1..K the
        target tokens at the speculative draft positions — and a
        (max_batch,) bool non-finite-logits flag array, the fault
        barrier the engine uses to quarantine a poisoned sequence
        without losing the step for everyone else).  Sampling runs
        INSIDE the jit: only these two small arrays ever cross the
        device boundary — the (S·(K+1), V) logits never do."""
        tr = self.tracer
        t0 = tr.clock() if tr.enabled else 0.0
        with tr.span("executor.prepare") if tr.enabled else OFF:
            tables = kv.device_tables(plan.slot_seqs, plan.p_bucket)
            ks, vs = kv.take_kv()
            kss, vss = kv.take_scales()      # ([], []) unquantized
        n_compiled = self.compile_count
        try:
            # the operands are placed inside the call's span
            with tr.span("executor.dispatch") if tr.enabled else OFF:
                next_tokens, bad, ks, vs, kss, vss = self._step(
                    *self._operands(plan, tables, ks, vs, kss, vss))
        finally:
            if ks is not None:
                kv.put_kv(ks, vs)
                kv.put_scales(kss, vss)
        if self._attn_kernel:
            # the kernel cuts each span into blocks of bq query tokens
            bq = query_block_size(self.cfg.n_heads // self.cfg.n_kv_heads,
                                  plan.t_bucket)
            self.attn_query_blocks += sum(
                -(-(s.end - s.start + len(s.drafts)) // bq)
                for s in plan.spans)
            self.attn_query_tokens += plan.n_tokens
        if self.compile_count > n_compiled:
            self.compiled_buckets.append((plan.t_bucket, plan.p_bucket))
            if tr.enabled:
                tr.record("executor.build", t0, tr.clock(),
                          t_bucket=plan.t_bucket, p_bucket=plan.p_bucket)
        with tr.span("executor.wait") if tr.enabled else OFF:
            return np.asarray(next_tokens), np.asarray(bad)

    def _place(self, a) -> jnp.ndarray:
        """Plan operands under a mesh get an explicit replica-axis
        placement (row r → replica r's devices); otherwise asarray —
        stable input shardings keep the jit cache at one entry per
        shape bucket."""
        if self._plan_sh is not None:
            a = np.asarray(a)
            sh = self._plan_sh.get(a.ndim)
            if sh is not None:
                return jax.device_put(a, sh)
        return jnp.asarray(a)

    def _operands(self, plan: StepPlan, tables, ks, vs, kss, vss) -> tuple:
        """The step's arguments, in ``_unified_step``'s order."""
        op = self._place
        return (plan.p_bucket, self._params, ks, vs, kss, vss,
                op(plan.tokens), op(plan.seg_ids), op(plan.positions),
                op(plan.write_idx), tables, op(plan.sample_idx),
                op(plan.sample_pos), op(plan.temps), op(plan.top_ks),
                op(plan.top_ps), op(plan.seeds))

    def lower(self, plan: StepPlan, kv: PagedKVCache):
        """Lower (without running) the step for ``plan``'s bucket — what
        ``execute`` would compile.  Leaves the pool in place."""
        return self._step.lower(*self._operands(
            plan, kv.device_tables(plan.slot_seqs, plan.p_bucket),
            kv.k, kv.v, kv.k_scale or [], kv.v_scale or []))

    # -- the jitted data plane -------------------------------------------

    def _unified_step(self, p_bucket: int, params,
                      k_pages: List[jnp.ndarray],
                      v_pages: List[jnp.ndarray],
                      k_scales: List[jnp.ndarray],
                      v_scales: List[jnp.ndarray],
                      tokens: jnp.ndarray, seg_ids: jnp.ndarray,
                      positions: jnp.ndarray, write_idx: jnp.ndarray,
                      tables: jnp.ndarray, sample_idx: jnp.ndarray,
                      sample_pos: jnp.ndarray, temps: jnp.ndarray,
                      top_ks: jnp.ndarray, top_ps: jnp.ndarray,
                      seeds: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                 List[jnp.ndarray], List[jnp.ndarray],
                                 List[jnp.ndarray], List[jnp.ndarray]]:
        """``params`` is the :func:`serving_params` tree.
        Single replica: tokens/seg_ids/positions/write_idx (T,),
        tables (S, W>=P), sample_idx (S, K+1), sample_pos/temps/top_ks/
        top_ps/seeds (S,) — all operands, never statics (per-request
        params cannot trigger a recompile).  With R data replicas every
        plan operand grows a leading replica axis ((R, T), (R, S, K+1),
        (R, S)) and the transformer body vmaps over it — replica r runs
        the single-device step against its OWN slice of the page pool
        ((R, N/R, ps, Hkv, hd) view) and its own S-row table block, so
        per-replica bucket shapes (and the compiled-variant count) are
        IDENTICAL to the single-device plan.  Under a mesh GSPMD then
        partitions the vmapped program over ``data``/``model``.
        Returns ((R*S, K+1) sampled int32 tokens, (R*S,) non-finite-
        logits flags, new K/V page arrays)."""
        cfg = self.cfg
        replicated = tokens.ndim == 2
        if not replicated:
            x, new_k, new_v, new_ks, new_vs = self._body(
                params, k_pages, v_pages, k_scales, v_scales, tokens,
                seg_ids,
                positions, write_idx, tables[:, :p_bucket])
            s, kp1 = sample_idx.shape
            xs = jnp.take(x, sample_idx.reshape(-1), axis=0)  # (S*(K+1), D)
        else:
            r = tokens.shape[0]
            n_total, ps = k_pages[0].shape[0], k_pages[0].shape[1]
            n_local = n_total // r
            k_r = [a.reshape(r, n_local, *a.shape[1:]) for a in k_pages]
            v_r = [a.reshape(r, n_local, *a.shape[1:]) for a in v_pages]
            ks_r = [a.reshape(r, n_local, *a.shape[1:]) for a in k_scales]
            vs_r = [a.reshape(r, n_local, *a.shape[1:]) for a in v_scales]
            tab_r = tables.reshape(r, tables.shape[0] // r,
                                   tables.shape[1])[:, :, :p_bucket]
            # weights are shared by every replica (unbatched)
            x, new_k, new_v, new_ks, new_vs = jax.vmap(
                self._body, in_axes=(None,) + (0,) * 9)(
                params, k_r, v_r, ks_r, vs_r, tokens, seg_ids, positions,
                write_idx, tab_r)
            new_k = [a.reshape(n_total, *a.shape[2:]) for a in new_k]
            new_v = [a.reshape(n_total, *a.shape[2:]) for a in new_v]
            new_ks = [a.reshape(n_total, *a.shape[2:]) for a in new_ks]
            new_vs = [a.reshape(n_total, *a.shape[2:]) for a in new_vs]
            if self._kv_sharding is not None:
                cons = jax.lax.with_sharding_constraint
                new_k = [cons(a, self._kv_sharding) for a in new_k]
                new_v = [cons(a, self._kv_sharding) for a in new_v]
                if self._scale_sharding is not None:
                    new_ks = [cons(a, self._scale_sharding)
                              for a in new_ks]
                    new_vs = [cons(a, self._scale_sharding)
                              for a in new_vs]
            _, s_r, kp1 = sample_idx.shape
            s = r * s_r
            # per-replica row gather out of (R, T, D) hidden states,
            # then flatten: the sampling tail below is replica-oblivious
            xs = jax.vmap(lambda xr, ir: jnp.take(xr, ir, axis=0))(
                x, sample_idx.reshape(r, -1)).reshape(s * kp1, -1)
            sample_pos = sample_pos.reshape(-1)
            temps = temps.reshape(-1)
            top_ks = top_ks.reshape(-1)
            top_ps = top_ps.reshape(-1)
            seeds = seeds.reshape(-1)
        logits = xs @ (params["embed"].T if cfg.tie_embeddings
                       else params["lm_head"])
        # per-slot fault barrier: a NaN/inf logits row (poisoned KV,
        # overflowed activations) flags JUST that slot — the engine
        # quarantines the one request instead of crashing the step loop
        bad = jnp.any(~jnp.all(jnp.isfinite(logits), axis=-1)
                      .reshape(s, kp1), axis=-1)
        # sample IN-JIT: row i of a slot draws the token at absolute
        # position sample_pos + i under that slot's params — the PRNG
        # key depends only on (seed, position), which is what makes the
        # speculative targets bitwise-equal to a non-speculative replay
        gen_pos = (sample_pos[:, None]
                   + jnp.arange(kp1, dtype=jnp.int32)[None, :])
        toks = sampling.sample_tokens(
            logits, jnp.repeat(temps, kp1), jnp.repeat(top_ks, kp1),
            jnp.repeat(top_ps, kp1), jnp.repeat(seeds, kp1),
            gen_pos.reshape(-1))
        return toks.reshape(s, kp1), bad, new_k, new_v, new_ks, new_vs

    def _body(self, params, k_pages: List[jnp.ndarray],
              v_pages: List[jnp.ndarray],
              k_scales: List[jnp.ndarray], v_scales: List[jnp.ndarray],
              tokens: jnp.ndarray, seg_ids: jnp.ndarray,
              positions: jnp.ndarray, write_idx: jnp.ndarray,
              tables: jnp.ndarray
              ) -> Tuple[jnp.ndarray, List[jnp.ndarray], List[jnp.ndarray],
                         List[jnp.ndarray], List[jnp.ndarray]]:
        """One replica's transformer pass over its (n, ps, Hkv, hd) page
        slice: embed → layers (KV scatter + paged attention in place) →
        final norm.  Returns the (T, D) normed hidden states and the
        updated page (and, quantized, scale) arrays; write_idx/tables
        are replica-LOCAL."""
        cfg = self.cfg
        t = tokens.shape[0]
        n_pages, ps = k_pages[0].shape[0], k_pages[0].shape[1]
        scale = cfg.query_scale or cfg.hd ** -0.5

        x = jnp.take(params["embed"], tokens, axis=0)          # (T, D)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)

        qmode = self._kv_quant
        new_k, new_v, new_ks, new_vs = [], [], [], []
        for li, lp in enumerate(params["layers"]):
            h = L.rms_norm(x, lp["norm1"], cfg.norm_eps, cfg.norm_offset) \
                if cfg.norm == "rms" else L.layer_norm(
                    x, lp["norm1"], lp.get("norm1_b"), cfg.norm_eps)
            q = (h @ lp["attn"]["wq"]).reshape(t, cfg.n_heads, cfg.hd)
            k = (h @ lp["attn"]["wk"]).reshape(t, cfg.n_kv_heads, cfg.hd)
            v = (h @ lp["attn"]["wv"]).reshape(t, cfg.n_kv_heads, cfg.hd)
            if cfg.rope_theta is not None:
                # (T, H, 1, hd) + per-token positions (T, 1)
                q = L.apply_rope(q[:, :, None], positions[:, None],
                                 cfg.rope_theta)[:, :, 0]
                k = L.apply_rope(k[:, :, None], positions[:, None],
                                 cfg.rope_theta)[:, :, 0]

            # one segment-indexed scatter per layer (padding + reused-
            # prefix rows carry an OOB index and drop)
            kf = k_pages[li].reshape(n_pages * ps, cfg.n_kv_heads, cfg.hd)
            vf = v_pages[li].reshape(n_pages * ps, cfg.n_kv_heads, cfg.hd)
            ks_p = vs_p = None
            if qmode is None:
                kf = kf.at[write_idx].set(k.astype(kf.dtype), mode="drop")
                vf = vf.at[write_idx].set(v.astype(vf.dtype), mode="drop")
            else:
                # quantize on scatter: int8/fp8 codes into the pool,
                # per-(token, head) scales into the parallel arrays at
                # the SAME flat slots (same drop semantics)
                kq, k_sc = quant.quantize(k, qmode)
                vq, v_sc = quant.quantize(v, qmode)
                kf = kf.at[write_idx].set(kq, mode="drop")
                vf = vf.at[write_idx].set(vq, mode="drop")
                ks_p = k_scales[li].reshape(n_pages * ps, cfg.n_kv_heads) \
                    .at[write_idx].set(k_sc, mode="drop") \
                    .reshape(n_pages, ps, cfg.n_kv_heads)
                vs_p = v_scales[li].reshape(n_pages * ps, cfg.n_kv_heads) \
                    .at[write_idx].set(v_sc, mode="drop") \
                    .reshape(n_pages, ps, cfg.n_kv_heads)
                new_ks.append(ks_p)
                new_vs.append(vs_p)
            kp = kf.reshape(n_pages, ps, cfg.n_kv_heads, cfg.hd)
            vp = vf.reshape(n_pages, ps, cfg.n_kv_heads, cfg.hd)
            new_k.append(kp)
            new_v.append(vp)

            # attend the page pool in place through the block table
            # (includes this step's writes; no per-slot gather) — a
            # quantized pool keeps q in compute dtype and dequantizes
            # the pages in-kernel via the scale operands
            o = paged_attention(q.astype(kp.dtype) if qmode is None
                                else q, kp, vp, tables,
                                seg_ids, positions, scale=scale,
                                k_scale=ks_p, v_scale=vs_p,
                                backend=self._attn_backend)
            x = x + o.reshape(t, -1).astype(x.dtype) @ lp["attn"]["wo"]
            if "mlp" in lp:
                h2 = L.rms_norm(x, lp["norm2"], cfg.norm_eps,
                                cfg.norm_offset) if cfg.norm == "rms" \
                    else L.layer_norm(x, lp["norm2"], lp.get("norm2_b"),
                                      cfg.norm_eps)
                x = x + L.mlp(lp["mlp"], h2, cfg.act)

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps,
                       cfg.norm_offset) if cfg.norm == "rms" else \
            L.layer_norm(x, params["final_norm"],
                         params.get("final_norm_b"), cfg.norm_eps)
        return x, new_k, new_v, new_ks, new_vs
