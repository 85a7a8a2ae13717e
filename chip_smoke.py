#!/usr/bin/env python3
"""Smoke test of the system on a TPU, through the entry points a user calls.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded serving path only

One process; it needs a TPU and has no CPU fallback.  Phases, in order
(any failure exits non-zero and prints no result line):

1. device   — platform, device kind and count; no TPU is an error.
2. eager    — a few training steps of the ``examples/quickstart.py``
              model with the fusion queue on; fused chains must lower to
              the native Pallas kernel; loss and gradients agree with a
              plain ``jax.numpy`` reference.
3. server   — ``launch/server.py``'s HTTP server fronting gemma-2b at its
              published width (18 layers, d_model 2048, vocab 256000,
              bf16, random weights from ``--seed``) on a real socket;
              streamed requests must all end ``finished`` with no
              executor or request failure and no more compiles than
              shape buckets.  Prints the attention backend found in the
              compiled step, compile seconds per bucket and peak HBM.
4. kernel   — the Pallas paged-attention kernel against the jnp
              reference on the engine's own page pool.
5. reference — the K/V the engine wrote for a served sequence, every
              layer, against the K/V of the dense ``models/lm`` forward
              of the same weights (the check that can see a wrong
              scatter, page, position or attention output), then the
              served greedy tokens of two requests against a dense
              rollout.

``--four-chips`` runs only the sharded path (``launch/serve.py --dp/--tp``
over ``mesh_for_serving``) at gemma-2b width, depth cut to
``FOUR_CHIP_LAYERS``: a single-device run on device 0 as the comparison
point, then (4,1) and (2,2) meshes; each run's written K/V is held to
the dense forward's as in phase 5.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Everything compiled or read here comes from files git tracks; weights and
data are made from the seed.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import importlib.util
import json
import math
import sys
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

CONFIG = "gemma-2b"
PROMPT_LENS = (300, 320, 340, 360)   # a few hundred tokens; 19-23 pages
MAX_NEW = 16
ENGINE_KW = dict(page_size=16, num_pages=512, max_batch=4, chunk_size=512,
                 token_budget=512, max_pages_per_seq=32)
FOUR_CHIP_LAYERS = 4

# Tolerances, fixed before any chip run:
# * eager: TPU f32 matmuls and convolutions at default precision take
#   bf16 passes (8-bit mantissa), so the fused eager path and the jnp
#   reference agree to ~1e-3 relative; 2e-2 leaves room for the
#   different op order of the tape's backward.
EAGER_RTOL = 2e-2
# * kernel vs reference: both read bf16 pages and emit bf16; the kernel
#   keeps fp32 online-softmax statistics and casts probabilities to
#   bf16 for the PV product, the reference normalizes first — a few
#   bf16 ulps (2^-8 relative each) apart.
KERNEL_TOL = 3e-2
# * served tokens vs dense rollout: both run bf16 weights, so logits
#   carry bf16 rounding (one ulp is 2^-7 of the power of two below
#   |logit|), accumulated differently by the paged kernel and the dense
#   path over 18 layers.  A served token is accepted where its
#   reference logit is within TIE_ULPS ulps of the reference's best
#   (a near-tie rounding can flip); exact argmax agreement must reach
#   AGREEMENT_FLOOR.  With random weights the top-2 logit gap over a
#   256000-entry vocab averages ~0.2 against ~0.02 of rounding noise,
#   so a few flips in 32 tokens are expected and 0.75 is a floor, not
#   a target.
TIE_ULPS = 4
AGREEMENT_FLOOR = 0.75
# * engine-written K/V vs the dense forward's, per layer: relative L2
#   error ||served - dense|| / ||dense|| over every written position.
#   Layer 0 differs by bf16 rounding alone (2^-9 relative per rounding);
#   deeper layers add the rounding of differently ordered matmuls and
#   softmax sums (paged kernel vs dense attention, flat (T, D) vs
#   (1, S, D) products), compounding through the residual stream.  A
#   control repeats the comparison against a dense forward whose
#   attention output is dropped (wo scaled by 0): the error an engine
#   whose attention contributed nothing would show.  The control must
#   exceed KV_TOL at the last layer, or the check could not see an
#   attention fault.
KV_TOL = 5e-2
# * (2,2) mesh vs single device: tensor-parallel all-reduces add bf16
#   partial sums in another order, so a near-tie can flip a token and
#   everything after it differs.  Agreement is the mean over requests
#   of the matched prefix (tokens before the first difference) over
#   MAX_NEW; at a per-token flip rate of ~5% it is ~0.7.  (4,1) runs
#   no cross-device reduction and must match token for token.
TP_AGREEMENT_FLOOR = 0.5


class SmokeFailure(Exception):
    """A phase did not meet its contract."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def bf16_ulp(x: float) -> float:
    """One bf16 ulp at magnitude ``x`` (7 explicit mantissa bits)."""
    return 2.0 ** (math.floor(math.log2(max(abs(x), 1e-30))) - 7)


# ----------------------------------------------------------------------
# 1. device
# ----------------------------------------------------------------------

def device_phase(min_count: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"[device] platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    check(dev["platform"] == "tpu",
          f"no TPU found (platform {dev['platform']!r}); this smoke test "
          f"has no CPU fallback")
    check(dev["count"] >= min_count,
          f"{min_count} TPU devices needed, {dev['count']} found")
    return dev


# ----------------------------------------------------------------------
# 2. eager
# ----------------------------------------------------------------------

def _load_quickstart():
    spec = importlib.util.spec_from_file_location(
        "quickstart", ROOT / "examples" / "quickstart.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def eager_phase(seed: int, steps: int = 3) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    import repro.nn.functional as F
    import repro.optim as optim
    from repro.core import fuse
    from repro.kernels import ops
    from repro.nn import param_dict

    qs = _load_quickstart()
    np.random.seed(seed)
    repro.manual_seed(seed)
    model = qs.FullBasicModel()
    x, y = qs.make_data(64)
    p0 = {k: jnp.asarray(v.data) for k, v in param_dict(model).items()}

    def ref_loss(p, xd, yd):
        h = jax.lax.conv_general_dilated(
            xd, p["conv.weight"], (1, 1), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        h = jnp.maximum(h + p["conv.bias"].reshape(1, -1, 1, 1), 0.0)
        logits = h.reshape(xd.shape[0], -1) @ p["fc.w"] + p["fc.b"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(logp[jnp.arange(yd.shape[0]), yd])

    ref_l, ref_g = jax.jit(jax.value_and_grad(ref_loss))(
        p0, jnp.asarray(x.data), jnp.asarray(y.data))

    opt = optim.Adam(model.parameters(), lr=1e-3)
    losses = []
    with fuse.fusion():
        for step in range(steps):
            opt.zero_grad()
            loss = F.nll_loss(model(x), y)
            loss.backward()
            if step == 0:
                got_g = {k: jnp.asarray(v.grad.data)
                         for k, v in param_dict(model).items()}
            opt.step()
            losses.append(float(loss.data))

    check(all(math.isfinite(v) for v in losses),
          f"eager losses not finite: {losses}")
    l_err = abs(losses[0] - float(ref_l)) / max(abs(float(ref_l)), 1e-6)
    g_err = max(float(jnp.linalg.norm(got_g[k] - ref_g[k])
                      / jnp.maximum(jnp.linalg.norm(ref_g[k]), 1e-12))
                for k in ref_g)
    per_op = repro.dispatch_cache_stats()["per_op"]
    pallas = per_op.get("__fused_pallas__", {})
    n_native = pallas.get("hits", 0) + pallas.get("misses", 0)
    log(f"[eager] losses={losses} ref_loss={float(ref_l)!r} "
        f"loss_rel_err={l_err!r} grad_rel_err={g_err!r} "
        f"(tol {EAGER_RTOL}) native_fused_chains={n_native} "
        f"interpret={ops._interpret()}")
    check(l_err <= EAGER_RTOL and g_err <= EAGER_RTOL,
          "eager loss/gradients disagree with the jnp reference")
    check(not ops._interpret() and n_native > 0,
          "fusion queue did not lower any chain to the native Pallas "
          "fused_elementwise kernel")


# ----------------------------------------------------------------------
# 3. server
# ----------------------------------------------------------------------

def _compile_listener(records: list):
    """Collect trace/lower/compile seconds of the serving step, one
    record per compile (a trace event opens a record)."""
    def listen(event, duration, **kw):
        if "_unified_step" not in str(kw.get("fun_name", "")):
            return
        if event.endswith("jaxpr_trace_duration"):
            records.append(0.0)
        if records and event.startswith("/jax/core/compile/"):
            records[-1] += duration
    return listen


def server_phase(seed: int, config: str = CONFIG):
    """Serve len(PROMPT_LENS) streamed requests through the HTTP server;
    returns (engine, [(prompt, tokens)])."""
    import jax
    import numpy as np

    from repro.launch.serve import build_engine
    from repro.launch.server import HttpFrontendServer, sse_client
    from repro.serving.frontend import AsyncFrontend

    compile_s: list = []
    jax.monitoring.register_event_duration_secs_listener(
        _compile_listener(compile_s))

    t0 = time.perf_counter()
    eng = build_engine(None, config, seed=seed, **ENGINE_KW)
    cfg = eng.cfg
    log(f"[server] model={cfg.name} layers={cfg.n_layers} "
        f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads}x"
        f"{cfg.hd} vocab={cfg.vocab_size} dtype="
        f"{np.dtype(cfg.param_dtype).name} built in "
        f"{time.perf_counter() - t0!r} s")
    rng = np.random.RandomState(seed)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, n)]
               for n in PROMPT_LENS]
    fe = AsyncFrontend(eng, hwm_frac=0.95, max_queue_depth=64,
                       max_stream_tokens=256)
    server = HttpFrontendServer(fe, "127.0.0.1", 0)

    async def one(prompt):
        toks, terminal = [], None
        async for ev, data in sse_client(
                server.host, server.port,
                {"prompt": prompt, "max_new_tokens": MAX_NEW}):
            if ev == "token":
                toks.append(int(data["token"]))
            else:
                terminal = ev
        return toks, terminal

    async def drive():
        await server.start()
        log(f"[server] listening on {server.host}:{server.port}")
        clients = asyncio.ensure_future(
            asyncio.gather(*[one(p) for p in prompts]))
        try:
            await asyncio.wait({clients, server.pump_task},
                               return_when=asyncio.FIRST_COMPLETED)
            if server.pump_task.done():
                clients.cancel()
                server.pump_task.result()     # the engine's exception
                raise SmokeFailure("engine pump stopped mid-serve")
            return clients.result()
        finally:
            await server.stop()

    t1 = time.perf_counter()
    results = asyncio.run(drive())
    wall = time.perf_counter() - t1
    m = fe.stats()
    terminals = [t for _, t in results]
    log(f"[server] {len(results)} streams in {wall!r} s (wall clock, "
        f"compiles included): terminals={terminals} tokens="
        f"{[len(t) for t, _ in results]}")
    for k in ("executor_failures", "failed_requests", "bucket_compiles",
              "steps", "prefill_chunks", "decoded_tokens", "page_hwm",
              "tokens_dropped"):
        log(f"[server]   {k}={m[k]}")
    log(f"[server]   bucket_count={eng.bucket_count}")
    buckets = eng.executor.compiled_buckets
    for i, b in enumerate(buckets):
        s = compile_s[i] if i < len(compile_s) else None
        log(f"[server] compile bucket (T={b[0]}, P={b[1]}): "
            f"{s!r} s trace+lower+compile")
    check(all(t == "finished" for t in terminals),
          f"not every stream finished: {terminals}")
    check(all(len(t) == MAX_NEW for t, _ in results),
          "a stream returned fewer tokens than asked")
    check(m["executor_failures"] == 0 and m["failed_requests"] == 0,
          "executor or request failures while serving")
    check(m["bucket_compiles"] <= eng.bucket_count,
          "more compiles than shape buckets")
    check(m["tokens_dropped"] == 0, "tokens dropped by the front door")

    backend = _step_attention(eng, prompts[0][:24])
    log(f"[server] attention backend: requested="
        f"{eng.executor._attn_backend!r} compiled step contains "
        f"{backend}")
    check(backend == "paged_attention_fwd",
          "the compiled step does not run the Pallas paged kernel")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[server] device peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use')} bytes_in_use="
        f"{stats.get('bytes_in_use')} bytes_limit="
        f"{stats.get('bytes_limit')}")
    return eng, list(zip(prompts, [t for t, _ in results]))


def _step_attention(eng, prompt) -> str:
    """Lower (not run) the serving step for a fresh plan and report the
    paged-attention implementation inside it."""
    rid = eng.submit(prompt, max_new_tokens=1)
    try:
        plan = eng.scheduler.plan()
        text = eng.executor.lower(plan, eng.kv).as_text()
    finally:
        eng.cancel(rid)
    return ("paged_attention_fwd" if "paged_attention_fwd" in text
            else "the jnp reference gather")


# ----------------------------------------------------------------------
# 4. kernel vs reference on the engine's pool
# ----------------------------------------------------------------------

def kernel_phase(eng, seed: int) -> None:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.attention import paged_attention

    cfg = eng.cfg
    kp, vp = eng.kv.k[0], eng.kv.v[0]
    n_pages, ps, hkv, d = kp.shape
    live = np.flatnonzero(np.asarray(jnp.any(kp != 0, axis=(1, 2, 3))))
    check(live.size > 0, "the engine's page pool holds no written page")
    t, s, p = 64, 4, 32
    rng = np.random.RandomState(seed)
    tables = jnp.asarray(rng.choice(live, (s, p)), jnp.int32)
    seg = jnp.asarray(np.arange(t) % s, jnp.int32)
    pos = jnp.asarray(rng.randint(0, p * ps, t), jnp.int32)
    q = jax.random.normal(jax.random.key(seed), (t, cfg.n_heads, d),
                          jnp.float32).astype(kp.dtype)
    run = {b: jax.jit(functools.partial(paged_attention, backend=b))
           for b in ("pallas", "ref")}
    got = run["pallas"](q, kp, vp, tables, seg, pos).astype(jnp.float32)
    want = run["ref"](q, kp, vp, tables, seg, pos).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - want)
                        / (KERNEL_TOL + KERNEL_TOL * jnp.abs(want))))
    log(f"[kernel] paged_attention pallas vs ref on layer-0 pool "
        f"(N={n_pages}, ps={ps}, Hkv={hkv}, D={d}, {kp.dtype}) over "
        f"{live.size} written pages: max |diff|/(tol+tol*|ref|)={err!r} "
        f"(tol {KERNEL_TOL})")
    check(bool(jnp.all(jnp.isfinite(got))), "kernel output not finite")
    check(err <= 1.0, "paged kernel disagrees with the jnp reference")


# ----------------------------------------------------------------------
# 5. engine-written K/V and served tokens vs the dense forward
# ----------------------------------------------------------------------

def served_kv(eng, prompt, tag: str):
    """Serve ``prompt`` greedily on ``eng`` until MAX_NEW tokens are out
    and read back what the engine wrote for it.  Returns (the tokens
    whose K/V is in the pool, [(K, V) per layer] as (S, Hkv, D) float32
    host arrays)."""
    import jax.numpy as jnp
    import numpy as np

    rid = eng.submit(prompt, max_new_tokens=MAX_NEW + 1)
    for _ in range(4 * MAX_NEW):
        req = eng.running.get(rid)
        if req is not None and len(req.out_tokens) >= MAX_NEW:
            break
        eng.step()
    check(req is not None and len(req.out_tokens) == MAX_NEW,
          f"[{tag}] K/V probe request did not reach {MAX_NEW} tokens")
    seq = list(prompt) + list(req.out_tokens[:MAX_NEW - 1])
    check(eng.kv.lengths[rid] == len(seq),
          f"[{tag}] engine holds K/V for {eng.kv.lengths[rid]} tokens, "
          f"expected {len(seq)}")
    kv = []
    for layer in range(eng.cfg.n_layers):
        k, v, _ = eng.kv.gather([rid], layer)          # (1, Hkv, S, D)
        kv.append(tuple(np.asarray(a[0].transpose(1, 0, 2)
                                   .astype(jnp.float32)) for a in (k, v)))
    eng.cancel(rid)
    return seq, kv


def kv_check(cfg, params, seq, served, tag: str) -> None:
    """Hold the engine-written ``served`` K/V of ``seq`` to the dense
    causal forward of ``models/lm`` (``_apply_block``, the reference
    attention) and to its attention-dropped control (see KV_TOL)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import layers as L
    from repro.models import lm
    from repro.serving.executor import split_layer_params

    specs = list(cfg.pattern) * cfg.n_groups + list(cfg.tail)
    dense_cfg = replace(cfg, attn_backend="ref")

    @jax.jit
    def dense_kv(params, toks, attn_gain):
        s = toks.shape[0]
        x = jnp.take(params["embed"], toks, axis=0)[None]     # (1, S, D)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        aux = jnp.zeros((), jnp.float32)
        out = []
        for spec, lp in zip(specs, split_layer_params(cfg, params)):
            h = lm._norm(cfg, x, lp["norm1"], lp.get("norm1_b"))
            k, v = (jnp.swapaxes((h @ lp["attn"][w]).reshape(
                1, s, cfg.n_kv_heads, cfg.hd), 1, 2) for w in ("wk", "wv"))
            if cfg.rope_theta is not None:
                k = L.apply_rope(k, jnp.arange(s), cfg.rope_theta)
            out.append(tuple(jnp.swapaxes(a[0], 0, 1).astype(jnp.float32)
                             for a in (k, v)))
            attn = dict(lp["attn"], wo=lp["attn"]["wo"]
                        * attn_gain.astype(lp["attn"]["wo"].dtype))
            x, aux, _ = lm._apply_block(dense_cfg, spec, dict(lp, attn=attn),
                                        x, aux)
        return out

    n = len(seq)
    toks = np.zeros(-(-n // 128) * 128, np.int32)
    toks[:n] = seq

    def rel_errors(gain):
        want = dense_kv(params, jnp.asarray(toks), jnp.float32(gain))
        return [max(float(np.linalg.norm(got - np.asarray(w)[:n])
                          / np.linalg.norm(np.asarray(w)[:n]))
                    for got, w in zip(pair, ref))
                for pair, ref in zip(served, want)]

    errs, ctrl = rel_errors(1.0), rel_errors(0.0)
    for layer, (e, c) in enumerate(zip(errs, ctrl)):
        log(f"[{tag}] layer {layer}: written K/V vs dense rel err {e!r}; "
            f"control (attention dropped) {c!r}")
    log(f"[{tag}] {n} positions x {len(errs)} layers: max rel err "
        f"{max(errs)!r} (tol {KV_TOL}); last-layer control {ctrl[-1]!r}")
    check(max(errs) <= KV_TOL,
          f"[{tag}] engine-written K/V disagree with the dense forward")
    check(ctrl[-1] > KV_TOL,
          f"[{tag}] the K/V check cannot see an attention fault: dropping "
          f"attention stays within tolerance")


def reference_phase(served, written, seed: int,
                    config: str = CONFIG) -> None:
    """``served``: [(prompt, tokens)] of greedy requests; ``written``:
    :func:`served_kv`'s (sequence, K/V) from the same engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import device_params, model_config
    from repro.models import lm

    cfg = replace(model_config(None, config), attn_backend="ref")
    params = device_params(cfg, seed)
    kv_check(cfg, params, *written, tag="reference")
    b = len(served)
    length = max(len(pr) for pr, _ in served) + MAX_NEW
    length = -(-length // 128) * 128

    @jax.jit
    def logits_at(params, toks, idx):
        logits, _ = lm.forward(cfg, params, tokens=toks)
        return jnp.take_along_axis(
            logits, idx[:, :, None], axis=1).astype(jnp.float32)

    def pack(seqs):
        toks = np.zeros((b, length), np.int32)
        for i, sq in enumerate(seqs):
            toks[i, :len(sq)] = sq
        return jnp.asarray(toks)

    # rollout: the reference's own greedy continuation
    seqs = [list(pr) for pr, _ in served]
    for _ in range(MAX_NEW):
        idx = jnp.asarray([[len(sq) - 1] for sq in seqs], jnp.int32)
        nxt = np.asarray(jnp.argmax(
            logits_at(params, pack(seqs), idx)[:, 0], axis=-1))
        for sq, tok in zip(seqs, nxt):
            sq.append(int(tok))
    # teacher-forced: reference logits along the SERVED sequence
    forced = pack([list(pr) + list(tk[:-1]) for pr, tk in served])
    idx = jnp.asarray([[len(pr) - 1 + j for j in range(MAX_NEW)]
                       for pr, _ in served], jnp.int32)
    lg = np.asarray(logits_at(params, forced, idx))       # (B, NEW, V)

    # how far the reference's best logit stands above its second: a wide
    # margin everywhere means the comparison cannot tell small errors
    # apart (random weights can make greedy decoding repeat a token)
    top2 = np.sort(lg, axis=-1)[..., -2:]
    margins = [float(b - a) / bf16_ulp(b) for a, b in top2.reshape(-1, 2)]
    log(f"[reference] top-2 margin of the reference logits in bf16 ulps: "
        f"min {min(margins)!r} median {float(np.median(margins))!r}")
    exact, within = 0, 0
    for i, (pr, tk) in enumerate(served):
        roll = seqs[i][len(pr):]
        prefix = next((j for j, (a, c) in enumerate(zip(tk, roll))
                       if a != c), MAX_NEW)
        log(f"[reference] request {i}: served {tk}")
        log(f"[reference] request {i}: rollout {roll} "
            f"(matched prefix {prefix}/{MAX_NEW})")
        for j, tok in enumerate(tk):
            row = lg[i, j]
            best = float(row.max())
            gap = best - float(row[tok])
            tol = TIE_ULPS * bf16_ulp(best)
            exact += int(int(row.argmax()) == tok)
            within += int(gap <= tol)
            if gap > 0:
                log(f"[reference]   position {j}: served {tok} "
                    f"(logit {float(row[tok])!r}) vs reference best "
                    f"{int(row.argmax())} (logit {best!r}); gap {gap!r} "
                    f"tol {tol!r}")
    n = b * MAX_NEW
    log(f"[reference] exact agreement {exact}/{n} = {exact / n!r} "
        f"(floor {AGREEMENT_FLOOR}); within {TIE_ULPS} bf16 ulps of the "
        f"reference best: {within}/{n}")
    check(within == n, "a served token is not a near-best reference token")
    check(exact / n >= AGREEMENT_FLOOR,
          "exact agreement with the dense reference below its floor")


# ----------------------------------------------------------------------
# --four-chips: the sharded serving path
# ----------------------------------------------------------------------

def _device_memory(tag: str) -> list:
    import jax
    used = []
    for dv in jax.devices():
        st = dv.memory_stats() or {}
        used.append(st.get("bytes_in_use", 0))
        log(f"[mesh {tag}] device {dv.id}: bytes_in_use="
            f"{st.get('bytes_in_use')} peak_bytes_in_use="
            f"{st.get('peak_bytes_in_use')}")
    return used


def four_chip_phase(seed: int) -> None:
    import numpy as np

    from repro.launch.mesh import mesh_for_serving
    from repro.launch.serve import device_params, model_config
    from repro.serving.engine import ServingEngine

    cfg = replace(model_config(None, CONFIG), n_layers=FOUR_CHIP_LAYERS)
    rng = np.random.RandomState(seed)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, n)]
               for n in PROMPT_LENS]

    def run(dp: int, tp: int):
        mesh = mesh_for_serving(dp * tp, tp=tp)
        # one slot per replica: every replica plans the same bucket
        # shapes as the single device — one prefill step (T=512) and
        # decode steps (T=8) per request — so (4,1) differs from the
        # comparison point only by its partitioning
        kw = dict(ENGINE_KW, max_batch=1)
        t0 = time.perf_counter()
        eng = ServingEngine(cfg, device_params(cfg, seed, mesh),
                            mesh=mesh, **kw)
        ids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
        fin = {r.req_id: r.out_tokens for r in eng.run()}
        m = eng.metrics
        log(f"[mesh {dp}x{tp}] {len(fin)}/{len(ids)} finished in "
            f"{time.perf_counter() - t0!r} s (compiles included); "
            f"steps={m['steps']} bucket_compiles={m['bucket_compiles']} "
            f"(T, P)={eng.executor.compiled_buckets} "
            f"executor_failures={m['executor_failures']} "
            f"failed_requests={m['failed_requests']} "
            f"page_hwm_per_replica={m['page_hwm_per_replica']}")
        used = _device_memory(f"{dp}x{tp}")
        check(len(fin) == len(ids) and m["failed_requests"] == 0,
              f"mesh {dp}x{tp}: not every request finished")
        check(m["bucket_compiles"] <= eng.bucket_count,
              f"mesh {dp}x{tp}: more compiles than shape buckets")
        written = served_kv(eng, prompts[0], f"mesh {dp}x{tp}")
        del eng
        gc.collect()
        return [fin[i] for i in ids], used, written

    log(f"[mesh] {cfg.name} at full width, depth cut to {cfg.n_layers} "
        f"layers; {len(prompts)} greedy requests of {MAX_NEW} tokens")
    base, _, base_kv = run(1, 1)
    dp_out, used, dp_kv = run(4, 1)
    diff = [i for i, (a, c) in enumerate(zip(base, dp_out)) if a != c]
    log(f"[mesh 4x1] token-for-token vs device 0: "
        f"{'identical' if not diff else f'requests {diff} differ'}")
    check(min(used) >= 0.25 * max(used),
          "a device of the (4,1) mesh holds no share of params/pages")
    check(not diff, "(4,1) mesh tokens differ from the single device")
    tp_out, used, tp_kv = run(2, 2)
    prefixes = [next((j for j, (a, c) in enumerate(zip(x, z)) if a != c),
                     MAX_NEW) for x, z in zip(base, tp_out)]
    agree = sum(prefixes) / (len(prefixes) * MAX_NEW)
    log(f"[mesh 2x2] matched prefixes {prefixes}; agreement {agree!r} "
        f"(floor {TP_AGREEMENT_FLOOR})")
    for i, (x, z) in enumerate(zip(base, tp_out)):
        if x != z:
            log(f"[mesh 2x2]   request {i}: device 0 {x} vs (2,2) {z}")
    check(min(used) >= 0.25 * max(used),
          "a device of the (2,2) mesh holds no share of params/pages")
    check(agree >= TP_AGREEMENT_FLOOR,
          "(2,2) mesh agreement below its floor")
    # every run's written K/V against the dense forward on device 0
    params = device_params(cfg, seed)
    for (dp, tp), written in (((1, 1), base_kv), ((4, 1), dp_kv),
                              ((2, 2), tp_kv)):
        kv_check(cfg, params, *written, tag=f"mesh {dp}x{tp}")


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded serving path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
        log(f"[setup] compile cache: {enable_compile_cache()}")
        dev = device_phase(4 if args.four_chips else 1)
        if args.four_chips:
            four_chip_phase(args.seed)
        else:
            t0 = time.perf_counter()
            eager_phase(args.seed)
            log(f"[eager] phase {time.perf_counter() - t0!r} s")
            t0 = time.perf_counter()
            eng, served = server_phase(args.seed)
            log(f"[server] phase {time.perf_counter() - t0!r} s")
            kernel_phase(eng, args.seed)
            written = served_kv(eng, served[0][0], "reference")
            del eng
            gc.collect()
            t0 = time.perf_counter()
            reference_phase(served[:2], written, args.seed)
            log(f"[reference] phase {time.perf_counter() - t0!r} s")
    except SmokeFailure as e:
        log(f"[FAIL] {e}")
        return 1
    except Exception:                   # report any crash as a failure
        import traceback
        traceback.print_exc()
        log("[FAIL] exception (traceback on stderr)")
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
