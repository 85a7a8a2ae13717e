"""Distributed step builders: train / prefill / decode under pjit.

``make_train_step`` assembles loss → grad → clip → optimizer into one
pjit-ed function with full sharding annotations (params per
``distributed.sharding``, optimizer state inheriting param specs =
ZeRO-sharded, batch over ('pod','data')).  Buffer donation on the state
makes the update in-place at the XLA level.

Also the CLI trainer used by the examples: synthetic/real DataLoader,
checkpoint/restart (preemption-safe), straggler-aware step timing.
"""

from __future__ import annotations

import functools
import time
from dataclasses import replace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed import act_sharding as AS
from ..distributed import sharding as S
from ..models import lm as LM
from ..optim.functional import clip_by_global_norm, make_optimizer

Params = Dict[str, Any]


# ----------------------------------------------------------------------
# spec derivation for optimizer state
# ----------------------------------------------------------------------

def opt_state_specs(opt_state_abs, param_spec_tree):
    """Optimizer-state PartitionSpecs: moment tensors inherit the param
    spec; Adafactor row/col drop the reduced dim's entry; scalars
    replicate."""

    def like(sub_abs, sub_specs):
        return jax.tree_util.tree_map(
            lambda leaf, spec: spec, sub_abs, sub_specs)

    specs = {}
    for key, sub in opt_state_abs.items():
        if key in ("m", "v", "momentum"):
            specs[key] = like(sub, param_spec_tree)
        elif key == "fac":
            def fac_spec(p_spec, fac_leaf_dict):
                out = {}
                for k2, leaf in fac_leaf_dict.items():
                    if k2 == "row":      # param shape minus last dim
                        out[k2] = P(*tuple(p_spec)[:-1]) \
                            if len(tuple(p_spec)) else P()
                    elif k2 == "col":    # minus second-to-last
                        t = tuple(p_spec)
                        out[k2] = P(*(t[:-2] + t[-1:])) if len(t) >= 2 \
                            else P()
                    else:                # "v" for 1-d params
                        out[k2] = P(*tuple(p_spec))
                return out

            specs[key] = jax.tree_util.tree_map(
                fac_spec, param_spec_tree, sub,
                is_leaf=lambda x: isinstance(x, dict)
                and ("row" in x or "v" in x))
        else:
            specs[key] = jax.tree_util.tree_map(lambda _: P(), sub)
    return specs


def shard_tree(mesh: Mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# ----------------------------------------------------------------------
# step builders
# ----------------------------------------------------------------------

def make_train_step(cfg: LM.LMConfig, mesh: Mesh, *,
                    optimizer: str = "adamw", lr: float = 3e-4,
                    grad_clip: float = 1.0, donate: bool = True,
                    batch_abs: Optional[Dict] = None,
                    accum_steps: int = 1,
                    foreach: bool = False,
                    opt_kwargs: Optional[Dict] = None):
    """Returns (train_step_jit, state_shardings, abstract_state,
    batch_shardings_fn).  Pass ``batch_abs`` (ShapeDtypeStructs) so the
    batch input shardings are pinned at jit time (required to
    ``.lower()`` against abstract inputs).

    ``foreach=True`` selects the fused multi-tensor optimizer update
    (bucketed concat, one kernel per dtype bucket) — fewer HLO ops and
    faster compiles on single-device/replicated meshes, but keep it off
    when params are sharded (concat gathers across shards)."""
    opt_kwargs = dict(opt_kwargs or {})
    if optimizer == "adafactor":
        opt_kwargs.setdefault("lr", lr)
    else:
        opt_kwargs.setdefault("lr", lr)
    init_opt, update_opt = make_optimizer(optimizer, foreach=foreach,
                                          **opt_kwargs)

    params_abs = LM.abstract_params(cfg)
    opt_abs = jax.eval_shape(init_opt, params_abs)
    p_specs = S.param_specs(cfg, params_abs, mesh)
    o_specs = opt_state_specs(opt_abs, p_specs)
    state_specs = {"params": p_specs, "opt": o_specs, "step": P()}
    state_shardings = shard_tree(mesh, state_specs)
    state_abs = {"params": params_abs, "opt": opt_abs,
                 "step": jax.ShapeDtypeStruct((), jnp.int32)}

    def train_step(state, batch):
        def loss_fn(p, b):
            with AS.scope(mesh):
                return LM.lm_loss(cfg, p, b)

        if accum_steps <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(state["params"],
                                                      batch)
        else:
            # gradient accumulation: scan over microbatches; activation
            # memory scales with batch/accum_steps instead of batch
            def micro(i):
                return jax.tree_util.tree_map(
                    lambda x: x.reshape(
                        (accum_steps, x.shape[0] // accum_steps)
                        + x.shape[1:])[i] if hasattr(x, 'shape') and
                    x.ndim > 0 else x, batch)

            def body(carry, i):
                loss_acc, grad_acc = carry
                l, g = jax.value_and_grad(loss_fn)(state["params"],
                                                   micro(i))
                return (loss_acc + l,
                        jax.tree_util.tree_map(jnp.add, grad_acc, g)), None

            zero_grads = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32),
                state["params"])
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero_grads),
                jnp.arange(accum_steps))
            loss = loss / accum_steps
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps,
                                           grads)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        new_params, new_opt = update_opt(grads, state["opt"],
                                         state["params"])
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = {"loss": loss, "grad_norm": gnorm}
        return new_state, metrics

    def batch_shardings(b_abs):
        return {k: NamedSharding(mesh, s)
                for k, s in S.batch_specs(cfg, b_abs, mesh).items()}

    jit_step = jax.jit(
        train_step,
        in_shardings=(state_shardings,
                      batch_shardings(batch_abs) if batch_abs else None),
        out_shardings=(state_shardings,
                       {"loss": S.replicated(mesh),
                        "grad_norm": S.replicated(mesh)}),
        donate_argnums=(0,) if donate else (),
    )
    return jit_step, state_shardings, state_abs, batch_shardings


def make_serve_step(cfg: LM.LMConfig, mesh: Mesh, *, batch: int,
                    max_seq: int, cache_dtype=jnp.bfloat16,
                    donate_cache: bool = True):
    """Single-token decode step, cache donated (in-place update)."""
    params_abs = LM.abstract_params(cfg)
    p_shardings = shard_tree(mesh, S.param_specs(cfg, params_abs, mesh))
    cache_abs = LM.abstract_cache(cfg, batch, max_seq, cache_dtype)
    c_shardings = shard_tree(mesh, S.cache_specs(cfg, cache_abs, mesh))

    def serve_step(params, cache, tokens, pos):
        with AS.scope(mesh):
            logits, new_cache = LM.decode_step(cfg, params, cache, tokens,
                                               pos)
        return logits, new_cache

    jit_step = jax.jit(
        serve_step,
        in_shardings=(p_shardings, c_shardings, None, None),
        out_shardings=(None, c_shardings),
        donate_argnums=(1,) if donate_cache else (),
    )
    return jit_step, p_shardings, params_abs, c_shardings, cache_abs


# ----------------------------------------------------------------------
# the runnable trainer (examples/end-to-end driver calls this)
# ----------------------------------------------------------------------

def train_loop(cfg: LM.LMConfig, *, steps: int, batch_size: int,
               seq_len: int, mesh: Optional[Mesh] = None,
               optimizer: str = "adamw", lr: float = 3e-4,
               foreach: bool = False,
               checkpoint_dir: Optional[str] = None,
               checkpoint_every: int = 100,
               log_every: int = 10, seed: int = 0,
               straggler_threshold: float = 3.0) -> Dict[str, Any]:
    """Real training on synthetic LM data.  Restores from checkpoint_dir
    if present (fault-tolerant restart); saves asynchronously."""
    from ..checkpoint import CheckpointManager
    from ..data import DataLoader, SyntheticLMDataset

    if mesh is None:
        from .mesh import make_local_mesh
        mesh = make_local_mesh()

    step_fn, state_shardings, state_abs, batch_sharding_fn = \
        make_train_step(cfg, mesh, optimizer=optimizer, lr=lr,
                        foreach=foreach)

    with mesh:
        params = jax.jit(
            functools.partial(LM.init_params, cfg),
            out_shardings=state_shardings["params"],
        )(jax.random.key(seed))
        init_opt, _ = make_optimizer(optimizer, lr=lr)
        opt = jax.jit(init_opt,
                      out_shardings=state_shardings["opt"])(params)
        state = {"params": params, "opt": opt,
                 "step": jnp.zeros((), jnp.int32)}

        ckpt = None
        start_step = 0
        if checkpoint_dir:
            ckpt = CheckpointManager(checkpoint_dir)
            restored = ckpt.restore_latest(state, mesh)
            if restored is not None:
                state = restored
                start_step = int(jax.device_get(state["step"]))

        ds = SyntheticLMDataset(cfg.vocab_size, seq_len, size=1 << 20,
                                seed=seed)
        loader = DataLoader(ds, batch_size=batch_size, shuffle=True,
                            num_workers=2, seed=seed, drop_last=True)

        history = []
        step_times = []
        it = iter(loader)
        t_loop = time.perf_counter()
        for step in range(start_step, steps):
            try:
                tokens, labels = next(it)
            except StopIteration:
                it = iter(loader)
                tokens, labels = next(it)
            batch = {"tokens": jnp.asarray(tokens.data),
                     "labels": jnp.asarray(labels.data)}
            t0 = time.perf_counter()
            state, metrics = step_fn(state, batch)
            loss = float(jax.device_get(metrics["loss"]))
            dt = time.perf_counter() - t0
            step_times.append(dt)
            # straggler watchdog: flag steps >> median
            if len(step_times) > 10:
                med = float(np.median(step_times[-50:]))
                if dt > straggler_threshold * med:
                    print(f"[straggler] step {step}: {dt:.3f}s "
                          f"(median {med:.3f}s)")
            history.append(loss)
            if step % log_every == 0:
                tok_s = batch_size * seq_len / dt
                print(f"step {step:5d}  loss {loss:.4f}  "
                      f"{dt*1e3:6.1f} ms/step  {tok_s:,.0f} tok/s")
            if ckpt and step > 0 and step % checkpoint_every == 0:
                ckpt.save_async(state, step)
        if ckpt:
            ckpt.save(state, steps)
            ckpt.wait()
        wall = time.perf_counter() - t_loop
        return {"losses": history, "steps": steps - start_step,
                "wall_time_s": wall, "final_loss": history[-1]
                if history else None}
