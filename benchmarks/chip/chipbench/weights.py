"""Seeded random weights, made by the benchmark on the device.

The benchmark makes the weights, hands them to the program, and makes
them again, layer by layer, for the reference: the reference then holds
the very values the program served, and takes nothing the program made.
Every array comes from its own key, ``fold_in(key(seed32), slot)``, so
one layer can be made alone and equals the same layer of the whole set
bit for bit.

Layout: what ``repro.models.lm.init_params`` gives a pattern of one
``attn``/``dense`` block, which is what ``ServingEngine`` takes::

    embed (V, D), lm_head (D, V), final_norm (D,) f32,
    groups[0]: norm1/norm2 (L, D) f32, attn.wq (L, D, H*hd),
               attn.wk/wv (L, D, Hkv*hd), attn.wo (L, H*hd, D),
               mlp.w_gate/w_up (L, D, F), mlp.w_down (L, F, D)

Matrices are normal with standard deviation ``1/sqrt(fan_in)``, the
embedding 0.02, and the norm weights ``1 + 0.1 * normal`` (not ones, so
a path that dropped a norm weight would show).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

TOP_SLOTS = {"embed": 0, "lm_head": 1, "final_norm": 2}
LAYER_SLOT0 = 100


@dataclass(frozen=True)
class Dims:
    """The widths a weight set is made at (from a configuration file)."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    dtype: str = "bfloat16"

    @classmethod
    def of(cls, config: dict) -> "Dims":
        c = config
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or
                   c["hidden_size"] // c["num_attention_heads"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   dtype=c["serve"]["dtype"])


def seed32(seed: int) -> int:
    """A 32-bit weight seed from any whole number (``jax.random.key``
    drops the high bits of a seed of 2**32 or more)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def _matrix(key, rows: int, cols: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (rows, cols), jnp.float32)
            * (1.0 / np.sqrt(rows))).astype(dtype)


def _norm(key, d: int) -> jnp.ndarray:
    return 1.0 + 0.1 * jax.random.normal(key, (d,), jnp.float32)


def layer(dims: Dims, key) -> dict:
    """One block's weights from its key."""
    dt = jnp.dtype(dims.dtype)
    d, q, kv, f = (dims.d_model, dims.n_heads * dims.head_dim,
                   dims.n_kv_heads * dims.head_dim, dims.d_ff)
    ks = jax.random.split(key, 9)
    return {
        "norm1": _norm(ks[0], d),
        "attn": {"wq": _matrix(ks[1], d, q, dt),
                 "wk": _matrix(ks[2], d, kv, dt),
                 "wv": _matrix(ks[3], d, kv, dt),
                 "wo": _matrix(ks[4], q, d, dt)},
        "norm2": _norm(ks[5], d),
        "mlp": {"w_gate": _matrix(ks[6], d, f, dt),
                "w_up": _matrix(ks[7], d, f, dt),
                "w_down": _matrix(ks[8], f, d, dt)},
    }


def top(dims: Dims, base, name: str) -> jnp.ndarray:
    """The embedding, the output head or the final norm weight."""
    key = jax.random.fold_in(base, TOP_SLOTS[name])
    dt = jnp.dtype(dims.dtype)
    if name == "embed":
        return (jax.random.normal(key, (dims.vocab, dims.d_model),
                                  jnp.float32) * 0.02).astype(dt)
    if name == "lm_head":
        return _matrix(key, dims.d_model, dims.vocab, dt)
    return _norm(key, dims.d_model)


def base_key(seed: int):
    return jax.random.key(seed32(seed))


def layer_key(base, i: int):
    return jax.random.fold_in(base, LAYER_SLOT0 + i)


@functools.partial(jax.jit, static_argnums=(0,))
def _all(dims: Dims, base) -> dict:
    keys = jax.vmap(lambda i: layer_key(base, i))(jnp.arange(dims.n_layers))
    return {name: top(dims, base, name) for name in TOP_SLOTS} | {
        "groups": [jax.vmap(functools.partial(layer, dims))(keys)]}


def program_params(dims: Dims, seed: int) -> dict:
    """The whole weight set in the program's layout, made in one jitted
    call on the default device."""
    return _all(dims, base_key(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_f32(dims: Dims, base, i) -> dict:
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  layer(dims, layer_key(base, i)))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _top_f32(dims: Dims, base, name: str) -> jnp.ndarray:
    return top(dims, base, name).astype(jnp.float32)


class Float32Weights:
    """The same weights, one piece at a time, in float32 (for the
    reference, after the program's copy is freed)."""

    def __init__(self, dims: Dims, seed: int):
        self.dims = dims
        self.base = base_key(seed)

    def layer(self, i: int) -> dict:
        return _layer_f32(self.dims, self.base, i)

    def top(self, name: str) -> jnp.ndarray:
        return _top_f32(self.dims, self.base, name)
