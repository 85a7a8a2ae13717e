"""Plain float32 reference of a dense decoder with grouped-query attention
(the Llama / Mistral / Yi block), in ``jax.numpy`` only.

Per the published description (Hugging Face ``LlamaForCausalLM`` /
``MistralForCausalLM``): token embedding; per layer
``x += o_proj(attn(rope(q_proj(n1)), rope(k_proj(n1)), v_proj(n1)))``
with ``n1 = rmsnorm(x) * w1``, causal attention over every earlier
position, each of ``num_key_value_heads`` K/V heads shared by
``num_attention_heads / num_key_value_heads`` query heads, scores scaled
by ``head_dim ** -0.5``; then ``x += down(silu(gate(n2)) * up(n2))``;
final RMS norm and the output head.  RoPE rotates the two halves of each
head (``rotate_half``) at angles ``position * rope_theta ** (-2i/d)``.
No sliding window (the configurations state none).

It imports nothing of the program.  It runs layer by layer over the
sequences it is given, in float32 at ``highest`` matmul precision, with
attention in blocks of queries, so that a long sequence fits beside one
layer's weights.

``quant`` makes the control: every matmul with weights (q, k, v, o,
gate, up, down, output head) takes its weights per output column and its
activations per token to ``int8`` or ``fp8`` (e4m3) with a symmetric
absmax scale, as a W8A8 serving path would; the rest stays float32.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # queries per attention block
PAD = 2048             # sequences pad to a multiple of this, rows to a
                       # power of two: few shapes, so few compiles
QMAX = {"int8": 127.0, "fp8": 448.0}


def fake_quant(a: jnp.ndarray, axis: int, mode: str) -> jnp.ndarray:
    """``a`` rounded to ``mode`` with one absmax scale per slice along
    ``axis``, back in float32."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / QMAX[mode]
    s = jnp.where(s > 0, s, 1.0)
    if mode == "int8":
        q = jnp.clip(jnp.round(a / s), -127, 127)
    else:
        q = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * s


def mm(x: jnp.ndarray, w: jnp.ndarray, quant: Optional[str]) -> jnp.ndarray:
    if quant is not None:
        x, w = fake_quant(x, -1, quant), fake_quant(w, 0, quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x (n, heads, d) at positions 0..n-1."""
    n, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray
              ) -> jnp.ndarray:
    """Causal GQA: q (n, H, d), k/v (n, Hkv, d) -> (n, H, d)."""
    n, h, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    qb = q.reshape(n // Q_BLOCK, Q_BLOCK, hkv, g, d)
    kpos = jnp.arange(n)

    def block(args):
        i, qi = args
        s = jnp.einsum("bkgd,nkd->kgbn", qi, k,
                       precision=HIGHEST) * (d ** -0.5)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgbn,nkd->bkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(n // Q_BLOCK), qb))
    return o.reshape(n, h, d)


@functools.partial(jax.jit, static_argnames=("h", "hkv", "d", "eps",
                                             "theta", "quant"))
def block(w: dict, x: jnp.ndarray, *, h: int, hkv: int, d: int,
          eps: float, theta: float, quant: Optional[str]) -> jnp.ndarray:
    n = x.shape[0]
    a = w["attn"]
    n1 = rms_norm(x, w["norm1"], eps)
    q = rope(mm(n1, a["wq"], quant).reshape(n, h, d), theta)
    k = rope(mm(n1, a["wk"], quant).reshape(n, hkv, d), theta)
    v = mm(n1, a["wv"], quant).reshape(n, hkv, d)
    x = x + mm(attention(q, k, v).reshape(n, h * d), a["wo"], quant)
    n2 = rms_norm(x, w["norm2"], eps)
    m = w["mlp"]
    return x + mm(jax.nn.silu(mm(n2, m["w_gate"], quant))
                  * mm(n2, m["w_up"], quant), m["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x: jnp.ndarray, rows: jnp.ndarray, norm: jnp.ndarray,
         lm_head: jnp.ndarray, *, eps: float,
         quant: Optional[str]) -> jnp.ndarray:
    return mm(rms_norm(x[rows], norm, eps), lm_head, quant)


@jax.jit
def _embed(table: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(table, tokens, axis=0)


def logits(config: dict, weights, seqs: Sequence[Sequence[int]],
           rows: Sequence[Sequence[int]],
           quant: Optional[str] = None) -> List[jnp.ndarray]:
    """Float32 logits of ``seqs[i]`` at the positions ``rows[i]``.

    ``weights`` gives float32 arrays: ``top(name)`` for ``embed``,
    ``final_norm``, ``lm_head`` and ``layer(i)`` for block ``i``, in the
    layout of ``chipbench.weights``.  Each result has its rows padded
    to a power of two (at least 64); the first ``len(rows[i])`` are the
    ones asked for."""
    c = config
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c.get("head_dim") or c["hidden_size"] // h
    kw = dict(h=h, hkv=hkv, d=d, eps=float(c["rms_norm_eps"]),
              theta=float(c["rope_theta"]), quant=quant)
    embed = weights.top("embed")
    xs = []
    for s in seqs:
        n = -(-len(s) // PAD) * PAD     # pad: later positions cannot
        toks = np.zeros(n, np.int32)      # reach earlier ones (causal)
        toks[:len(s)] = s
        xs.append(_embed(embed, jnp.asarray(toks)))
    del embed
    for i in range(c["num_hidden_layers"]):
        w = weights.layer(i)
        xs = [block(w, x, **kw) for x in xs]
        del w
    norm, lm = weights.top("final_norm"), weights.top("lm_head")
    out = []
    for x, r in zip(xs, rows):
        idx = np.zeros(1 << max(6, (len(r) - 1).bit_length()), np.int32)
        idx[:len(r)] = r
        out.append(head(x, jnp.asarray(idx), norm, lm, eps=kw["eps"],
                        quant=quant))
    return out
