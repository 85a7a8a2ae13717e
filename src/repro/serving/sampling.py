"""In-jit token sampling for the serving executor.

The sampling contract (``ServingEngine(greedy=...)``, per-request
temperature / top-k / top-p) is honored INSIDE the jitted
``unified_step``: logits never round-trip to host — the only arrays
that cross the device boundary per step are the sampled token ids
(``(S, K+1)`` int32) and the per-slot fault flags.  This is the §5.2
separation applied to the sampling tail of the step: the host decides
*what* to sample (per-request params ride as tiny operand arrays), the
device decides *which token* comes out.

Determinism contract (the replay anchor every test leans on):

  * the PRNG key for a sampled token depends ONLY on
    ``(seed, position)`` — ``fold_in(key(seed), position)`` where
    ``position`` is the token's absolute index in its sequence.  The
    same request replayed on a rebuilt engine, after a preemption, or
    inside a speculative batch therefore draws the SAME token at every
    position, which is what makes speculative decoding exact for any
    temperature (see ``spec.py``), not just for greedy;
  * ``temperature <= 0`` short-circuits to pure argmax — bitwise the
    pre-sampling behavior — so greedy serving pays no PRNG cost in
    semantics (the noise lanes are computed but discarded by a
    ``where``, keeping one fused executable for both modes);
  * filtering is threshold-based: ties at the top-k boundary or at the
    top-p cutoff value are all kept.  Deterministic, and identical
    between the in-jit path and the host reference used by the parity
    tests.

Sharded serving note: under R data replicas the executor flattens the
per-replica sampling operands to one (R·S·(K+1),) batch before calling
``sample_tokens`` — the position-keyed PRNG makes this layout-oblivious
(a slot's token depends on its own (seed, position), never on which
replica row or mesh shape carried it), which is exactly why seeded
outputs are bitwise-identical across mesh shapes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["SamplingParams", "filter_logits", "sample_tokens",
           "sample_ref"]

_NEG_INF = jnp.finfo(jnp.float32).min
_MIN_TEMP = 1e-6
_MIN_UNIFORM = 1e-20


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration.

    ``temperature <= 0`` means greedy (argmax); ``top_k <= 0`` disables
    the top-k filter; ``top_p >= 1`` disables the nucleus filter.
    ``seed`` roots the request's PRNG stream — two requests with equal
    seeds draw identical noise at equal positions (replay-friendly; use
    distinct seeds for independent randomness)."""
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    @property
    def greedy(self) -> bool:
        """True when this config degenerates to argmax decoding."""
        return self.temperature <= 0.0

    def validate(self) -> "SamplingParams":
        """Raise ``ValueError`` on out-of-range fields (negative top_k,
        top_p outside (0, 1]); returns self for chaining."""
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        return self


def filter_logits(logits: jnp.ndarray, temperature: jnp.ndarray,
                  top_k: jnp.ndarray, top_p: jnp.ndarray) -> jnp.ndarray:
    """Temperature-scale one ``(V,)`` logits row and mask everything
    outside the top-k / top-p support to ``-inf``.

    Fixed-shape (jit/vmap-safe): the per-row ``top_k`` is applied as a
    value threshold (the k-th largest scaled logit; ties at the
    boundary are kept), and ``top_p`` keeps the smallest sorted prefix
    whose exclusive cumulative probability is still below ``top_p``
    (so the token that crosses the boundary is included — the standard
    nucleus rule).  ``top_k <= 0`` and ``top_p >= 1`` are no-ops."""
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    scaled = logits / jnp.maximum(temperature, _MIN_TEMP)

    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v)
    srt = jnp.sort(scaled)[::-1]                       # descending
    kth = srt[k_eff - 1]
    keep = scaled >= kth

    ranks = jnp.arange(v)
    in_k = ranks < k_eff
    srt_k = jnp.where(in_k, srt, _NEG_INF)
    probs = jax.nn.softmax(srt_k)
    cum = jnp.cumsum(probs)
    keep_sorted = ((cum - probs) < top_p) & in_k       # exclusive cumsum
    thr = jnp.min(jnp.where(keep_sorted, srt_k, jnp.inf))
    keep = keep & (scaled >= thr)
    return jnp.where(keep, scaled, _NEG_INF)


def _fold_keys(seeds: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """(R,) seeds x (R,) positions -> (R,) typed PRNG keys, entirely
    on device: ``fold_in(key(seed), position)`` per row."""
    return jax.vmap(
        lambda s, p: jax.random.fold_in(jax.random.key(s), p)
    )(seeds, positions)


def sample_tokens(logits: jnp.ndarray, temperature: jnp.ndarray,
                  top_k: jnp.ndarray, top_p: jnp.ndarray,
                  seeds: jnp.ndarray, positions: jnp.ndarray
                  ) -> jnp.ndarray:
    """Sample one token per ``(R, V)`` logits row, fully in-jit.

    ``temperature``/``top_k``/``top_p``/``seeds``/``positions`` are
    ``(R,)`` per-row arrays (operands, not statics — per-request params
    never trigger a recompile).  Stochastic rows draw via the
    Gumbel-max trick over the filtered support (``logits +
    (-log(-log(u)))``, one elementwise pass XLA fuses, and one that
    GSPMD partitions under a mesh); rows with ``temperature <= 0``
    return plain ``argmax(logits)``.  Returns ``(R,)`` int32 token
    ids."""
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    filtered = jax.vmap(filter_logits)(logits, temperature, top_k, top_p)
    keys = _fold_keys(seeds, positions)
    uniform = jax.vmap(
        lambda k: jax.random.uniform(k, (v,), jnp.float32,
                                     minval=_MIN_UNIFORM)
    )(keys)
    perturbed = filtered + -jnp.log(-jnp.log(uniform))
    stochastic = jnp.argmax(perturbed, axis=-1)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temperature > 0.0, stochastic,
                     greedy).astype(jnp.int32)


def sample_ref(logits: jnp.ndarray, params: SamplingParams,
               position: int,
               seed: Optional[int] = None) -> int:
    """Host-side single-row reference: sample the token the in-jit path
    would produce for one ``(V,)`` logits row at ``position``.  The
    parity tests pin ``sample_tokens`` against this (and against an
    independent numpy filter reference)."""
    seed = params.seed if seed is None else seed
    tok = sample_tokens(
        jnp.asarray(logits, jnp.float32)[None],
        jnp.asarray([params.temperature], jnp.float32),
        jnp.asarray([params.top_k], jnp.int32),
        jnp.asarray([params.top_p], jnp.float32),
        jnp.asarray([seed], jnp.uint32),
        jnp.asarray([position], jnp.int32))
    return int(tok[0])
