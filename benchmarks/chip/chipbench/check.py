"""Whether the served tokens are right: the plain reference's verdict.

Once the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed and always holding the
longest one, goes through the configuration's float32 reference: the
prompt and the served tokens, teacher-forced, give the reference's
logits at each position where a token was served.

The number compared is the widest gap, in logits, by which a served
token lies below the reference's best choice at its position.  For a
greedy token that is ``max(l) - l[served]``.  For a sampled one (the
engine draws ``argmax(l / T + g)`` over the top-p support, with Gumbel
noise ``g`` keyed by ``(seed, position)``), the same on the perturbed
scores, times ``T``; the noise is made again here from JAX's own PRNG
with the engine's documented keying, and the support is the reference's
own nucleus.  A right program reads rounding; a wrong token reads the
distance to the right one.

The control (``quant``) runs the reference in place of the program at a
lower precision and reads, at the same positions, the gap of the token
that it puts first.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import stats

MIN_UNIFORM = 1e-20


def sample(records: List[dict], prompts: Sequence[Sequence[int]],
           seed: int, n: int) -> List[dict]:
    """``n`` finished requests: the longest (prompt and output), and the
    rest drawn from the seed."""
    done = [r for r in records if not stats.failed(r)]
    if not done:
        return []
    size = [len(prompts[r["i"]]) + len(r["tokens"]) for r in done]
    longest = int(np.argmax(size))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(pick)]


def gumbel(seed: int, positions: jnp.ndarray, vocab: int) -> jnp.ndarray:
    keys = jax.vmap(lambda p: jax.random.fold_in(
        jax.random.key(jnp.uint32(seed)), p))(positions)
    u = jax.vmap(lambda k: jax.random.uniform(
        k, (vocab,), jnp.float32, minval=MIN_UNIFORM))(keys)
    return -jnp.log(-jnp.log(u))


def nucleus(scaled: jnp.ndarray, top_p: float) -> jnp.ndarray:
    """Rows' top-p support: the smallest descending prefix whose
    exclusive cumulative probability stays below ``top_p``."""
    if top_p >= 1.0:
        return jnp.ones_like(scaled, bool)
    srt = -jnp.sort(-scaled, axis=-1)
    p = jax.nn.softmax(srt, axis=-1)
    keep = (jnp.cumsum(p, axis=-1) - p) < top_p
    thr = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True)
    return scaled >= thr


@functools.partial(jax.jit, static_argnames=("t", "top_p", "seed"))
def scores(logits: jnp.ndarray, positions: jnp.ndarray, *, t: float,
           top_p: float, seed: int):
    """What the engine takes the argmax of, in logit units: over the
    support, and over every token."""
    if t <= 0:
        return logits, logits
    scaled = logits / t
    z = t * (scaled + gumbel(seed, positions, logits.shape[-1]))
    return jnp.where(nucleus(scaled, top_p), z, -jnp.inf), z


@functools.partial(jax.jit, static_argnames=("t", "top_p", "seed"))
def _gap(ref: jnp.ndarray, tokens: jnp.ndarray, positions: jnp.ndarray,
         n: jnp.ndarray, *, t: float, top_p: float, seed: int):
    best, every = scores(ref, positions, t=t, top_p=top_p, seed=seed)
    own = jnp.take_along_axis(every, tokens[:, None], axis=-1)[:, 0]
    rows = jnp.max(best, axis=-1) - own
    valid = jnp.arange(rows.shape[0]) < n
    return jnp.max(jnp.where(valid, rows, -jnp.inf)), \
        jnp.argmax(jnp.where(valid, rows, -jnp.inf))


def _kw(sampling: dict) -> dict:
    return {"t": float(sampling["temperature"]),
            "top_p": float(sampling["top_p"]),
            "seed": int(sampling["seed"])}


def _pad(a: np.ndarray, n: int) -> jnp.ndarray:
    out = np.zeros(n, np.int32)
    out[:len(a)] = a
    return jnp.asarray(out)


def gap(ref_logits: jnp.ndarray, tokens: np.ndarray,
        positions: np.ndarray, sampling: dict) -> tuple:
    """Widest gap by which ``tokens`` lie below the reference's best,
    and the row where it is.  ``ref_logits`` may carry padding rows
    past ``len(tokens)``."""
    rows = ref_logits.shape[0]
    g, at = _gap(ref_logits, _pad(tokens, rows), _pad(positions, rows),
                 jnp.int32(len(tokens)), **_kw(sampling))
    return float(g), int(at)


def first_choice(logits: jnp.ndarray, positions: np.ndarray,
                 sampling: dict) -> np.ndarray:
    """The token a model with these logits serves at each position."""
    best, _ = scores(logits, _pad(positions, logits.shape[0]),
                     **_kw(sampling))
    return np.asarray(jnp.argmax(best, axis=-1))[:len(positions)]


def readings(reference, config: dict, weights, picked: List[dict],
             prompts: Sequence[Sequence[int]], sampling: dict,
             controls: Sequence[str] = ()) -> Dict[str, float]:
    """The program's gap over ``picked`` and, for each control
    precision, the gap of the control's own choices."""
    seqs, rows, served, pos = [], [], [], []
    for r in picked:
        p, out = list(prompts[r["i"]]), list(r["tokens"])
        seqs.append(p + out[:-1])
        rows.append(np.arange(len(p) - 1, len(p) + len(out) - 1))
        served.append(np.asarray(out, np.int32))
        pos.append(np.arange(len(p), len(p) + len(out), dtype=np.int32))
    ref = reference.logits(config, weights, seqs, rows)
    per = [gap(lg, s, q, sampling) for lg, s, q in zip(ref, served, pos)]
    out = {"logit_gap": max(g for g, _ in per),
           # per request: prompt length, tokens served, its widest gap
           # and the output index where it is
           "by_request": [[len(prompts[r["i"]]), len(r["tokens"]), g, at]
                          for r, (g, at) in zip(picked, per)]}
    for mode in controls:
        ctl = reference.logits(config, weights, seqs, rows, quant=mode)
        out[f"control_gap.{mode}"] = max(
            gap(lg, first_choice(c, q, sampling), q, sampling)[0]
            for lg, c, q in zip(ref, ctl, pos))
        del ctl
    out["tokens_compared"] = int(sum(len(s) for s in served))
    out["requests_compared"] = len(picked)
    return out


def verdict(values: Dict[str, float], limits: dict,
            unfinished: int) -> Dict[str, Dict[str, float]]:
    """Each number compared beside its limit."""
    return {"logit_gap": {"value": values.get("logit_gap"),
                          "limit": float(limits["logit_gap"])},
            "requests_compared": {
                "value": values.get("requests_compared", 0),
                "limit": int(limits["min_requests"])},
            "never_finished": {"value": unfinished, "limit": 0}}


def control_checks(values: Dict[str, float], limits: dict,
                   unfinished: int) -> Dict[str, Dict[str, dict]]:
    """Each control's verdict: the control's gap (``control_gap.<mode>``)
    in the place of the program's, held to the same limits."""
    prefix = "control_gap."
    return {k[len(prefix):]: verdict(dict(values, logit_gap=v), limits,
                                     unfinished)
            for k, v in values.items() if k.startswith(prefix)}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    g = checks["logit_gap"]
    return (g["value"] is not None and g["value"] <= g["limit"]
            and checks["requests_compared"]["value"]
            >= checks["requests_compared"]["limit"]
            and checks["never_finished"]["value"] == 0)


def unfinished(records: List[dict]) -> int:
    return sum(1 for r in records if r["terminal"] == "never_finished")
