"""The traffic generator: deterministic per seed, the stated
distributions and clipping, the same work for every seed, and closed
loops that never run dry."""

import hashlib
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import traffic  # noqa: E402

SEED = 2**31 + 12345          # seeds run past 32 signed bits


# an open-loop chat mix: Poisson arrivals, lognormal lengths clipped to
# a 4096 context
CHAT = {"loop": "open", "rate_per_s": 1.2,
        "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                          "min": 32, "max": 3584},
        "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                          "min": 16, "max": 512},
        "warmup_s": 10}


def _mix(name):
    if name == "chat":
        return dict(CHAT)
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["longdoc", "chat"])
def test_same_seed_same_requests_other_seed_same_sizes(name):
    mix = _mix(name)
    n = 128 if mix["loop"] == "closed" else None
    a = traffic.generate(mix, SEED, 32000, 30, n)
    b = traffic.generate(mix, SEED, 32000, 30, n)
    c = traffic.generate(mix, SEED + 1, 32000, 30, n)
    assert a == b
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # one schedule of sizes and gaps for every seed, in the same order
    for f in (lambda r: len(r.prompt), lambda r: r.max_new_tokens,
              lambda r: r.at):
        assert list(map(f, a)) == list(map(f, c))
    if mix["loop"] == "open":
        warm = mix["warmup_s"]
        for reqs in (a, c):
            win = [r.at for r in reqs if r.at >= warm]
            assert len(win) == round(mix["rate_per_s"] * 30)
            assert warm <= min(win) and max(win) < warm + 30


def test_lengths_follow_the_stated_distributions_and_clip():
    mix = _mix("chat")
    reqs = traffic.generate(mix, SEED, 64000, 200)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new_tokens for r in reqs])
    assert p.min() >= 32 and p.max() == 3584
    assert o.min() >= 16 and o.max() == 512
    assert abs(np.median(p) - 512) <= 8 and abs(np.median(o) - 128) <= 4
    # lognormal: log-lengths spread by sigma between the clips
    inner = p[(p > 32) & (p < 3584)]
    assert np.std(np.log(inner)) == pytest.approx(1.0, rel=0.15)
    assert (p + o).max() <= 4096
    ld = traffic.generate(_mix("longdoc"), SEED, 32000, 30, count=128)
    lp = np.array([len(r.prompt) for r in ld])
    lo = _mix("longdoc")["output_tokens"]
    assert 2304 <= lp.min() and lp.max() <= 3584
    assert np.mean(lp) == pytest.approx(2944, rel=0.01)
    assert max(len(r.prompt) + r.max_new_tokens for r in ld) \
        <= 3584 + lo["max"]
    assert all(r.at == 0 for r in ld)


# sha256 of ``[[prompt, max_new_tokens], ...]`` (``json.dumps``) of
# ``longdoc``'s 128 requests for SEED from the generator that held a
# closed loop's pool fixed at 128 (``"requests": 128``)
FIXED_POOL_128 = ("c54e91868cd2fe7d4490894609da6b28"
                  "e4d18df47e58ed3c7fede241b5b62d94")


def test_a_closed_loops_first_waves_are_the_old_fixed_pools():
    """Waves are drawn one after another as the clients take them, so
    a program that sends as many as the old fixed pool held sends the
    same requests it sent from that pool."""
    first = traffic.generate(_mix("longdoc"), SEED, 32000, 51, count=128)
    got = json.dumps([[r.prompt, r.max_new_tokens] for r in first])
    assert hashlib.sha256(got.encode()).hexdigest() == FIXED_POOL_128


def test_a_closed_loop_never_runs_dry():
    """However fast the program, its clients find a next request: the
    stream has no end, every wave holds the same prompt and answer
    lengths, and a closed loop's whole list cannot be asked for."""
    mix = json.loads((HERE / "tests" / "data" / "tiny-longdoc.json")
                     .read_text())
    c = mix["clients"]
    stream = traffic.requests(mix, SEED, 100, 51)
    waves = [[next(stream) for _ in range(c)] for _ in range(400)]
    for f in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert len({tuple(sorted(map(f, w))) for w in waves}) == 1
    with pytest.raises(ValueError):
        traffic.generate(mix, SEED, 100, 51)


def test_open_loop_gaps_are_exponential_at_the_rate():
    mix = dict(_mix("chat"), rate_per_s=20.0, warmup_s=1)
    reqs = [r for r in traffic.generate(mix, SEED, 100, 100) if r.at >= 1]
    gaps = np.diff([r.at for r in reqs])
    assert np.mean(gaps) == pytest.approx(1 / 20.0, rel=0.05)
    # exponential: the standard deviation equals the mean
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.1)


def test_prompt_ids_avoid_zero_and_share_no_prefix():
    reqs = traffic.generate(_mix("chat"), SEED, 50, 10)
    ids = np.concatenate([r.prompt for r in reqs])
    assert ids.min() >= 1 and ids.max() < 50
    firsts = {tuple(r.prompt[:16]) for r in reqs if len(r.prompt) >= 16}
    assert len(firsts) == sum(len(r.prompt) >= 16 for r in reqs)


@pytest.mark.parametrize("dist,u,want", [
    ({"dist": "uniform", "min": 10, "max": 20}, 0.5, 15),
    ({"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 1,
      "max": 10**6}, 0.5, 100),
    ({"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 90,
      "max": 200}, 0.01, 90),
    ({"dist": "fixed", "value": 7}, 0.9, 7),
])
def test_quantile(dist, u, want):
    assert traffic.quantile(dist, u) == want
    assert math.isfinite(traffic.quantile(dist, 0.999))
