"""Operation and byte counts, percentiles, rates and gaps of the chip
benchmark, against hand counts on small shapes."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import flops, stats  # noqa: E402

TINY = dict(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=2,
            d_ff=16, vocab=10, page_size=4, itemsize=2)


def test_matmul_flops_per_token_by_hand():
    m = flops.Model(**TINY)
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16, down 16x8 per layer
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert m.matmul_flops_per_token == 2 * per_layer * 2


def test_attention_flops_count_the_real_causal_context():
    m = flops.Model(**TINY)
    # positions 3, 4, 5 attend to 4, 5, 6 keys; heads 4, head_dim 2
    assert m.attn_flops([(3, 6, True)]) == 4 * (4 + 5 + 6) * 4 * 2
    # a decode token at position 9 (10 keys) and a chunk 0..2 (1+2 keys)
    assert m.attn_flops([(9, 10, True), (0, 2, False)]) == \
        4 * (10 + 1 + 2) * 8


def test_step_flops_take_the_head_only_for_sampled_rows():
    m = flops.Model(**TINY)
    spans = [(9, 10, True), (0, 2, False)]
    assert m.step_flops(spans) == (3 * m.matmul_flops_per_token
                                   + 2 * m.attn_flops(spans)
                                   + 1 * 2 * 8 * 10)


def test_kernel_bytes_read_each_live_page_once():
    m = flops.Model(**TINY)
    page = 4 * 2 * 2 * 2                  # ps * kv heads * hd * bf16
    # span ending at 10 covers pages 0..2; span ending at 2 covers page 0
    spans = [(9, 10, True), (0, 2, False)]
    qo = 2 * 3 * 4 * 2 * 2                # q and o of 3 tokens
    assert m.kernel_bytes(spans) == 2 * (3 + 1) * page + qo


def test_model_of_a_configuration_file():
    cfg = {"num_hidden_layers": 8, "hidden_size": 4096,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "intermediate_size": 14336, "vocab_size": 32000,
           "serve": {"page_size": 16, "dtype": "bfloat16"}}
    m = flops.Model.of(cfg)
    assert m.head_dim == 128 and m.itemsize == 2
    # 218.1 M weight parameters a layer (Mistral-7B)
    assert m.matmul_flops_per_token == 2 * 218_103_808 * 8


@pytest.mark.parametrize("values,q,want", [
    ([5.0], 95, 5.0),
    (list(range(1, 101)), 95, 95),
    (list(range(1, 21)), 95, 19),
    ([3, 1, 2], 50, 2),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def _rec(i, due, times, n=None, terminal="finished"):
    return {"i": i, "due": due, "sent": due, "times": list(times),
            "tokens": [1] * len(times), "terminal": terminal,
            "max_new_tokens": len(times) if n is None else n,
            "req_id": i}


def _steady(n_req=40, stall=None):
    """Requests due every 0.25 s with 10 tokens each, served by one
    step clock: a step every 20 ms, or every 100 ms inside ``stall``
    (a server that stalls serves every stream late).  A request's first
    token comes at the first step 50 ms or more after it is due."""
    steps, t = [], 0.0
    while t < 20.0:
        steps.append(t)
        t = round(t + (0.1 if stall and stall[0] <= t < stall[1]
                       else 0.02), 9)
    recs = []
    for i in range(n_req):
        due = 0.25 * i
        k = next(j for j, s in enumerate(steps)
                 if s >= round(due + 0.05, 9))
        recs.append(_rec(i, due, steps[k:k + 10]))
    return recs


def test_window_rate_ttft_and_gaps_on_synthetic_timestamps():
    recs = _steady()
    window = (2.0, 8.0)
    out = stats.end_to_end(recs, window, 20.0,
                           ["ttft_p95_ms", "itl_p95_ms", "output_tok_s"])
    assert out["ttft_p95_ms"] == pytest.approx(60.0)
    assert out["itl_p95_ms"] == pytest.approx(20.0)
    n = sum(1 for r in recs for t in r["times"] if 2.0 <= t < 8.0)
    assert out["output_tok_s"] == pytest.approx(n / 6.0)
    assert len(stats.sent_in_window(recs, window)) == 24


def test_a_stall_inside_the_window_moves_both_tails():
    window = (2.0, 8.0)
    wanted = ["ttft_p95_ms", "itl_p95_ms"]
    base = stats.end_to_end(_steady(), window, 20.0, wanted)
    stalled = stats.end_to_end(_steady(stall=(5.0, 6.0)), window, 20.0,
                               wanted)
    assert stalled["itl_p95_ms"] == pytest.approx(100.0)
    assert stalled["ttft_p95_ms"] > base["ttft_p95_ms"] + 30


def test_failed_requests_count_as_missing():
    recs = _steady()
    window = (2.0, 8.0)
    for r in recs[8:10]:
        r["terminal"] = "http_503"
        r["times"], r["tokens"] = [], []
    recs[10]["tokens"] = recs[10]["tokens"][:3]       # too few tokens
    assert sum(stats.failed(r) for r in
               stats.sent_in_window(recs, window)) == 3
    ttfts = stats.ttfts(recs, window, end=20.0)
    assert sorted(ttfts)[-3:] == [20.0 - 2.5, 20.0 - 2.25, 20.0 - 2.0]
    # 3 of 24 missing: the 95th percentile is a missing one
    assert stats.percentile(ttfts, 95) > 10


def test_lateness_of_the_generator():
    recs = _steady()
    recs[12]["sent"] = recs[12]["due"] + 0.1
    late = stats.lateness(recs, (2.0, 8.0))
    assert late["late_max_ms"] == pytest.approx(100.0)
