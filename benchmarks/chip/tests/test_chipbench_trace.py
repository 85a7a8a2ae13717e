"""Trace reduction (busy union, kernel time by name, idle gaps and what
the host did in them) and the per-layer readers, on a trace built by
hand."""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import cell, flops, spec, tracefile  # noqa: E402
from chipbench.tracefile import Event  # noqa: E402

MS = 1e6                                  # ns


def _trace():
    """Two 10 ms steps of one chip in a 40 ms window, each a fusion, the
    paged kernel and another fusion; the host planned in the first gap
    and wrote to streams in the second."""
    ops, mods = [], []
    for t0 in (5 * MS, 25 * MS):
        ops += [Event("fusion.1", t0, t0 + 3 * MS),
                Event("paged_attention_fwd.3", t0 + 3 * MS, t0 + 7 * MS),
                Event("fusion.2", t0 + 6 * MS, t0 + 10 * MS)]
        mods.append(Event("jit__unified_step(123)", t0, t0 + 10 * MS))
    spans = [Event("chipbench.pump", 0, 40 * MS),
             Event("chipbench.plan", 0, 5 * MS),
             Event("chipbench.execute", 4 * MS, 16 * MS),
             Event("unrelated", 15 * MS, 25 * MS)]
    tr = tracefile.Trace(ops={0: ops}, modules={0: mods},
                         spans=[s for s in spans
                                if s.name.startswith("chipbench.")],
                         window=(0.0, 40 * MS))
    tr.spans.remove(spans[0])        # the event loop ran 15-25 ms
    tr.spans.append(Event("chipbench.pump", 0, 15 * MS))
    tr.spans.append(Event("chipbench.pump", 25 * MS, 40 * MS))
    return tr


def test_busy_union_merges_overlapping_ops():
    tr = _trace()
    assert tracefile.busy_ns(tr.ops[0], tr.window) == 20 * MS
    assert tracefile.busy_ns(tr.ops[0], (0, 30 * MS)) == 15 * MS


def test_kernel_time_by_stable_name():
    tr = _trace()
    assert tracefile.time_of(tr.ops[0], "paged_attention") == (8 * MS, 2)
    assert tracefile.time_of(tr.modules[0], "_unified_step") == (20 * MS, 2)
    top = tracefile.top_ops(tr.ops[0], 2)
    assert [n for n, _ in top] == ["fusion", "paged_attention_fwd"]
    assert [t for _, t in top] == pytest.approx([0.014, 0.008])
    assert tracefile.op_kind(
        "%paged_attention_fwd.4 = bf16[512,8,7,128]{3,2,1,0} "
        "custom-call(s32[64,256]{1,0} %copy-done.52)") == \
        "paged_attention_fwd"


def test_idle_gaps_and_what_the_host_did():
    tr = _trace()
    gaps = tracefile.idle_gaps(tr.ops[0], tr.window)
    assert gaps == [(0, 5 * MS), (15 * MS, 25 * MS), (35 * MS, 40 * MS)]
    by = dict(tracefile.attribute_gaps(gaps, tr.spans))
    assert by == {"plan": pytest.approx(0.005),
                  "event_loop": pytest.approx(0.010),
                  "pump": pytest.approx(0.005)}


def _run(trace, steps):
    model = flops.Model(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                        head_dim=16, d_ff=128, vocab=100, page_size=16,
                        itemsize=2)
    log = cell.Log(steps=steps, plan=[(0.0, 0.001), (0.02, 0.021)],
                   commit=[(0.016, 0.0165), (0.036, 0.0365)],
                   compiles=[(0.5, "jit(_unified_step)")])
    return cell.Run(cell="x", config={}, traffic={}, model=model,
                    peaks={"bf16_flops_per_s": 1e9,
                           "hbm_bytes_per_s": 1e9},
                    records=[], window=(0.0, 1.0), log=log, host_window=(0.0, 0.04), trace=trace)


STEPS = [(0.002, 0.016, 16, 4, [(0, 16, True), (40, 41, True)]),
         (0.022, 0.036, 8, 4, [(16, 17, True), (41, 42, True)])]


def test_readers_on_a_hand_built_run():
    run = _run(_trace(), STEPS)
    m = run.model
    read = {name: spec.reader(name)(run) for name in (
        "step_mfu.closed", "paged_attn_roofline.closed", "idle_share",
        "plan_ms_mean", "window_compiles")}
    work = sum(m.step_flops(s[4]) for s in STEPS)
    assert read["step_mfu.closed"] == pytest.approx(
        100 * work / (0.020 * 1e9))
    least = 2 * sum(max(m.attn_flops(s[4]) / 1e9,
                        m.kernel_bytes(s[4]) / 1e9) for s in STEPS)
    assert read["paged_attn_roofline.closed"] == pytest.approx(
        100 * least / 0.008)
    assert read["idle_share"] == pytest.approx(50.0)
    assert read["plan_ms_mean"] == pytest.approx((1 + 1 + .5 + .5) / 2)
    assert read["window_compiles"] == 0


def test_readers_return_nothing_without_a_trace_or_steps():
    assert spec.reader("step_mfu.closed")(_run(None, STEPS)) is None
    assert spec.reader("paged_attn_roofline.closed")(_run(None, STEPS)) \
        is None
    assert spec.reader("idle_share")(_run(None, STEPS)) is None
    empty = _trace()
    empty.ops[0] = [e for e in empty.ops[0]
                    if "paged_attention" not in e.name]
    assert spec.reader("paged_attn_roofline.closed")(_run(empty, STEPS)) \
        is None
    assert spec.reader("step_mfu.closed")(_run(_trace(), [])) is None


def test_a_listed_metric_with_nothing_to_read_is_an_error():
    empty = _trace()
    empty.ops[0] = [e for e in empty.ops[0]
                    if "paged_attention" not in e.name]
    listed = {"name": "paged_attn_roofline.closed", "unit": "%",
              "workloads": ["x"]}
    with pytest.raises(cell.MissingMetric, match="paged_attn_roofline"):
        cell.per_layer(_run(empty, STEPS), [listed])
    # a metric for every cell, with nothing to read here, is left out
    unlisted = {"name": "paged_attn_roofline.closed", "unit": "%"}
    assert cell.per_layer(_run(empty, STEPS), [unlisted]) == {}
    got = cell.per_layer(_run(_trace(), STEPS), [listed])
    assert got["paged_attn_roofline.closed"]["unit"] == "%"
