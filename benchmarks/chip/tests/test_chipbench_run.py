"""Whole runs of the harness on the CPU at a tiny size: the server, the
load generator in its child process, the window, the reference check.

The chip check is skipped (``require_chip=False``); everything else is
a run.  A sound program comes out correct; the control (the reference
in the program's place at a lower precision) and each fault planted in
the timed path come out not correct."""

import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

from chipbench import cell, spec, weights  # noqa: E402

DATA = HERE / "tests" / "data"
# the tiny configuration's limit, set as the cells' are: program runs
# read 0 to 0.003; on the greedy closed loop the control reads 0.062
# (int8) and 0.44 (fp8) at this size
LIMITS = {"sample_requests": 4, "min_requests": 2, "logit_gap": 0.02}


E2E = [{"name": n, "unit": u} for n, u in (
    ("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"),
    ("output_tok_s", "tokens/s"), ("setup_s", "s"))]


def _cell(mix: str) -> spec.Cell:
    tr = json.loads((DATA / f"{mix}.json").read_text())
    return spec.Cell(
        name=mix, config=json.loads((DATA / "tiny.json").read_text()),
        traffic=tr, limits=LIMITS, chips=1, end_to_end=E2E, per_layer=[])


def _run(mix: str, seed: int, tmp_path, **kw) -> dict:
    return cell.run_cell(_cell(mix), seed, 2.0, False,
                         t_start=time.time(), work_dir=tmp_path,
                         require_chip=False, **kw)


def test_sound_run_is_correct_and_the_control_is_not(tmp_path):
    out = _run("tiny-chat", 2**31 + 77, tmp_path, controls=("fp8",))
    assert out["correct"], out["checks"]
    assert out["attempted"] == 16 and out["failed"] == 0
    m = out["metrics"]
    assert set(m) == {"ttft_p95_ms", "itl_p95_ms", "output_tok_s",
                      "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert not out["controls"]["fp8"]["correct"], out["controls"]


def test_each_control_in_the_programs_place_is_not_correct(tmp_path):
    """The greedy closed loop of the longdoc cell, at the tiny size: the
    program passes, and each control's own first choices, put in the
    place of the served tokens, fail the same verdict."""
    out = _run("tiny-longdoc", 2**31 + 79, tmp_path,
               controls=("int8", "fp8"))
    assert out["correct"], out["checks"]
    for mode in ("int8", "fp8"):
        ctl = out["controls"][mode]
        assert not ctl["correct"], out["controls"]
        assert ctl["logit_gap"] == out["readings"][f"control_gap.{mode}"]
        assert ctl["logit_gap"] > LIMITS["logit_gap"]


def _planted(monkeypatch, fault: str) -> None:
    """Break the program's timed path underneath the harness."""
    from repro.serving.executor import Executor
    execute = Executor.execute

    def broken(self, plan, kv):
        old = ([a.copy() for a in kv.k], [a.copy() for a in kv.v])
        toks, bad = execute(self, plan, kv)
        if fault == "token_altered":
            toks = (toks + 1) % self.cfg.vocab_size
        elif fault == "state_unchanged":
            kv.put_kv(*old)        # the step's K/V writes are lost
        return toks, bad
    monkeypatch.setattr(Executor, "execute", broken)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, tmp_path,
                                                  fault):
    _planted(monkeypatch, fault)
    out = _run("tiny-longdoc", 2**31 + 78, tmp_path)
    assert not out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] > LIMITS["logit_gap"]


def test_one_layer_made_alone_equals_the_whole_set():
    dims = weights.Dims.of(json.loads((DATA / "tiny.json").read_text()))
    seed = 2**32 + 3
    whole = weights.program_params(dims, seed)
    alone = weights.Float32Weights(dims, seed)
    for i in range(dims.n_layers):
        want = jax.tree_util.tree_map(lambda a: a[i], whole["groups"][0])
        got = alone.layer(i)
        for w, g in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(w, np.float32),
                                          np.asarray(g))
    for name in weights.TOP_SLOTS:
        np.testing.assert_array_equal(np.asarray(whole[name], np.float32),
                                      np.asarray(alone.top(name)))
    assert weights.seed32(seed) != weights.seed32(3)
