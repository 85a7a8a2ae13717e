"""BENCHMARK.json against the contract's rules, the discovery of added
files by name, and the harness refusing to run off the chip."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from chipbench import spec  # noqa: E402


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_keeps_the_contract():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    spec.validate(bench)
    assert bench["command"][1] == "benchmarks/chip/run.py"
    assert all(not p.startswith("/") and ".." not in p
               for p in bench["command"] + bench["paths"])
    assert 1 <= bench["run_seconds"] <= 51
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        cell = spec.find_cell(ROOT, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("benchmarks/chip/")
        assert set(c["reduced"]) <= set(cfg["published"])
        for k in ("hidden_size", "intermediate_size",
                  "num_attention_heads", "num_key_value_heads"):
            assert k not in c["reduced"]
    assert len(json.dumps(bench)) < 64 * 1024


@pytest.mark.parametrize("bad", [
    {"name": "has space"}, {"name": "a,b"}, {"name": "a/b"},
    {"name": "µs"}, {"unit": "tokens per second"}, {"unit": "µs"},
    {"better": "up"}, {"source": "guess"}, {"moves": "nothing"},
])
def test_names_and_units_outside_the_allowed_characters_are_refused(bad):
    bench = _bench()
    bench["per_layer"][0].update(bad)
    with pytest.raises(spec.SpecError):
        spec.validate(bench)


def _copy(tmp_path):
    """A checkout holding BENCHMARK.json and the benchmark's files."""
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".work",
                                                  "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    return root


def test_added_traffic_cell_and_metric_are_found_by_name(tmp_path):
    root = _copy(tmp_path)
    hd = root / "benchmarks" / "chip"
    mix = json.loads((hd / "traffic" / "longdoc.json").read_text())
    mix["clients"] = 4
    (hd / "traffic" / "longdoc-few.json").write_text(json.dumps(mix))
    (hd / "cells" / "mistral-7b-l8.longdoc-few.json").write_text(
        json.dumps({"sample_requests": 4, "min_requests": 2,
                    "logit_gap": 1.0}))
    (hd / "metrics" / "tokens_seen.py").write_text(
        "def read(run):\n    return float(len(run))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "mistral-7b-l8.longdoc-few",
                               "config": "mistral-7b-l8",
                               "traffic": "longdoc-few", "chips": 1,
                               "why": "fewer long-document clients"})
    bench["per_layer"].append({"name": "tokens_seen", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "front door",
                               "moves": "output_tok_s",
                               "workloads": ["mistral-7b-l8.longdoc-few"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.find_cell(root, "mistral-7b-l8.longdoc-few",
                          harness_dir=hd)
    assert cell.traffic["clients"] == 4
    assert cell.config["name"] == "mistral-7b-l8"
    assert [m["name"] for m in cell.per_layer if
            m["name"] == "tokens_seen"] == ["tokens_seen"]
    assert spec.reader("tokens_seen", hd)([1, 2, 3]) == 3.0
    # the existing cells are untouched by the addition
    old = spec.find_cell(root, "mistral-7b-l8.longdoc", harness_dir=hd)
    assert "tokens_seen" not in [m["name"] for m in old.per_layer]


def test_unknown_device_has_no_peaks():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mistral-7b-l8.longdoc", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_harness_exits_nonzero_off_tpu_and_prints_no_result():
    p = _run_py(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_harness_exits_nonzero_with_only_its_own_files(tmp_path):
    p = _run_py(_copy(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
