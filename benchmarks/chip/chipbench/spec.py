"""Reading ``BENCHMARK.json`` and finding a cell's files by name.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric lives in a file of its own, found by the name
that ``BENCHMARK.json`` gives it:

    <config entry's "file">                 sizes and serving settings
    references/<config "reference">.py      plain float32 reference
    traffic/<traffic>.json                  the traffic mix
    cells/<cell>.json                       the cell's correctness limit
    metrics/<metric>.py                     per-layer metric reader

so a later change adds a configuration, a mix or a metric by adding files
and entries, and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

HARNESS_DIR = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks the contract."""


@dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    config: dict                 # the configuration file's content
    traffic: dict                # the traffic file's content
    limits: dict                 # the cell file's content
    chips: int
    end_to_end: List[dict]       # the metrics this cell reports
    per_layer: List[dict]


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load(root: Path) -> dict:
    """``BENCHMARK.json`` at ``root``, checked against the contract."""
    bench = _read_json(Path(root) / "BENCHMARK.json")
    validate(bench)
    return bench


def validate(bench: dict) -> None:
    """Raise :class:`SpecError` where names, units, sources or
    cross-references break the rules of the benchmark's contract."""
    def name(v, what):
        if not isinstance(v, str) or not NAME_RE.match(v):
            raise SpecError(f"bad {what} name {v!r}")

    names = [c["name"] for c in bench["configs"]]
    for c in bench["configs"]:
        name(c["name"], "config")
        for k in c["reduced"]:
            name(k, "reduced key")
    cells = [w["name"] for w in bench["workloads"]]
    for w in bench["workloads"]:
        name(w["name"], "workload")
        name(w["traffic"], "traffic")
        if w["config"] not in names:
            raise SpecError(f"{w['name']}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            raise SpecError(f"{w['name']}: chips must be 1 or 4")
    metrics = bench["end_to_end"] + bench["per_layer"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in metrics:
        name(m["name"], "metric")
        if not UNIT_RE.match(m["unit"]):
            raise SpecError(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"{m['name']}: better must be lower|higher")
        if m["source"] not in SOURCES:
            raise SpecError(f"{m['name']}: bad source {m['source']!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                raise SpecError(f"{m['name']}: unknown workload {w!r}")
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            raise SpecError(f"{m['name']}: moves unknown {m['moves']!r}")
    for group in (names, cells, [m["name"] for m in metrics]):
        if len(group) != len(set(group)):
            raise SpecError(f"duplicate names in {group}")


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports ``metric`` (no ``workloads``: all)."""
    return cell in metric.get("workloads", [cell])


def find_cell(root: Path, name: str,
              harness_dir: Path = HARNESS_DIR) -> Cell:
    """The cell ``name`` with its configuration, traffic and limits."""
    bench = load(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SpecError(f"unknown workload {name!r}; "
                        f"cells: {', '.join(work)}")
    w = work[name]
    centry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _read_json(Path(root) / centry["file"])
    traffic = _read_json(harness_dir / "traffic" / f"{w['traffic']}.json")
    limits = _read_json(harness_dir / "cells" / f"{name}.json")
    return Cell(
        name=name, config=config, traffic=traffic, limits=limits,
        chips=w["chips"],
        end_to_end=[m for m in bench["end_to_end"] if reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if reports(m, name)])


def _load_module(path: Path, modname: str):
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, harness_dir: Path = HARNESS_DIR
           ) -> Callable[[object], Optional[float]]:
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    mod = _load_module(harness_dir / "metrics" / f"{metric}.py",
                       f"chipbench_metric_{metric.replace('.', '_')}")
    return mod.read


def reference(config: dict, harness_dir: Path = HARNESS_DIR):
    """The plain reference module named by the configuration."""
    name = config["reference"]
    return _load_module(harness_dir / "references" / f"{name}.py",
                        f"chipbench_reference_{name}")


def peaks(device_kind: str, harness_dir: Path = HARNESS_DIR) -> Dict:
    """The peak rates of ``device_kind``; a device not in the table is
    an error, never a default."""
    table = _read_json(harness_dir / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json")
    return table["devices"][device_kind]
