"""Finds a cell's files by name and holds the arithmetic every cell shares.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
configuration's ``file`` holds its sizes and names its ``driver``, a
module of ``perfbench/drivers/``; the traffic mix is
``perfbench/traffic/<traffic>.json``; a per-layer metric is read by
``perfbench/metrics/<name>.py``.  A cell, a mix or a metric is added as
files and manifest entries, with no edit here.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]      # the manifest's entries this cell reports
    per_layer: list[dict]


def load_manifest(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(manifest: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` with its configuration, traffic mix and
    metrics, every file found by name.  A name that is not in the manifest
    raises ``KeyError``; a missing file raises ``FileNotFoundError``."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(traffic_file(w["traffic"], root))
    e2e = [m for m in manifest["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    for m in per_layer:
        metric_file(m["name"], root)
    driver_module(config["driver"])
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer)


def traffic_file(name: str, root: Path = ROOT) -> Path:
    return _existing(root / "perfbench" / "traffic" / f"{name}.json")


def metric_file(name: str, root: Path = ROOT) -> Path:
    return _existing(root / "perfbench" / "metrics" / f"{name}.py")


def _existing(path: Path) -> Path:
    if not path.is_file():
        raise FileNotFoundError(path)
    return path


def driver_module(name: str):
    """``perfbench/drivers/<name>.py``: the general generator and system
    driver that the configuration names."""
    return importlib.import_module(f"perfbench.drivers.{name}")


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(readings)`` function of one per-layer metric."""
    path = metric_file(name, root)
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- arithmetic over a window --------------------------------------------------


def rate(total: float, seconds: float) -> float:
    """Work per second over the whole window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return total / seconds


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of every value, linearly interpolated
    between the two nearest ranks (NumPy's default), over all requests
    of the window together."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class Check:
    """One number compared with the plain reference, and its limit: the
    run is correct only where every number is at or under its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def check_lines(checks: list[Check]) -> list[str]:
    return [f"check {c.name} {c.value} limit {c.limit} "
            f"{'ok' if c.ok else 'FAILED'}" for c in checks]
