"""``BENCHMARK.json``: every cell finds its files by name, every name and
unit keeps to the allowed characters, and the rate and tail of a window
are taken over all its requests."""

import json
import shutil
import statistics
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import harness  # noqa: E402

MANIFEST = harness.load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


def test_keys_are_the_contracts():
    assert set(MANIFEST) == TOP_KEYS
    for part, (must, may) in ENTRY_KEYS.items():
        for entry in MANIFEST[part]:
            assert must <= set(entry) <= must | may, (part, entry)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_every_file_by_name(cell):
    c = harness.resolve(MANIFEST, cell, ROOT)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"], ROOT))
        assert m["moves"] in names
    assert c.chips in (1, 4)
    assert c.config["name"] == next(
        w["config"] for w in MANIFEST["workloads"] if w["name"] == cell)


def test_names_and_units_use_the_allowed_characters():
    names = [e["name"] for part in ("configs", "workloads", "end_to_end",
                                    "per_layer") for e in MANIFEST[part]]
    names += [w["config"] for w in MANIFEST["workloads"]]
    names += [w["traffic"] for w in MANIFEST["workloads"]]
    names += [k for c in MANIFEST["configs"] for k in c["reduced"]]
    for name in names:
        assert harness.NAME.match(name), name
    for part in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in MANIFEST[part]]
        assert len(got) == len(set(got)), part
    for m in METRICS:
        assert harness.UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for text in ([w["why"] for w in MANIFEST["workloads"]]
                 + [c["why"] for c in MANIFEST["configs"]]
                 + [c["source"] for c in MANIFEST["configs"]]
                 + [m["layer"] for m in MANIFEST["per_layer"]]
                 + MANIFEST["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text, text


def test_bounds_and_run_length_are_in_range():
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
    setup = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == "setup_s")
    assert setup["bound"] <= 0.25
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_paths_hold_the_command_and_every_file():
    for p in MANIFEST["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") \
            and ".." not in p.split("/")
    under = tuple(p + "/" for p in MANIFEST["paths"])
    assert MANIFEST["command"][1].startswith(under)
    for c in MANIFEST["configs"]:
        assert c["file"].startswith(under)
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))


def test_a_cell_is_added_with_files_and_entries_alone(tmp_path):
    """A new configuration, mix and metric: new files plus manifest
    entries, and the harness resolves them unchanged."""
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench")
    bench = tmp_path / "perfbench"
    cfg = json.loads((bench / "configs" / "taxi_object.json").read_text())
    cfg.update(name="taxi_small", objects=2)
    (bench / "configs" / "taxi_small.json").write_text(json.dumps(cfg))
    mix = json.loads(
        (bench / "traffic" / "filter.client_pallas.json").read_text())
    mix["clients"] = 1
    (bench / "traffic" / "filter.one_client.json").write_text(
        json.dumps(mix))
    (bench / "metrics" / "scans_per_s.one.py").write_text(
        "def read(r):\n    return 1.0\n")
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append(dict(manifest["configs"][0],
                                    name="taxi_small",
                                    file="perfbench/configs/taxi_small.json"))
    manifest["workloads"].append({"name": "taxi_small.one", "chips": 1,
                                  "config": "taxi_small",
                                  "traffic": "filter.one_client",
                                  "why": "one client"})
    manifest["end_to_end"][0]["workloads"].append("taxi_small.one")
    manifest["per_layer"].append({
        "name": "scans_per_s.one", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "storage",
        "moves": "scan_rows_per_s", "workloads": ["taxi_small.one"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    c = harness.resolve(harness.load_manifest(tmp_path), "taxi_small.one",
                        tmp_path)
    assert c.config["objects"] == 2 and c.traffic["clients"] == 1
    assert [m["name"] for m in c.per_layer] == ["scans_per_s.one"]
    assert {m["name"] for m in c.end_to_end} == {"scan_rows_per_s",
                                                 "setup_s"}
    assert harness.metric_reader("scans_per_s.one", tmp_path)(None) == 1.0


def test_an_unknown_cell_or_missing_file_is_refused(tmp_path):
    with pytest.raises(KeyError):
        harness.resolve(MANIFEST, "no.such.cell", ROOT)
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["workloads"][0]["traffic"] = "no_such_mix"
    with pytest.raises(FileNotFoundError):
        harness.resolve(manifest, manifest["workloads"][0]["name"], ROOT)


def test_rate_is_over_the_whole_window():
    assert harness.rate(120 * 1_677_721, 40.0) == 120 * 1_677_721 / 40.0
    with pytest.raises(ValueError):
        harness.rate(1, 0)


def test_tail_is_over_all_requests_not_a_median_of_chunks():
    rng = np.random.default_rng(3)
    # two halves of a window: a fast one and a slow one
    lat = list(rng.gamma(2, 0.1, 200)) + list(rng.gamma(2, 0.5, 50))
    p90 = harness.percentile(lat, 90)
    assert p90 == pytest.approx(float(np.percentile(lat, 90)), rel=1e-12)
    chunks = [harness.percentile(lat[i:i + 50], 90)
              for i in range(0, len(lat), 50)]
    assert p90 != pytest.approx(statistics.median(chunks), rel=0.05)
    assert harness.percentile([2.0], 90) == 2.0
    with pytest.raises(ValueError):
        harness.percentile([], 90)


def test_checks_pass_at_or_under_their_limit():
    ok, bad = harness.Check("a", 0, 0), harness.Check("b", 1, 0)
    assert ok.ok and not bad.ok
    assert harness.check_lines([ok, bad]) == [
        "check a 0 limit 0 ok", "check b 1 limit 0 FAILED"]
