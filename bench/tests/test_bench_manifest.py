"""BENCHMARK.json keeps the contract's rules, and a new configuration,
traffic mix or per-layer metric is found by its name alone."""
import copy
import hashlib
import json
import time

import pytest

from bench import harness, manifest
from bench.tests.conftest import ROOT, add_tiny_cells, copy_bench


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


def test_committed_manifest_is_valid(man):
    manifest.validate(man, ROOT)


def test_every_cell_reports_what_each_metric_moves(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        for cell in manifest.cells_reporting(man, m):
            assert cell in manifest.cells_reporting(man, e2e[m["moves"]])


def _broken(man, path, value):
    bad = copy.deepcopy(man)
    obj = bad
    for k in path[:-1]:
        obj = obj[k]
    obj[path[-1]] = value
    return bad


@pytest.mark.parametrize("path,value", [
    (("workloads", 0, "name"), "has space"),
    (("workloads", 0, "name"), "a/b"),
    (("workloads", 0, "name"), "x" * 65),
    (("workloads", 0, "name"), "-leading"),
    (("configs", 0, "reduced"), ["hidden size"]),
    (("end_to_end", 0, "unit"), "tasks per second"),
    (("end_to_end", 0, "unit"), "x" * 17),
    (("end_to_end", 0, "unit"), "µs"),
    (("end_to_end", 0, "better"), "up"),
    (("end_to_end", 0, "bound"), 0.3),
    (("end_to_end", 0, "bound"), 0.001),
    (("end_to_end", 0, "source"), "program_span"),
    (("per_layer", 0, "moves"), "no_such_metric"),
    (("per_layer", 0, "workloads"), ["no_such_cell"]),
    (("per_layer", 0, "layer"), "two\nlines"),
    (("workloads", 0, "chips"), 2),
    (("workloads", 0, "why"), "x" * 201),
    (("run_seconds",), 52),
    (("command",), ["python3", "/abs/run.py"]),
    (("paths",), ["../outside"]),
])
def test_rule_breaks_are_refused(man, path, value):
    with pytest.raises(manifest.ManifestError):
        manifest.validate(_broken(man, path, value))


def test_metric_listing_a_cell_that_does_not_report_its_target(man):
    bad = copy.deepcopy(man)
    cell = bad["workloads"][0]["name"]
    bad["end_to_end"].append({"name": "other_rate", "unit": "1/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": [bad["workloads"][1]["name"]]})
    bad["per_layer"][0]["moves"] = "other_rate"
    bad["per_layer"][0]["workloads"] = [cell]
    with pytest.raises(manifest.ManifestError, match="does not report"):
        manifest.validate(bad)


def test_missing_files_are_refused(tmp_path):
    root = copy_bench(tmp_path)
    (root / "bench" / "metrics" / "tasks_per_wave.py").unlink()
    with pytest.raises(manifest.ManifestError, match="no reader file"):
        manifest.validate(manifest.load(root), root)


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_config_traffic_and_metric_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files plus manifest entries run, with no file that was there edited."""
    root = copy_bench(tmp_path)
    before = _digests(root)
    cells = add_tiny_cells(root)
    (root / "bench" / "metrics" / "windows_per_interval.py").write_text(
        "def read(trace, facts):\n"
        "    return facts['windows'] / facts['intervals']\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["per_layer"].append({
        "name": "windows_per_interval", "unit": "windows",
        "better": "lower", "source": "program_counter",
        "layer": "window loop", "moves": "tasks_per_s",
        "workloads": [cells[0]]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    after = _digests(root)
    assert all(after[p] == d for p, d in before.items())

    out = harness.run(root, cells[0], 7, 0.2, True,
                      t_start=time.perf_counter())
    assert out["correct"] is True
    # a sweep of the 1024 agents an interval, at W = 64
    assert out["metrics"]["windows_per_interval"]["value"] == 16
    assert out["metrics"]["windows_per_interval"]["unit"] == "windows"
