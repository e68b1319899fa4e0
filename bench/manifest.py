"""``BENCHMARK.json``: validation, and finding each part by its name.

Everything a cell needs is found by name, so a later change adds a
configuration, a traffic mix or a per-layer metric by adding files and a
manifest entry, with no edit to a file that is there:

  cell           ``workloads[name]`` in ``BENCHMARK.json``
  configuration  ``configs[cell.config].file`` (``bench/configs/<name>.json``),
                 whose ``kind`` names the model builder
                 ``bench/models/<kind>.py`` and the plain reference
                 ``bench/reference/<kind>.py``
  traffic mix    ``bench/traffic/<cell.traffic>.json``
  metric         ``bench/metrics/<metric name>.py`` (per-layer metrics)
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}

#: the bench directory inside a checkout
BENCH = "bench"


class ManifestError(ValueError):
    """``BENCHMARK.json`` breaks a rule, or names a file that is missing."""


def load(root: Path) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _line(text, what, errs):
    if (not isinstance(text, str) or not 1 <= len(text) <= 200
            or "\n" in text or "\t" in text):
        errs.append(f"{what}: 1 to 200 characters on one line, no tab")


def _name(name, what, errs):
    if not isinstance(name, str) or not NAME.fullmatch(name):
        errs.append(f"{what}: bad name {name!r}")


def cells_reporting(man: dict, metric: dict) -> list[str]:
    """The cells that report ``metric``: its ``workloads`` key, or every
    cell when it has none."""
    return list(metric.get("workloads",
                           [c["name"] for c in man["workloads"]]))


def validate(man: dict, root: Path | None = None) -> None:
    """Raise ``ManifestError`` listing every rule the manifest breaks.
    With ``root``, also check that each file it names is there."""
    errs: list[str] = []
    if set(man) != TOP_KEYS:
        errs.append(f"top-level keys {sorted(man)} != {sorted(TOP_KEYS)}")
        raise ManifestError("; ".join(errs))
    cmd = man["command"]
    if (not isinstance(cmd, list) or not 1 <= len(cmd) <= 32
            or not all(isinstance(w, str) for w in cmd)):
        errs.append("command: a list of 1 to 32 strings")
    else:
        for w in cmd:
            _line(w, "command word", errs)
            if w.startswith("/") or ".." in w.split("/"):
                errs.append(f"command word {w!r} leads out of the checkout")
    paths = man["paths"]
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        errs.append("paths: 1 to 16 directories")
        paths = []
    for p in paths:
        if (not isinstance(p, str) or not PATH.fullmatch(p)
                or p.startswith("/") or ".." in p.split("/")):
            errs.append(f"paths: bad path {p!r}")
    rs = man["run_seconds"]
    if not isinstance(rs, int) or isinstance(rs, bool) or not 1 <= rs <= 51:
        errs.append("run_seconds: a whole number from 1 to 51")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/") for p in paths)

    configs = {c.get("name"): c for c in man["configs"]}
    if not 1 <= len(man["configs"]) <= 24:
        errs.append("configs: 1 to 24")
    if len(configs) != len(man["configs"]):
        errs.append("configs: duplicate name")
    files = set()
    for c in man["configs"]:
        if set(c) != CONFIG_KEYS:
            errs.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        _name(c["name"], "config", errs)
        _line(c["source"], f"config {c['name']} source", errs)
        _line(c["why"], f"config {c['name']} why", errs)
        if not under_paths(c["file"]):
            errs.append(f"config {c['name']}: file not under paths")
        if c["file"] in files:
            errs.append(f"config {c['name']}: file shared")
        files.add(c["file"])
        if not isinstance(c["reduced"], list) or len(c["reduced"]) > 16:
            errs.append(f"config {c['name']}: reduced is a list of <= 16")
        else:
            for k in c["reduced"]:
                _name(k, f"config {c['name']} reduced key", errs)

    cells = man["workloads"]
    if not 1 <= len(cells) <= 24:
        errs.append("workloads: 1 to 24")
    names = [c.get("name") for c in cells]
    if len(set(names)) != len(names):
        errs.append("workloads: duplicate name")
    pairs = [(c.get("config"), c.get("traffic")) for c in cells]
    if len(set(pairs)) != len(pairs):
        errs.append("workloads: a (config, traffic) pair appears twice")
    used = set()
    for c in cells:
        if set(c) != CELL_KEYS:
            errs.append(f"cell {c.get('name')}: keys {sorted(c)}")
            continue
        _name(c["name"], "cell", errs)
        _name(c["config"], f"cell {c['name']} config", errs)
        _name(c["traffic"], f"cell {c['name']} traffic", errs)
        _line(c["why"], f"cell {c['name']} why", errs)
        if c["config"] not in configs:
            errs.append(f"cell {c['name']}: unknown config {c['config']}")
        used.add(c["config"])
        if c["chips"] not in (1, 4):
            errs.append(f"cell {c['name']}: chips is 1 or 4")
    if sum(c.get("chips") == 4 for c in cells) > max(1, len(cells) // 2):
        errs.append("workloads: too many 4-chip cells")
    for name in configs:
        if name not in used:
            errs.append(f"config {name}: used by no cell")

    cell_names = set(names)
    metrics = man["end_to_end"] + man["per_layer"]
    mnames = [m.get("name") for m in metrics]
    if len(set(mnames)) != len(mnames):
        errs.append("metrics: duplicate name")
    if not 1 <= len(man["end_to_end"]) <= 16:
        errs.append("end_to_end: 1 to 16")
    if not 1 <= len(man["per_layer"]) <= 128:
        errs.append("per_layer: 1 to 128")
    e2e = {}
    for m in man["end_to_end"]:
        keys = set(m) - {"workloads"}
        if keys != E2E_KEYS:
            errs.append(f"metric {m.get('name')}: keys {sorted(m)}")
            continue
        e2e[m["name"]] = m
        if m["source"] not in SOURCES_E2E:
            errs.append(f"metric {m['name']}: end-to-end source")
        b = m["bound"]
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.25:
            errs.append(f"metric {m['name']}: bound from 0.01 to 0.25")
    if "setup_s" not in e2e:
        errs.append("end_to_end: setup_s is missing")
    layers_by_metric = {}
    for m in man["per_layer"]:
        keys = set(m) - {"workloads"}
        if keys != LAYER_KEYS:
            errs.append(f"metric {m.get('name')}: keys {sorted(m)}")
            continue
        _line(m["layer"], f"metric {m['name']} layer", errs)
        layers_by_metric[m["name"]] = m["layer"]
        if m["source"] not in SOURCES:
            errs.append(f"metric {m['name']}: source {m['source']!r}")
        if m["moves"] not in e2e:
            errs.append(f"metric {m['name']}: moves {m['moves']!r}, "
                        "no end-to-end metric of that name")
            continue
        moved = set(cells_reporting(man, e2e[m["moves"]]))
        for cell in cells_reporting(man, m):
            if cell not in cell_names:
                errs.append(f"metric {m['name']}: unknown cell {cell}")
            elif cell not in moved:
                errs.append(f"metric {m['name']}: cell {cell} does not "
                            f"report {m['moves']}")
    for m in metrics:
        if "name" not in m:
            continue
        _name(m["name"], "metric", errs)
        if not isinstance(m.get("unit"), str) or not UNIT.fullmatch(m["unit"]):
            errs.append(f"metric {m['name']}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            errs.append(f"metric {m['name']}: better is lower or higher")
        for cell in m.get("workloads", []):
            if cell not in cell_names:
                errs.append(f"metric {m['name']}: unknown cell {cell}")
    for cell in cell_names:
        e2e_here = [m for m in man["end_to_end"]
                    if cell in cells_reporting(man, m)]
        if "setup_s" not in {m["name"] for m in e2e_here} or len(e2e_here) < 2:
            errs.append(f"cell {cell}: reports setup_s and one more "
                        "end-to-end metric")
        if not any(cell in cells_reporting(man, m) for m in man["per_layer"]):
            errs.append(f"cell {cell}: reports no per-layer metric")

    if root is not None:
        root = Path(root)
        for c in man["configs"]:
            if not (root / c.get("file", "")).is_file():
                errs.append(f"config {c.get('name')}: no file {c.get('file')}")
        for c in cells:
            if not traffic_path(root, c.get("traffic", "")).is_file():
                errs.append(f"cell {c.get('name')}: no traffic file")
        for m in man["per_layer"]:
            if not metric_path(root, m.get("name", "")).is_file():
                errs.append(f"metric {m.get('name')}: no reader file")
    if errs:
        raise ManifestError("; ".join(errs))


def traffic_path(root: Path, name: str) -> Path:
    return Path(root) / BENCH / "traffic" / f"{name}.json"


def metric_path(root: Path, name: str) -> Path:
    return Path(root) / BENCH / "metrics" / f"{name}.py"


def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path (a configuration's
    builder or reference, or a metric's reader)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise ManifestError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One cell with everything it names, found by name under ``root``."""

    def __init__(self, root: Path, workload: str, man: dict | None = None):
        self.root = Path(root)
        self.manifest = man if man is not None else load(self.root)
        by_name = {c["name"]: c for c in self.manifest["workloads"]}
        if workload not in by_name:
            raise ManifestError(
                f"no workload {workload!r}; known: {sorted(by_name)}")
        self.spec = by_name[workload]
        self.name = workload
        self.chips = self.spec["chips"]
        entry = {c["name"]: c for c in self.manifest["configs"]}[
            self.spec["config"]]
        with open(self.root / entry["file"]) as f:
            self.config = json.load(f)
        with open(traffic_path(self.root, self.spec["traffic"])) as f:
            self.traffic = json.load(f)
        kind = self.config["kind"]
        self.kind = kind
        self.builder_path = self.root / BENCH / "models" / f"{kind}.py"
        self.reference_path = self.root / BENCH / "reference" / f"{kind}.py"
        self.end_to_end = [m for m in self.manifest["end_to_end"]
                           if workload in cells_reporting(self.manifest, m)]
        self.per_layer = [m for m in self.manifest["per_layer"]
                          if workload in cells_reporting(self.manifest, m)]

    def builder(self):
        return load_module(self.builder_path, f"bench_model_{self.kind}")

    def reference(self):
        return load_module(self.reference_path, f"bench_ref_{self.kind}")

    def readers(self) -> dict:
        """``{metric name: read function}`` of this cell's per-layer
        metrics."""
        out = {}
        for m in self.per_layer:
            mod = load_module(metric_path(self.root, m["name"]),
                              "bench_metric_" + m["name"].replace(".", "_"))
            out[m["name"]] = mod.read
        return out
