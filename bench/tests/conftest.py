"""A copy of the benchmark with tiny cells, for runs on the CPU.

The copy holds ``BENCHMARK.json`` and ``bench/`` as committed, plus
small configurations of both kinds and small traffic mixes, added the
way a later change adds them: new files and new manifest entries.

Every test here runs the harness off the chip: the look for a TPU, the
check of the scheduling kernels' backend and JAX's compilation cache are
switched off from inside the tests, and the CPU has no peaks.
"""
import json
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

#: tiny cells: (config, kind sizes, traffic, engine)
TINY_CONFIGS = {
    "sis_tiny": ("sis_ws_1m", {"n_agents": 1024}),
    "axelrod_tiny": ("axelrod_f500", {"n_agents": 400, "n_features": 24}),
}
TINY_TRAFFIC = {"tiny": "wavefront", "tiny.overlap": "wavefront_overlap"}


def copy_bench(dst: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    return dst


def add_tiny_cells(root: Path) -> list[str]:
    """Add the tiny configurations, traffic mixes and cells to the copy
    at ``root``; returns the new cells' names."""
    man = json.loads((root / "BENCHMARK.json").read_text())
    for name, (base, sizes) in TINY_CONFIGS.items():
        cfg = json.loads((root / "bench" / "configs" / f"{base}.json")
                         .read_text())
        cfg.update(sizes)
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
        man["configs"].append({"name": name, "source": "a test",
                               "file": f"bench/configs/{name}.json",
                               "reduced": sorted(sizes), "why": "a test"})
    for name, engine in TINY_TRAFFIC.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps({
            "engine": engine, "window": 64, "kernels": [],
            "warmup_windows": 2, "check_tasks": 4096, "check_intervals": 2,
            "trace_seconds": 0}))
    cells = []
    for cfg in TINY_CONFIGS:
        for traffic in TINY_TRAFFIC:
            cells.append(f"{cfg}.{traffic}")
            man["workloads"].append({"name": cells[-1], "config": cfg,
                                     "traffic": traffic, "chips": 1,
                                     "why": "a test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] += cells
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    return cells


#: a builder of a kind whose task is not one agent's update: it wraps
#: SIS's and states a sweep as ``n_agents // 4`` tasks (``{sweep}``), or
#: leaves the key out (``None``)
WRAPPED_SIS = """from pathlib import Path

from bench import manifest

_sis = manifest.load_module(Path(__file__).with_name("sis.py"),
                            "bench_model_sis_wrapped")


def build(cfg, state_seed):
    case = _sis.build(cfg, state_seed)
    case.pop("tasks_per_sweep")
    sweep = {sweep}
    if sweep is not None:
        case["tasks_per_sweep"] = sweep
    return case
"""


def add_wrapped_sis_cell(root: Path, kind: str, sweep: str,
                         record_every_sweeps: int) -> str:
    """Add to the copy at ``root`` a tiny SIS configuration of kind
    ``kind``, whose builder wraps SIS's and returns ``tasks_per_sweep =
    <sweep>`` (an expression in ``cfg``, or ``"None"`` for no key), and
    its cell on the ``tiny`` traffic; returns the cell's name."""
    bench = root / "bench"
    (bench / "models" / f"{kind}.py").write_text(
        WRAPPED_SIS.format(sweep=sweep))
    shutil.copy(bench / "reference" / "sis.py",
                bench / "reference" / f"{kind}.py")
    cfg = json.loads((bench / "configs" / "sis_ws_1m.json").read_text())
    cfg.update(kind=kind, n_agents=1024,
               record_every_sweeps=record_every_sweeps)
    (bench / "configs" / f"{kind}.json").write_text(json.dumps(cfg))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": kind, "source": "a test",
                           "file": f"bench/configs/{kind}.json",
                           "reduced": ["n_agents", "record_every_sweeps"],
                           "why": "a test"})
    cell = f"{kind}.tiny"
    man["workloads"].append({"name": cell, "config": kind,
                             "traffic": "tiny", "chips": 1, "why": "a test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    return cell


@pytest.fixture(autouse=True)
def off_chip(monkeypatch):
    import jax

    from bench import harness

    monkeypatch.setattr(harness, "check_chip", lambda chips: jax.devices())
    monkeypatch.setattr(harness, "check_kernels", lambda traffic, got: None)
    monkeypatch.setattr(harness, "enable_cache", lambda root: "(not set)")
    monkeypatch.setattr(harness, "peaks_for", lambda root, kind: {})


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = copy_bench(tmp_path_factory.mktemp("bench_copy"))
    add_tiny_cells(root)
    return root
