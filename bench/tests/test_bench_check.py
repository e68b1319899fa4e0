"""The check decides ``correct``: sound runs pass, and a run whose timed
path is broken underneath, or the control, fails.

Each test drives a whole run of a tiny cell on the CPU (the look for a
chip skipped), with the system under test replaced where a fault needs
it. The cells run on one chip, so the fault of an exchange between chips
left out cannot arise in them.
"""
import time
import types

import jax
import pytest

from bench import harness, manifest
from bench.control import control_system
from bench.tests.conftest import (add_tiny_cells, add_wrapped_sis_cell,
                                   copy_bench)


def _run(root, cell, system=harness.program_system, seed=2**31 + 12345):
    return harness.run(root, cell, seed, 0.2, False,
                       t_start=time.perf_counter(), system=system)


def _faulty(fault):
    def system(case, traffic, cell):
        run = harness.program_system(case, traffic, cell)

        def broken(state, n_tasks, *, seed):
            if fault == "unchanged":      # a step that returns its state
                return state, run(state, n_tasks, seed=seed)[1]
            if fault == "half":           # half of the batch left out
                return run(state, n_tasks // 2, seed=seed)
            new, stats = run(state, n_tasks, seed=seed)
            # an answer altered where it is produced: one element off
            leaf = jax.tree_util.tree_leaves(new)[0]
            flat = leaf.reshape(-1)
            altered = flat.at[0].set(flat[0] ^ 1).reshape(leaf.shape)
            return jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(new), [altered]), stats

        return broken

    return system


@pytest.mark.parametrize("cell", ["sis_tiny.tiny", "axelrod_tiny.tiny",
                                  "sis_tiny.tiny.overlap",
                                  "axelrod_tiny.tiny.overlap"])
def test_sound_run_is_correct(tiny_root, cell):
    out = _run(tiny_root, cell)
    assert out["correct"] is True, out["check"]
    assert out["check"]["mismatches"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"tasks_per_s", "interval_ms_p95",
                                   "setup_s"}
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_makes_the_run_incorrect(tiny_root, fault):
    out = _run(tiny_root, "sis_tiny.tiny", _faulty(fault))
    assert out["correct"] is False
    assert out["check"]["mismatches"]["value"] > 0
    assert out["failed"] >= 1


@pytest.mark.parametrize("cell", ["sis_tiny.tiny", "axelrod_tiny.tiny"])
def test_control_is_not_correct(tiny_root, cell):
    out = _run(tiny_root, cell, control_system)
    assert out["correct"] is False
    assert out["check"]["mismatches"]["value"] > 0


def _copy_with(tmp_path, kind, sweep, record_every_sweeps):
    root = copy_bench(tmp_path)
    add_tiny_cells(root)
    return root, add_wrapped_sis_cell(root, kind, sweep, record_every_sweeps)


def test_interval_is_the_builders_sweeps(tmp_path, monkeypatch):
    """An interval is ``record_every_sweeps`` sweeps of the builder's
    ``tasks_per_sweep``, not of ``n_agents``: here 2 sweeps of 1024 // 4
    tasks; the window runs them and the reference replays them."""
    root, cell = _copy_with(tmp_path, "sis_quarter_sweep",
                            "int(cfg['n_agents']) // 4", 2)
    items, calls, replayed = [], [], []
    sample_items = harness.Sample.items

    def spy_items(self):
        items.extend(sample_items(self))
        return list(items)

    monkeypatch.setattr(harness.Sample, "items", spy_items)
    cell_reference = manifest.Cell.reference

    def reference(self):
        class Spy(cell_reference(self).Reference):
            def replay(self, state, seed, n):
                replayed.append(n)
                return super().replay(state, seed, n)

        return types.SimpleNamespace(Reference=Spy)

    monkeypatch.setattr(manifest.Cell, "reference", reference)

    def system(case, traffic, cell):
        run = harness.program_system(case, traffic, cell)

        def counted(state, n_tasks, *, seed):
            calls.append(n_tasks)
            return run(state, n_tasks, seed=seed)

        return counted

    out = _run(root, cell, system)
    assert out["correct"] is True, out["check"]
    warm, *rest = [n for _, _, n, _, _ in items]
    assert warm == 2 * 64 and rest and set(rest) == {1024 // 2}
    assert replayed == [n for _, _, n, _, _ in items]
    assert calls[0] == warm and set(calls[1:]) == {1024 // 2}


@pytest.mark.parametrize("sweep", ["None", "0", "float(cfg['n_agents'])"])
def test_builder_without_a_sweep_length_is_refused(tmp_path, sweep):
    root, cell = _copy_with(tmp_path, "sis_bad_sweep", sweep, 1)
    with pytest.raises(ValueError, match="tasks_per_sweep"):
        _run(root, cell)


def test_traced_run_reports_per_layer_metrics(tiny_root):
    out = harness.run(tiny_root, "sis_tiny.tiny.overlap", 5, 1.0, True,
                      t_start=time.perf_counter())
    assert out["correct"] is True
    # no device plane on the CPU: the device metrics are left out, the
    # counts and the host's dispatch time are there
    assert {"tasks_per_wave", "compile_s",
            "host_dispatch_us_per_window"} <= set(out["metrics"])
    assert "device_idle_pct" not in out["metrics"]
    assert out["metrics"]["tasks_per_wave"]["value"] > 1
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # trace_seconds 0: one traced interval, and the warm-up
    assert out["attempted"] == 1 + 1


def test_cli_refuses_without_a_chip():
    import subprocess
    import sys

    from bench.tests.conftest import ROOT

    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "sis_ws_1m.w256", "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout == ""
