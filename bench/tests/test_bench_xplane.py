"""The reduction from a profiler trace to the per-layer metrics.

A synthetic XSpace with known intervals checks the arithmetic exactly;
a trace recorded on a TPU v5e and cut to one interval
(``fixtures/sis_w256.xplane.pb``, made by ``bench/tools/trim_trace.py``)
checks the reduction against the values recorded beside it.
"""
import json
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import harness, manifest, xplane
from bench.tests.conftest import ROOT
from bench.tools import trim_trace

FIXTURES = Path(__file__).parent / "fixtures"
SCHED = "jit(_schedule_window)/protocol.conflict_matrix/pallas_call"
LEVELS = "jit(_schedule_window)/protocol.wave_levels/pallas_call"
EXEC = "jit(_execute)/protocol.execute_window/while/body"


def _readers():
    return {m: manifest.load_module(manifest.metric_path(ROOT, m), m).read
            for m in ("device_idle_pct", "schedule_us_per_window",
                      "execute_us_per_wave", "host_dispatch_us_per_window",
                      "schedule_roofline")}


def _facts(**kw):
    facts = {"windows": 2, "waves": 4, "tasks": 512, "intervals": 1,
             "window": 256, "window_sizes": [[256, 2]], "overlap": False, "footprint": (10, 1),
             "compile_s": 1.0, "layers": harness._layers(ROOT),
             "peaks": {"hbm_bytes_per_s": 819e9,
                       "vpu_int32_compare_per_s": 1e12}}
    facts.update(kw)
    return facts


def synthetic():
    """One 1000 ns interval; device busy 100..300 (schedule, with a
    nested op) and 400..600 and 650..700 (execution); host dispatch
    events 10..60 and 320..380, one inside the read-back span."""
    ops = [("fusion.1", 100, 200, {"tf_op": SCHED}),
           ("fusion.2", 150, 100, {"tf_op": LEVELS}),     # nested
           ("while.3", 400, 200, {"tf_op": EXEC}),
           ("fusion.4", 650, 50, {"tf_op": EXEC}),
           ("copy.5", 2000, 10, {"tf_op": EXEC})]         # after the window
    host = [("bench.interval", 0, 1000, {}),
            ("PjitFunction(_schedule_window)", 10, 50, {}),
            ("PjitFunction(_execute)", 320, 60, {}),
            ("bench.observable", 900, 90, {}),
            ("PjitFunction(<lambda>)", 910, 20, {})]
    return [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", [
                ("jit__execute", 0, 3000, {})])]),
            ("/host:CPU", [("python", host)])]


def test_synthetic_trace_reduces_exactly():
    t = xplane.from_profile(ProfileData.from_serialized_xspace(
        trim_trace.serialize(synthetic())))
    assert t.window_s == pytest.approx(1000e-9)
    assert t.busy_s() == pytest.approx(450e-9)
    r, f = _readers(), _facts()
    assert r["device_idle_pct"](t, f) == pytest.approx(55.0)
    # union of the nested schedule ops: 200 ns over 2 windows
    assert r["schedule_us_per_window"](t, f) == pytest.approx(0.1)
    # 250 ns of execution in the window over 4 waves
    assert r["execute_us_per_wave"](t, f) == pytest.approx(0.0625)
    # 110 ns of dispatch outside the read-back, over 2 windows
    assert r["host_dispatch_us_per_window"](t, f) == pytest.approx(0.055)
    least = (256 * 255 // 2 * 21 + 256 * 255 // 2) / 1e12
    assert r["schedule_roofline"](t, f) == pytest.approx(
        100 * least / 100e-9)
    ops = dict(map(tuple, t.device_ops(harness._layers(ROOT))))
    assert ops == pytest.approx({"schedule:protocol.conflict_matrix": 200e-9,
                                 "schedule:protocol.wave_levels": 100e-9,
                                 # the scope path has a while in it
                                 "wave execution:while": 250e-9})
    gaps = dict(map(tuple, t.idle_gaps()))
    assert gaps == pytest.approx({"PjitFunction(_schedule_window)": 100e-9,
                                  "bench.interval": 350e-9,
                                  "PjitFunction(_execute)": 100e-9})


def test_layers_found_by_op_name():
    """As on a TPU v5e: ops named by their HLO text, no scope stat."""
    ops = [("%conflict_matrix_pallas.3 = s32[256,256] custom-call(...)",
            100, 100, {}),
           ("%wave_levels_pallas.1 = s32[256,1] custom-call(...)",
            200, 100, {}),
           ("%fusion.7 = s32[256] fusion(...)", 300, 50, {}),
           ("%while.2 = (s32[], s8[1000000]) while(...)", 400, 300, {}),
           ("%fusion.9 = s8[1000000] fusion(...)", 450, 100, {})]  # in it
    planes = [("/device:TPU:0", [("XLA Ops", ops)]),
              ("/host:CPU", [("python", [("bench.interval", 0, 1000, {})])])]
    t = xplane.from_profile(ProfileData.from_serialized_xspace(
        trim_trace.serialize(planes)))
    r, f = _readers(), _facts()
    assert r["device_idle_pct"](t, f) == pytest.approx(45.0)
    assert r["schedule_us_per_window"](t, f) == pytest.approx(0.1)
    assert r["execute_us_per_wave"](t, f) == pytest.approx(0.075)
    ops = dict(map(tuple, t.device_ops(harness._layers(ROOT))))
    assert ops == pytest.approx({"schedule:conflict_matrix_pallas": 100e-9,
                                 "schedule:wave_levels_pallas": 100e-9,
                                 "other:fusion": 50e-9,      # not fusion.9
                                 "wave execution:while": 300e-9})


def test_wave_loop_nested_in_a_window_loop():
    """As in a device loop over windows: an outer ``while`` holds each
    window's schedule kernels and its wave loop, itself a ``while``. Only
    the inner loops are wave execution; the breakdown keeps its labels."""
    ops = [("%while.1 = (s32[], s8[1000000]) while(...)", 100, 800, {}),
           ("%wave_levels_pallas.2 = s32[256,1] custom-call(...)",
            150, 40, {}),
           ("%while.3 = (s32[], s8[1000000]) while(...)", 200, 200, {}),
           ("%fusion.4 = s8[1000000] fusion(...)", 250, 50, {}),  # in it
           ("%conflict_matrix_pallas.5 = s32[256,256] custom-call(...)",
            450, 30, {}),
           ("%while.6 = (s32[], s8[1000000]) while(...)", 500, 200, {})]
    planes = [("/device:TPU:0", [("XLA Ops", ops)]),
              ("/host:CPU", [("python", [("bench.interval", 0, 1000, {})])])]
    t = xplane.from_profile(ProfileData.from_serialized_xspace(
        trim_trace.serialize(planes)))
    r, f = _readers(), _facts()
    # the two inner loops, 400 ns over 4 waves; not the outer 800 ns
    assert r["execute_us_per_wave"](t, f) == pytest.approx(0.1)
    assert xplane.nested_loops(t.chips[0]) == [(200, 400), (500, 700)]
    assert r["schedule_us_per_window"](t, f) == pytest.approx(0.035)
    assert r["device_idle_pct"](t, f) == pytest.approx(20.0)
    ops = dict(map(tuple, t.device_ops(harness._layers(ROOT))))
    assert ops == pytest.approx({"schedule:wave_levels_pallas": 40e-9,
                                 "schedule:conflict_matrix_pallas": 30e-9,
                                 "wave execution:while": 800e-9})


def test_readers_return_nothing_without_a_device():
    planes = [p for p in synthetic() if p[0] == "/host:CPU"]
    t = xplane.from_profile(ProfileData.from_serialized_xspace(
        trim_trace.serialize(planes)))
    r, f = _readers(), _facts()
    for name in ("device_idle_pct", "schedule_us_per_window",
                 "execute_us_per_wave", "schedule_roofline"):
        assert r[name](t, f) is None
    assert r["schedule_roofline"](xplane.from_profile(
        ProfileData.from_serialized_xspace(trim_trace.serialize(
            synthetic()))), _facts(peaks={})) is None


def test_union_merges_and_clips():
    assert xplane.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 11) == [
        [1, 4], [5, 11]]
    assert xplane.length(xplane.union([(0, 1), (0, 1)])) == 1


def test_chip_trace_fixture():
    """The trimmed chip trace gives the numbers its run printed."""
    path = FIXTURES / "sis_w256.xplane.pb"
    assert path.stat().st_size < 1 << 20
    expect = json.loads((FIXTURES / "sis_w256.expected.json").read_text())
    t = xplane.load(str(path))
    assert t.window_s == pytest.approx(expect["window_s"], rel=1e-12)
    assert t.busy_s() == pytest.approx(expect["busy_s"], rel=1e-12)
    f = _facts(**expect["facts"])
    f["footprint"] = tuple(f["footprint"])
    f["peaks"] = json.loads((ROOT / "bench" / "peaks.json").read_text())[
        "TPU v5 lite"]
    r = _readers()
    assert set(expect["metrics"]) == set(r)
    for name, value in expect["metrics"].items():
        assert r[name](t, f) == pytest.approx(value, rel=1e-9), name
    # the layers are there by name: the levels kernel, then the wave loop
    ops = t.device_ops(harness._layers(ROOT))
    assert [k for k, _ in ops[:2]] == ["schedule:wave_levels_pallas",
                                       "wave execution:while"]
