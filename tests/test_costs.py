"""Compiled-cost telemetry and benchmark-sentinel tests.

Pins the contracts docs/observability.md promises for the cost layer:

  * the HLO collective walker classifies ops by dynamic while depth,
    folds static trip counts in, skips async ``-done`` halves, and
    parses both replica-group print forms;
  * ``compiled_costs`` × ``comm_iteration_counts`` reproduces the
    runtime comm ledger's byte total **exactly** on the sharded rungs
    (the 8-virtual-device subprocess lane, same pattern as
    test_engine_differential.py);
  * ``report.py compare`` verdicts are golden — regressed / improved /
    neutral with dispersion-widened thresholds, backend mismatch is
    warn-only, and ``--gate`` exits nonzero only on a real regression;
  * ``fit_tn_cost_model`` recovers planted T(W, n) coefficients from
    synthetic sweep rows;
  * ``finalize_stats`` rejects non-finite values; ``median_time``
    carries its full sample list.
"""
import json
import math
import os
import subprocess
import sys
import textwrap

import pytest

from conftest import BASE_SEED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# HLO collective parsing on synthetic modules

# one dynamic wave loop (no constant trip in its condition) holding an
# async all-gather pair and, nested inside, a second dynamic chunk loop
# with a collective-permute; plus a statically-counted loop (trips=5)
# with an all-reduce at top level
SYNTH_HLO = textwrap.dedent("""\
    %chunk_cond (p.0: (s32[], f32[8])) -> pred[] {
      %lt.0 = pred[] compare(s32[] %a, s32[] %b), direction=LT
    }

    %chunk_body (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
      %cp = f32[8]{0} collective-permute(f32[8] %x), channel_id=3, source_target_pairs={{0,1},{1,2}}
    }

    %wave_cond (p.2: (s32[], f32[16])) -> pred[] {
      %lt.1 = pred[] compare(s32[] %c, s32[] %d), direction=LT
    }

    %wave_body (p.3: (s32[], f32[16])) -> (s32[], f32[16]) {
      %ags = f32[16]{0} all-gather-start(f32[2] %y), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
      %agd = f32[16]{0} all-gather-done(f32[16]{0} %ags)
      %w.1 = (s32[], f32[8]) while((s32[], f32[8]) %t0), condition=%chunk_cond, body=%chunk_body
    }

    %scan_cond (p.4: (s32[], f32[4])) -> pred[] {
      %c.5 = s32[] constant(5)
      %lt.2 = pred[] compare(s32[] %e, s32[] %c.5), direction=LT
    }

    %scan_body (p.5: (s32[], f32[4])) -> (s32[], f32[4]) {
      %ar = f32[4]{0} all-reduce(f32[4] %z), channel_id=2, replica_groups=[2,4], to_apply=%add
    }

    ENTRY %main (arg0: f32[16]) -> f32[16] {
      %w.2 = (s32[], f32[16]) while((s32[], f32[16]) %t1), condition=%wave_cond, body=%wave_body
      %w.3 = (s32[], f32[4]) while((s32[], f32[4]) %t2), condition=%scan_cond, body=%scan_body
    }
    """)


def test_parse_depth_classification_and_static_trips():
    from repro.obs.costs import parse_collectives

    coll = parse_collectives(SYNTH_HLO)
    by_op = {o.op: o for o in coll.ops}
    assert set(by_op) == {"all-gather", "collective-permute", "all-reduce"}
    # dynamic wave loop → depth 1; nested dynamic chunk loop → depth 2
    assert by_op["all-gather"].depth == 1
    assert by_op["collective-permute"].depth == 2
    # statically-counted loop stays depth 0 with the trip multiplier
    ar = by_op["all-reduce"]
    assert ar.depth == 0 and ar.static_mult == 5
    # per-depth per-call bytes: f32[16]=64, f32[8]=32, f32[4]*5=80
    assert coll.bytes_by_depth() == {1: 64, 2: 32, 0: 80}


def test_parse_skips_async_done_half():
    from repro.obs.costs import parse_collectives

    coll = parse_collectives(SYNTH_HLO)
    # the -done completion must not double-count the all-gather
    assert sum(1 for o in coll.ops if o.op == "all-gather") == 1


def test_parse_replica_group_forms():
    from repro.obs.costs import _group_size

    assert _group_size(
        "x, replica_groups={{0,1,2,3,4,5,6,7}}, dims") == 8
    assert _group_size("x, replica_groups={{0,1},{2,3}}, dims") == 2
    assert _group_size("x, replica_groups=[2,4], more") == 4
    assert _group_size("no groups here") is None


def test_total_and_wire_bytes_accounting():
    from repro.obs.costs import parse_collectives

    coll = parse_collectives(SYNTH_HLO)
    # executed counts: 7 waves, 3 chunk trips; depth-0 runs once per call
    iters = {0: 1, 1: 7, 2: 3}
    assert coll.total_bytes(iters) == 64 * 7 + 32 * 3 + 80
    # wire model applies per-op ring factors on the same accounting
    assert coll.wire_bytes(iters) > 0


def test_executor_cost_on_jitted_fn():
    import jax
    import jax.numpy as jnp

    from repro.obs.costs import executor_cost

    @jax.jit
    def f(x):
        return (x * 2.0 + 1.0).sum()

    cost = executor_cost(f, jnp.ones((64,), jnp.float32), name="toy")
    assert cost.name == "toy"
    assert cost.flops > 0
    assert cost.bytes_accessed >= 64 * 4
    assert cost.peak_bytes >= cost.output_bytes
    assert not cost.collectives.ops
    row = cost.as_row({1: 3})
    json.dumps(row)  # must be JSON-safe
    assert row["collective_bytes"] == 0


def test_ledger_cross_check_exact_and_mismatch():
    from repro.obs.costs import (CollectiveOp, ExecutorCost,
                                 HloCollectives, ledger_cross_check)

    coll = HloCollectives(ops=[CollectiveOp(
        op="all-gather", type_str="f32[16]", bytes_per_call=64,
        static_mult=1, depth=1, group_size=8)])
    cost = ExecutorCost(name="x", flops=0, bytes_accessed=0,
                        argument_bytes=0, output_bytes=0, temp_bytes=0,
                        collectives=coll)
    chk = ledger_cross_check({"x": cost}, {1: 7}, 64 * 7)
    assert chk.ok and chk.ratio == 1.0 and chk.parsed_bytes == 448
    chk = ledger_cross_check([cost], {1: 7}, 64 * 7 + 1)
    assert not chk.ok


# --------------------------------------------------------------------------
# compare verdicts (golden)


def _row(tps, seconds=1.0, samples=None, **over):
    r = {"kind": "engine", "model": "voter", "engine": "wavefront",
         "topology": "ws", "window": 64, "n_devices": 1, "n_agents": 512,
         "tasks_per_s": tps, "seconds": seconds}
    if samples is not None:
        r["seconds_samples"] = list(samples)
    r.update(over)
    return r


def _payload(rows, backend="cpu"):
    return {"meta": {"provenance": {"backend": backend}}, "rows": rows}


def test_compare_golden_verdicts():
    sys.path.insert(0, REPO)
    from benchmarks.report import compare_benches

    old = _payload([_row(100.0),
                    _row(100.0, engine="sharded"),
                    _row(100.0, engine="sharded_replicated")])
    new = _payload([_row(50.0),                               # 0.5x
                    _row(200.0, engine="sharded"),            # 2.0x
                    _row(105.0, engine="sharded_replicated"),  # within t
                    _row(99.0, engine="brand_new")])
    cmp = compare_benches(old, new, threshold=0.15)
    verdicts = {r["key"][2]: r["verdict"] for r in cmp["rows"]}
    assert verdicts == {"wavefront": "regressed", "sharded": "improved",
                        "sharded_replicated": "neutral",
                        "brand_new": "new"}
    assert not cmp["warn_only"]
    assert cmp["unmatched_old"] == 0
    assert len(cmp["regressed"]) == 1


def test_compare_dispersion_widens_threshold():
    from benchmarks.report import compare_benches

    # a 0.75x move regresses a quiet row, but a row whose repeats spread
    # (max-min)/median = 0.2 gets an effective threshold of 0.4 and the
    # same move is neutral
    old_q = _payload([_row(100.0)])
    new_q = _payload([_row(75.0)])
    assert compare_benches(old_q, new_q, 0.15)["rows"][0]["verdict"] \
        == "regressed"
    noisy = [0.9, 1.0, 1.1]
    old_n = _payload([_row(100.0, samples=noisy)])
    new_n = _payload([_row(75.0, samples=noisy)])
    row = compare_benches(old_n, new_n, 0.15)["rows"][0]
    assert row["verdict"] == "neutral"
    assert row["threshold"] == pytest.approx(0.4)


def test_compare_backend_mismatch_warn_only():
    from benchmarks.report import compare_benches

    old = _payload([_row(100.0)], backend="tpu")
    new = _payload([_row(10.0)], backend="cpu")
    cmp = compare_benches(old, new, 0.15)
    assert cmp["warn_only"]
    # verdicts still render — the gate just never fails on them
    assert cmp["rows"][0]["verdict"] == "regressed"


def test_compare_incomparable_and_unmatched():
    from benchmarks.report import compare_benches

    old = _payload([_row(100.0), _row(100.0, engine="gone")])
    new = _payload([_row(None)])
    cmp = compare_benches(old, new, 0.15)
    assert cmp["rows"][0]["verdict"] == "incomparable"
    assert cmp["unmatched_old"] == 1


# --------------------------------------------------------------------------
# the gate, end to end (subprocess — real exit codes)


def _run_compare(old_path, new_path, *extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.report", "compare",
         str(old_path), str(new_path), "--gate", *extra],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)


def test_gate_passes_on_committed_artifact():
    bench = os.path.join(REPO, "BENCH_engine.json")
    p = _run_compare(bench, bench)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "GATE: PASS" in p.stdout


def test_gate_fails_on_injected_regression(tmp_path):
    bench = os.path.join(REPO, "BENCH_engine.json")
    with open(bench) as f:
        payload = json.load(f)
    n_injected = 0
    for r in payload["rows"]:
        if r.get("tasks_per_s"):
            r["tasks_per_s"] *= 0.1
            n_injected += 1
    assert n_injected, "committed BENCH must carry tasks_per_s rows"
    bad = tmp_path / "BENCH_bad.json"
    bad.write_text(json.dumps(payload))
    p = _run_compare(bench, bad)
    assert p.returncode == 1, p.stdout + p.stderr
    assert "GATE: FAIL" in p.stdout
    # same injected regression under a backend mismatch → warn-only, passes
    payload["meta"].setdefault("provenance", {})["backend"] = "tpu"
    bad.write_text(json.dumps(payload))
    p = _run_compare(bench, bad)
    assert p.returncode == 0, p.stdout + p.stderr


# --------------------------------------------------------------------------
# T(W, n) cost-model fit recovers planted coefficients


def test_fit_tn_cost_model_recovery():
    from benchmarks.roofline import TN_FEATURES, fit_tn_cost_model

    planted = {"c_sched[s/W^2]": 1e-7, "c_wave[s/wave]": 2e-3,
               "c_agent[s/(wave*n)]": 1e-6, "c0[s]": 0.05}
    assert set(planted) == set(TN_FEATURES)
    rows = []
    for fam_i, fam in enumerate(("ws", "ba", "grid2d", "er", "complete")):
        for w in (8, 32, 128):
            for n in (64, 256, 1024):
                total = 4 * n
                waves = total // max(1, w // 8) + fam_i  # vary per family
                n_windows = max(total // w, 1)
                sec = (planted["c_sched[s/W^2]"] * n_windows * w ** 2
                       + planted["c_wave[s/wave]"] * waves
                       + planted["c_agent[s/(wave*n)]"] * waves * n
                       + planted["c0[s]"])
                rows.append({"model": "voter", "topology": fam,
                             "window": w, "n_agents": n,
                             "total_tasks": total, "total_waves": waves,
                             "seconds": sec})
    (fit,) = fit_tn_cost_model(rows)
    assert fit["model"] == "voter" and fit["n_rows"] == len(rows)
    assert fit["r2"] > 0.9999
    assert fit["rms_rel"] < 1e-6
    for name, want in planted.items():
        assert fit["coef"][name] == pytest.approx(want, rel=1e-4)
    assert set(fit["residuals_by_family"]) \
        == {"ws", "ba", "grid2d", "er", "complete"}


# --------------------------------------------------------------------------
# satellite contracts: non-finite stats rejected, timing carries samples


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the published table for the run's device kind; a
    kind with no entry (a CPU or virtual-device BENCH) is an error."""
    from benchmarks.roofline import device_peaks, mabs_roofline_rows

    v5e = device_peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["mem_bw"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("cpu")
    row = {"kind": "engine", "model": "sis", "engine": "wavefront",
           "window": 256, "n_devices": 1, "n_agents": 10**6,
           "total_waves": 4, "seconds": 1e-3,
           "cost": {"flops": 1e6, "bytes_accessed": 8.19e5}}
    bench = {"meta": {"provenance": {"device_kind": "TPU v5 lite"}},
             "rows": [row]}
    (r,) = mabs_roofline_rows(bench)
    assert r["t_memory_s"] == pytest.approx(4 * 8.19e5 / 819e9)
    with pytest.raises(ValueError):
        mabs_roofline_rows({"meta": {"provenance": {"device_kind": "cpu"}},
                            "rows": [row]})


def test_finalize_stats_rejects_nonfinite():
    from repro.obs import finalize_stats

    base = {"total_tasks": 40, "n_windows": 2, "total_waves": 10,
            "mean_parallelism": 4.0}
    assert finalize_stats(dict(base))["mean_parallelism"] == 4.0
    for bad in (float("nan"), float("inf"), -float("inf")):
        with pytest.raises(ValueError, match="non-finite"):
            finalize_stats({**base, "mean_parallelism": bad})
    with pytest.raises(ValueError, match="non-finite"):
        finalize_stats({**base, "total_waves": float("nan")})


def test_median_time_returns_samples():
    from repro.utils.timing import TimingResult, median_time

    t = median_time(lambda: math.sqrt(2.0), repeats=5)
    assert isinstance(t, TimingResult) and isinstance(t, float)
    assert len(t.samples) == 5
    assert list(t.samples) == sorted(t.samples)
    assert t.min_s == t.samples[0]
    assert float(t) == t.samples[2]  # median of 5 sorted repeats
    assert t.rel_spread >= 0.0
    # degenerate single repeat: defined, no dispersion
    t1 = median_time(lambda: None, repeats=1)
    assert t1.rel_spread == 0.0


# --------------------------------------------------------------------------
# the tentpole identity under 8 virtual devices: HLO-parsed collective
# bytes × executed iterations == runtime comm ledger, exactly

XCHECK_SCRIPT = textwrap.dedent("""\
    import jax

    from repro.engine import make_engine
    from repro.mabs.voter import VoterModel
    from repro.obs.costs import ledger_cross_check
    from repro.topology import watts_strogatz

    topo = watts_strogatz(256, 4, 0.1, jax.random.key({seed}))
    model = VoterModel(topo)
    for name in ("sharded", "sharded_window_halo"):
        eng = make_engine(name, model, window=16)
        state = model.init_state(jax.random.key({seed} + 1))
        state, stats = eng.run(state, 80, seed={seed} + 2)
        # read the executed iteration counts BEFORE compiled_costs: the
        # AOT path re-prepares state, which resets the comm ledger
        iters = eng.comm_iteration_counts(stats)
        costs = eng.compiled_costs(state, seed={seed} + 2)
        assert costs, name
        chk = ledger_cross_check(costs, iters,
                                 stats["comm_bytes_total"])
        print(name, chk.parsed_bytes, chk.ledger_bytes, chk.ratio)
        assert chk.ok, (name, chk)
    print("XCHECK-OK")
    """)


def test_cost_ledger_cross_check_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    p = subprocess.run(
        [sys.executable, "-c", XCHECK_SCRIPT.format(seed=BASE_SEED)],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-4000:]
    assert "XCHECK-OK" in p.stdout
