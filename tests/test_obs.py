"""Observability subsystem tests (repro.obs).

Pins the three contracts docs/observability.md promises:

  * tracing is OFF by default — no tracer installed, no events recorded,
    and engine stats come back as host-native Python scalars either way;
  * tracing ON does not perturb the protocol — every registry engine
    stays bit-exact vs the sequential oracle with a tracer installed,
    a traced run never fences and reads the device only in its
    read-back (counted by ``host_syncs``), its spans land on the
    profiler's host plane on the profiler's clock, and the export passes
    the Chrome trace-event schema validator (matched B/E spans, monotone
    timestamps, known phases);
  * the stats registry is the single schema authority — undeclared keys
    are rejected at the ``finalize_stats`` boundary, declared ones are
    normalized to their declared host types.

The 8-device sharded lane reuses the subprocess pattern of
test_engine_differential.py (the main process keeps its default single
device); it drives benchmarks/trace_smoke.py — the same script the CI
trace-export smoke step runs.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

from conftest import BASE_SEED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _voter(n=48, k=4):
    from repro.mabs.voter import VoterModel
    from repro.topology import ring

    return VoterModel(ring(n, k))


# --------------------------------------------------------------------------
# stats registry


def test_stats_registry_declarations():
    from repro.obs import STATS_VERSION, registry, row_keys
    from repro.obs.stats import GROUPS

    reg = registry()
    assert isinstance(STATS_VERSION, int) and STATS_VERSION >= 1
    assert reg, "registry must not be empty"
    for key, spec in reg.items():
        assert spec.key == key
        assert spec.group in GROUPS
        assert spec.kind in ("int", "float", "bool", "mapping")
        assert spec.description
    # the core quartet every engine emits
    for key in ("total_tasks", "n_windows", "total_waves",
                "mean_parallelism"):
        assert key in reg and not reg[key].nullable
    # row_keys: declaration order, group-filtered, all-groups default
    assert set(row_keys("comm")) == {k for k, s in reg.items()
                                     if s.group == "comm"}
    assert row_keys() == tuple(reg)
    both = row_keys("comm", "overlap")
    assert "per_wave_comm_bytes" in both and "mean_overlap_depth" in both
    assert "total_tasks" not in both


def test_finalize_stats_normalizes_and_rejects():
    import numpy as np

    from repro.obs import finalize_stats

    out = finalize_stats({
        "total_tasks": np.int64(7),
        "mean_parallelism": np.float32(1.5),
        "halo": np.bool_(True),
        "comm_modes": {"split": np.int32(3)},
        "per_wave_split_rows": None,       # nullable
    })
    assert out["total_tasks"] == 7 and type(out["total_tasks"]) is int
    assert out["mean_parallelism"] == 1.5
    assert type(out["mean_parallelism"]) is float
    assert out["halo"] is True
    assert out["comm_modes"] == {"split": 3}
    assert type(out["comm_modes"]["split"]) is int
    assert out["per_wave_split_rows"] is None
    with pytest.raises(ValueError, match="undeclared"):
        finalize_stats({"no_such_stat": 1})
    # non-strict: unknown keys pass through (ad-hoc analysis dicts)
    assert finalize_stats({"no_such_stat": 1}, strict=False) == {
        "no_such_stat": 1}
    with pytest.raises(ValueError, match="not nullable"):
        finalize_stats({"total_tasks": None})


def test_engine_stats_are_host_native():
    """Every engine's run stats pass the registry boundary as Python
    scalars — no 0-d arrays or numpy types leak to callers."""
    from repro.engine import make_engine

    m = _voter()
    st0 = m.init_state(jax.random.key(BASE_SEED + 1))
    for ename in ("sequential", "wavefront", "wavefront_overlap"):
        _, stats = make_engine(ename, m, window=16).run(
            st0, 40, seed=BASE_SEED + 2)
        for k, v in stats.items():
            assert v is None or type(v) in (int, float, bool, dict), (
                f"{ename}: stat {k!r} leaked {type(v).__name__}")


# --------------------------------------------------------------------------
# tracer core


def test_tracing_off_by_default():
    from repro.obs import current_tracer, tracing

    assert current_tracer() is None
    with tracing() as tr:
        assert current_tracer() is tr
        with tracing() as inner:     # blocks nest, inner wins
            assert current_tracer() is inner
        assert current_tracer() is tr
    assert current_tracer() is None


def test_span_tracer_subdivide_and_export(tmp_path):
    """Deferred facts: a span keeps device values by reference and reads
    them only when the trace is rendered; the export carries them, the
    counters, and validates."""
    import jax.numpy as jnp

    from repro.obs import SpanTracer, validate_chrome_trace

    reads = []

    def widths(lv, n):
        reads.append(1)
        return {"n_waves": int(n), "widths": [int(x) for x in lv]}

    tr = SpanTracer()
    with tr.span("repro.run", engine="test") as run:
        with tr.span("repro.execute", index=0) as sp:
            pass
        sp.defer(widths, jnp.array([3, 1]), jnp.int32(2))
        sp.args["rung"] = "split"         # args mutable after exit
        tr.count("host_syncs", 2)
        tr.count("host_syncs")
    assert run.t0 <= sp.t0 <= sp.t1 <= run.t1
    assert not reads, "deferred facts read before export"
    path = tmp_path / "t.json"
    payload = tr.export(str(path))
    assert reads == [1]
    assert validate_chrome_trace(payload) == len(payload["traceEvents"])
    on_disk = json.loads(path.read_text())
    assert validate_chrome_trace(on_disk)
    assert on_disk["otherData"]["counters"] == {"host_syncs": 3}
    execs = [e for e in on_disk["traceEvents"]
             if e["name"] == "repro.execute" and e["ph"] == "B"]
    assert execs[0]["args"] == {"index": 0, "rung": "split", "n_waves": 2,
                                "widths": [3, 1]}
    # ts in µs on the profiler's (epoch) clock
    assert execs[0]["ts"] == pytest.approx(sp.t0 / 1e3)
    tr.export()
    assert reads == [1], "deferred facts are read once"


def test_validator_rejects_malformed():
    from repro.obs import validate_chrome_trace

    ok = {"name": "a", "ph": "B", "ts": 1.0, "pid": 1, "tid": 0}
    end = {"name": "a", "ph": "E", "ts": 2.0, "pid": 1, "tid": 0}
    assert validate_chrome_trace([ok, end]) == 2
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({})
    with pytest.raises(ValueError, match="missing"):
        validate_chrome_trace([{"ph": "B", "ts": 0, "pid": 1, "tid": 0}])
    with pytest.raises(ValueError, match="unknown.*phase"):
        validate_chrome_trace([dict(ok, ph="Q")])
    with pytest.raises(ValueError, match="bad ts"):
        validate_chrome_trace([dict(ok, ts=-1.0)])
    with pytest.raises(ValueError, match="bad.*dur"):
        validate_chrome_trace([dict(ok, ph="X")])
    with pytest.raises(ValueError, match="unclosed"):
        validate_chrome_trace([ok])
    with pytest.raises(ValueError, match="without open B"):
        validate_chrome_trace([end])
    with pytest.raises(ValueError, match="cross-nested"):
        validate_chrome_trace([
            ok, {"name": "b", "ph": "B", "ts": 1.5, "pid": 1, "tid": 0},
            end, {"name": "b", "ph": "E", "ts": 2.5, "pid": 1, "tid": 0}])


# --------------------------------------------------------------------------
# traced engines: bit-exactness + taxonomy (single device in-process)


@pytest.mark.parametrize("ename", ["sequential", "wavefront",
                                   "wavefront_overlap"])
def test_traced_run_bit_exact_and_valid(ename):
    import jax.numpy as jnp

    from repro.core import ProtocolConfig, run_oracle
    from repro.engine import make_engine
    from repro.obs import tracing, validate_chrome_trace

    m = _voter()
    st0 = m.init_state(jax.random.key(BASE_SEED + 1))
    cfg = ProtocolConfig(window=16, strict=True)
    oracle = run_oracle(m, st0, 40, seed=BASE_SEED + 2, config=cfg)
    eng = make_engine(ename, m, window=16, strict=True)
    plain_out, plain_stats = eng.run(st0, 40, seed=BASE_SEED + 2)
    with tracing() as tr:
        out, stats = eng.run(st0, 40, seed=BASE_SEED + 2)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(oracle)):
        assert bool(jnp.all(a == b)), f"{ename} diverged under tracing"
    assert stats == plain_stats, f"{ename}: tracing changed the stats"
    payload = tr.export()
    validate_chrome_trace(payload)
    names = {e["name"] for e in payload["traceEvents"]}
    assert {"repro.run", "repro.execute"} <= names
    if ename != "sequential":
        assert "repro.readback" in names
    # the barrier wavefront engine schedules inside its device loop
    assert ("repro.schedule" in names) == (ename == "wavefront_overlap")
    if ename.endswith("_overlap"):
        assert "repro.boundary" in names
        bnd = [e["args"] for e in payload["traceEvents"]
               if e["name"] == "repro.boundary" and e["ph"] == "B"]
        assert len(bnd) == stats["n_boundaries"]
        assert (max(a["overlap_depth"] for a in bnd)
                == stats["max_overlap_depth"])
    # untraced runs record nothing: the tracer we never installed for
    # plain_out doesn't exist; a fresh run outside tracing() adds no
    # events to the old tracer either
    n = len(tr.events())
    eng.run(st0, 40, seed=BASE_SEED + 2)
    assert len(tr.events()) == n


def test_trace_wave_widths_match_schedule():
    """Each execute span of the overlapped loop (one a window) holds one
    deferred width per executed wave: all of them sum to the task total,
    and there are as many as the run's waves. A fused drain also runs
    the next window's head tasks, so the tasks run by the end of window
    i's span are at least the first i windows' and at most i + 1's."""
    from repro.engine import make_engine
    from repro.obs import tracing

    m = _voter()
    st0 = m.init_state(jax.random.key(BASE_SEED + 1))
    eng = make_engine("wavefront_overlap", m, window=16)
    with tracing() as tr:
        _, stats = eng.run(st0, 40, seed=BASE_SEED + 2)
    execs = [e["args"] for e in tr.events()
             if e["name"] == "repro.execute" and e["ph"] == "B"]
    assert len(execs) == stats["n_windows"]
    counts = [a["count"] for a in execs] + [0]
    done = 0
    for i, a in enumerate(execs):
        assert len(a["widths"]) == a["n_waves"]
        assert all(w > 0 for w in a["widths"])
        done += sum(a["widths"])
        assert sum(counts[:i + 1]) <= done <= sum(counts[:i + 2])
    assert sum(len(a["widths"]) for a in execs) == stats["total_waves"]
    assert done == stats["total_tasks"]


@pytest.mark.parametrize("ename", ["wavefront", "wavefront_overlap"])
def test_traced_run_never_fences(ename, monkeypatch):
    """A traced run waits for the device nowhere but in its read-back:
    no ``block_until_ready``, no device value read before
    ``repro.readback``, and ``host_syncs`` counts the arrays read there:
    the barrier wavefront engine's device loop reads one wave count a
    run, and ``device_loop_windows`` counts the windows it ran. It stays
    bit-exact, with the untraced run's stats."""
    import jax.numpy as jnp
    from jax._src.array import ArrayImpl

    from repro.core import ProtocolConfig, run_oracle
    from repro.engine import make_engine
    from repro.obs import current_tracer, tracing

    m = _voter()
    st0 = m.init_state(jax.random.key(BASE_SEED + 1))
    oracle = run_oracle(m, st0, 40, seed=BASE_SEED + 2,
                        config=ProtocolConfig(window=16, strict=True))
    eng = make_engine(ename, m, window=16, strict=True)
    _, plain_stats = eng.run(st0, 40, seed=BASE_SEED + 2)

    def fence(*a, **k):
        raise AssertionError("a traced run fenced")

    reads: dict = {}     # id -> (array, innermost open span)
    value = ArrayImpl._value

    def traced_value(self):
        tr = current_tracer()
        if tr is not None and id(self) not in reads:
            open_ = [s.name for s in tr.spans if s.t1 is None]
            reads[id(self)] = (self, open_[-1] if open_ else None)
        return value.fget(self)

    monkeypatch.setattr(jax, "block_until_ready", fence)
    monkeypatch.setattr(ArrayImpl, "block_until_ready", fence)
    monkeypatch.setattr(ArrayImpl, "_value", property(traced_value))
    with tracing() as tr:
        out, stats = eng.run(st0, 40, seed=BASE_SEED + 2)
    monkeypatch.undo()
    assert {where for _, where in reads.values()} == {"repro.readback"}
    if ename == "wavefront":
        n_reads = 1
        assert tr.counters["device_loop_windows"] == stats["n_windows"]
        (ex,) = [s for s in tr.spans if s.name == "repro.execute"]
        assert ex.args["device_loop"]
        assert ex.args["windows"] == stats["n_windows"]
    else:
        n_reads = stats["n_windows"] + 4 * stats["n_boundaries"]
        assert "device_loop_windows" not in tr.counters
    assert tr.counters["host_syncs"] == len(reads) == n_reads
    assert stats == plain_stats
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(oracle)):
        assert bool(jnp.all(a == b)), f"{ename} diverged under tracing"


def _profiled_run(ename, logdir):
    """One traced run of ``ename`` under a CPU ``jax.profiler`` session:
    (tracer, stats, the XSpace through ``bench.xplane``, the profile's
    start on the epoch clock in ns)."""
    from jax.profiler import ProfileData

    from repro.engine import make_engine
    from repro.obs import tracing

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from bench import xplane

    m = _voter()
    st0 = m.init_state(jax.random.key(BASE_SEED + 1))
    eng = make_engine(ename, m, window=16)
    eng.run(st0, 40, seed=BASE_SEED + 2)          # compile outside
    with tracing() as tr, jax.profiler.trace(str(logdir)):
        _, stats = eng.run(st0, 40, seed=BASE_SEED + 2)
    path = xplane.find_xplane(str(logdir))
    env = next(p for p in ProfileData.from_file(path).planes
               if p.name == "Task Environment")
    return tr, stats, xplane.load(path), dict(env.stats)[
        "profile_start_time"]


@pytest.mark.parametrize("ename", ["wavefront", "wavefront_overlap"])
def test_profiler_trace_nests_engine_calls(ename, tmp_path):
    """On the profiler's host plane ``repro.run`` encloses the window
    spans and the read-back, and every jitted call of the run after its
    set-up lies inside a schedule, boundary, execute or read-back span.
    The barrier wavefront engine dispatches its device loop in one
    execute span that covers every window."""
    tr, stats, t, _ = _profiled_run(ename, tmp_path)
    by = {}
    for e in t.host:
        if e.name.startswith("repro."):
            by.setdefault(e.name, []).append((e.start, e.end))
    (run,) = by["repro.run"]
    if ename == "wavefront":
        assert "repro.schedule" not in by
        assert len(by["repro.execute"]) == 1
        assert tr.counters["device_loop_windows"] == stats["n_windows"]
        loop = "PjitFunction(run_windows)"
    else:
        assert len(by["repro.schedule"]) == stats["n_windows"]
        assert len(by["repro.execute"]) == stats["n_windows"]
        loop = "PjitFunction(_execute)"
    assert len(by["repro.readback"]) == 1
    assert len(by.get("repro.boundary", [])) == stats.get("n_boundaries", 0)
    for name, spans in by.items():
        assert all(run[0] <= a and b <= run[1] for a, b in spans), name
    window = [s for n in ("repro.schedule", "repro.boundary",
                          "repro.execute", "repro.readback")
              for s in by.get(n, [])]
    first = min(a for a, _ in window)
    calls = [e for e in t.host if e.name.startswith("PjitFunction(")
             and run[0] <= e.start and e.end <= run[1]]
    names = {e.name for e in calls if e.start >= first}
    assert loop in names
    for e in calls:
        assert e.end <= first or any(a <= e.start and e.end <= b
                                     for a, b in window), e.name


def test_span_clock_matches_profiler(tmp_path):
    """The in-memory span times are on the profiler's clock: each span's
    XSpace copy lies inside its ``[t0, t1]`` (10 µs of slack), and
    starts a median of less than 10 µs after ``t0``."""
    import statistics

    tr, _, t, start_ns = _profiled_run("wavefront_overlap", tmp_path)
    xs = sorted((e.start + start_ns, e.end + start_ns, e.name)
                for e in t.host if e.name.startswith("repro."))
    mine = sorted((s.t0, s.t1, s.name) for s in tr.spans)
    assert [n for *_, n in xs] == [n for *_, n in mine]
    slack = 10_000
    for (a, b, _), (t0, t1, name) in zip(xs, mine):
        assert t0 - slack <= a and b <= t1 + slack, name
    assert statistics.median(a - t0 for (a, _, _), (t0, _, _)
                             in zip(xs, mine)) < slack


# --------------------------------------------------------------------------
# 8-device sharded lane: the CI smoke script, bit-exactness included


def run_py(argv, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    p = subprocess.run([sys.executable, *argv], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-4000:]
    return p.stdout


def test_trace_smoke_sharded_8dev(tmp_path):
    """The CI trace-export path end to end: traced sharded-overlap run,
    bit-exact assert, schema-valid export with comm spans, and both
    report subcommands rendering from the artifact."""
    trace = tmp_path / "trace.json"
    out = run_py([os.path.join(REPO, "benchmarks", "trace_smoke.py"),
                  "--out", str(trace)])
    assert "TRACE-OK" in out
    payload = json.loads(trace.read_text())
    from repro.obs import validate_chrome_trace

    validate_chrome_trace(payload)
    execs = [e["args"] for e in payload["traceEvents"]
             if e["name"] == "repro.execute" and e["ph"] == "B"]
    assert execs, "sharded trace must carry execute spans"
    for a in execs:
        assert a["rung"] in ("split", "window_halo", "pair_halo",
                             "full_state")
        assert len(a["rows"]) == len(a["bytes"]) == a["n_waves"]
        assert all(b >= r for r, b in zip(a["rows"], a["bytes"]))
        assert len(a["owned"]) == a["n_waves"]
        assert all(len(o) == 8 for o in a["owned"]), (
            "execute spans must carry 8 per-device owned-task counts")
    assert any(r > 0 for a in execs for r in a["rows"])
    assert payload["otherData"]["counters"]["host_syncs"] > 0
    explain = run_py(["-m", "benchmarks.report", "explain", str(trace)])
    assert "Wave-size histogram" in explain
    assert "Comm ledger" in explain
    assert "Per-device load (8 devices" in explain
    timing = run_py(["-m", "benchmarks.report", "trace", str(trace)])
    assert "Per-window split" in timing


# --------------------------------------------------------------------------
# satellites: timing fence, provenance


def test_block_all_fences_every_leaf():
    import jax.numpy as jnp

    from repro.utils.timing import block_all, median_time

    out = {"a": jnp.ones((4,)), "b": (jnp.zeros((2, 2)), 3, None)}
    assert block_all(out) is out          # passthrough, non-arrays ok
    t = median_time(lambda: {"x": jnp.arange(8) * 2, "n": 1},
                    repeats=3, warmup=1)
    assert t >= 0.0


def test_provenance_header():
    from repro.obs import STATS_VERSION, provenance

    p = provenance()
    assert p["jax_version"] == str(jax.__version__)
    assert p["backend"] == jax.default_backend()
    assert isinstance(p["device_count"], int) and p["device_count"] >= 1
    assert isinstance(p["device_kind"], str)
    assert "T" in p["timestamp"]          # ISO-8601
    assert p["stats_version"] == STATS_VERSION
    assert p["git_sha"] is None or isinstance(p["git_sha"], str)
    json.dumps(p)                          # JSON-safe by construction
