"""One run of one cell: set-up, the measured window, the check.

  set-up   compile cache at its fixed path; the cell's data from the
           seed; one engine object from the traffic mix
           (``make_engine(engine, model, window=W)``); a warm-up of a
           few windows, which compiles every program the window runs;
           the device's memory peak is read here, before the check's
           sample holds any state
  window   a closed loop of observation intervals on that same engine,
           each as long as the configuration records its observable
           (``record_every_sweeps`` sweeps, each of the builder's
           ``tasks_per_sweep`` tasks, the program's tasks that update
           every agent once):
           ``eng.run(state, interval_tasks, seed=s_i)``, then one scalar
           observable of the new state read back to the host; ``s_i``
           comes from the run's seed and the interval's index
  traced   (``trace=True``) whole intervals under ``jax.profiler.trace``
           for the traffic's ``trace_seconds``, reduced to the per-layer
           metrics; the trace stays in ``bench/.trace/<cell>`` until the
           cell's next traced run
  check    once the window has closed and the program's state is freed:
           the plain reference replays the warm-up, the last interval and
           others drawn from the seed (as many as ``check_tasks`` allows,
           at most ``check_intervals``), each from its input state, and
           every element of the state the program produced must equal
           the reference's.

``run`` returns the result line as a dict; ``run.py`` prints it.
"""
from __future__ import annotations

import json
import numbers
import shutil
import statistics
import sys
import time
from pathlib import Path

import numpy as np

from bench import manifest, xplane

#: seed tags, so each draw from the run's seed is its own stream
STATE, INTERVAL, SAMPLE = 1, 2, 3


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class OffKernel(RuntimeError):
    """A scheduling kernel ran off the compiled Pallas path."""


def derive(seed: int, *tags: int) -> int:
    """A seed in [0, 2**31) for one use of the run's seed."""
    return int(np.random.SeedSequence([int(seed), *tags])
               .generate_state(1, np.uint64)[0] % (2**31 - 1))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_chip(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs


class Compiles:
    """Persistent-cache hits and misses, and seconds in XLA compilation
    (loads from the cache included), from JAX's monitoring events."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}
    DURATION = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.counts = {"hits": 0, "misses": 0, "compile_s": 0.0}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def _duration(self, event: str, duration: float, **_):
        if event == self.DURATION:
            self.counts["compile_s"] += duration

    def now(self) -> dict:
        return dict(self.counts)


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, every program in it whatever its compile time."""
    import jax

    path = str(Path(root) / manifest.BENCH / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def program_system(case: dict, traffic: dict, cell=None):
    """The system under test: one engine object of the program, built
    once, whose ``run`` every interval calls."""
    from repro.engine import make_engine

    eng = make_engine(traffic["engine"], case["model"],
                      window=int(traffic["window"]))
    return eng.run


class Sample:
    """The intervals the reference replays: the warm-up, the last one,
    and a reservoir of ``k`` others drawn from the seed. Holds each
    one's index, seed, task count, input and output state (references to
    device arrays)."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng(derive(seed, SAMPLE))
        self.fixed: list[tuple] = []
        self.pool: list[tuple] = []
        self.seen = 0
        self.last = None

    def wants(self) -> int | None:
        """Reservoir slot for the next interval (or None); call once per
        interval, before it runs."""
        i, self.seen = self.seen, self.seen + 1
        if i < self.k:
            return i
        j = int(self.rng.integers(0, i + 1))
        return j if j < self.k else None

    def put(self, slot, item):
        if slot is None:
            return
        if slot < len(self.pool):
            self.pool[slot] = item
        else:
            self.pool.append(item)

    def items(self):
        got = self.fixed + self.pool
        if self.last is not None and all(self.last[0] != g[0] for g in got):
            got.append(self.last)
        return sorted(got, key=lambda g: g[0])


def tasks_per_sweep(case: dict, kind: str) -> int:
    """The builder's ``tasks_per_sweep``: how many of the program's tasks
    update every agent once. Raises ``ValueError`` unless it is there and
    a positive whole number."""
    n = case.get("tasks_per_sweep")
    if (not isinstance(n, numbers.Integral) or isinstance(n, bool)
            or n <= 0):
        raise ValueError(
            f"the builder bench/models/{kind}.py must return "
            f"'tasks_per_sweep', a positive int (the program's tasks that "
            f"update every agent once); it returned {n!r}")
    return int(n)


def check_size(traffic: dict, n_tasks: int) -> int:
    """How many window intervals the reference replays (the last one
    included): what ``check_tasks`` holds, at least 2, at most
    ``check_intervals``."""
    return max(2, min(int(traffic["check_intervals"]),
                      int(traffic["check_tasks"]) // n_tasks))


def window_sizes(n_tasks: int, window: int) -> dict:
    """``{tasks in a window: windows}`` of one interval of ``n_tasks``."""
    full, rest = divmod(n_tasks, window)
    sizes = {window: full} if full else {}
    if rest:
        sizes[rest] = 1
    return sizes


def _peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _mismatches(a, b) -> int:
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        return max(int(np.asarray(x).size) for x in la + lb)
    n = 0
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        n += int(x.size) if x.shape != y.shape else int(np.sum(x != y))
    return n


def peaks_for(root: Path, kind: str) -> dict:
    with open(Path(root) / manifest.BENCH / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def check_kernels(traffic: dict, resolved: dict) -> None:
    """Raise ``OffKernel`` unless every scheduling kernel the traffic
    names ran, and ran as compiled Pallas."""
    bad = {k: v for k, v in resolved.items() if v != "pallas"}
    missing = [k for k in traffic["kernels"] if k not in resolved]
    if bad or missing:
        raise OffKernel(f"scheduling kernels off compiled Pallas: {bad}, "
                        f"not traced: {missing}")


def run(root, workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, system=program_system) -> dict:
    """One run of ``workload``; returns the result line.

    ``system(case, traffic, cell)`` builds the callable the window
    drives: the program's engine by default, whose scheduling kernels
    must then have run as compiled Pallas."""
    import jax

    root = Path(root)
    man = manifest.load(root)
    manifest.validate(man, root)
    cell = manifest.Cell(root, workload, man)
    devs = check_chip(cell.chips)[:cell.chips]
    cache = enable_cache(root)
    compiles = Compiles()
    import repro.kernels as kernels

    tr = cell.traffic
    window = int(tr["window"])
    c0 = compiles.now()
    case = cell.builder().build(cell.config, derive(seed, STATE))
    sweep = tasks_per_sweep(case, cell.kind)
    sweeps = int(cell.config["record_every_sweeps"])
    n_tasks = sweep * sweeps
    log(f"{workload}: {case['describe']}; engine {tr['engine']} W={window}, "
        f"{n_tasks} tasks per interval ({sweeps} sweep(s) of {sweep} tasks); "
        f"compile cache {cache}")
    call = system(case, tr, cell)
    observe = case["observe"]
    sample = Sample(check_size(tr, n_tasks) - 1, seed)

    def interval(state, i, n):
        s = derive(seed, INTERVAL, i)
        with jax.profiler.TraceAnnotation(xplane.INTERVAL):
            new, stats = call(state, n, seed=s)
            with jax.profiler.TraceAnnotation(xplane.OBSERVABLE):
                value = observe(new).item()
        return new, stats, s, value

    kernels.RESOLVED.clear()
    state0 = case.pop("state")
    n_warm = int(tr["warmup_windows"]) * window
    state, _, s, _ = interval(state0, 0, n_warm)
    sample.fixed.append((0, s, n_warm, state0, state))
    del state0
    if system is program_system:
        check_kernels(tr, kernels.RESOLVED)
    # every program has run once: the deployment's own peak, before the
    # check's sample holds states
    peak = _peak_bytes(devs)
    setup_s = time.perf_counter() - t_start
    c1 = compiles.now()
    compile_s = c1["compile_s"] - c0["compile_s"]
    log(f"set-up {setup_s:.3f} s, compile and cache loads {compile_s:.3f} s "
        f"(cache hits {c1['hits'] - c0['hits']}, misses "
        f"{c1['misses'] - c0['misses']}); peak {peak} B")

    lat, stats_all = [], []
    i = 1
    span = min(seconds, float(tr["trace_seconds"])) if trace else seconds
    if trace:
        tdir = str(root / manifest.BENCH / ".trace" / workload)
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
    w0 = time.perf_counter()
    while True:
        slot = sample.wants()
        t_a = time.perf_counter()
        new, stats, s, _ = interval(state, i, n_tasks)
        t_b = time.perf_counter()
        lat.append(t_b - t_a)
        stats_all.append(stats)
        sample.put(slot, (i, s, n_tasks, state, new))
        sample.last = (i, s, n_tasks, state, new)
        state = new
        i += 1
        if t_b - w0 >= span:
            break
    window_s = t_b - w0
    if trace:
        jax.profiler.stop_trace()
    n_intervals = len(lat)
    compiles_in_window = compiles.now()["misses"] - c1["misses"]
    if compiles_in_window:
        log(f"warning: {compiles_in_window} compile(s) inside the window")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    metrics = {}
    breakdown = None
    if trace:
        t = xplane.load(xplane.find_xplane(tdir))
        sizes = window_sizes(n_tasks, window)
        facts = {
            "intervals": n_intervals,
            "tasks": n_intervals * n_tasks,
            "windows": sum(int(st["n_windows"]) for st in stats_all),
            "waves": sum(int(st["total_waves"]) for st in stats_all),
            "window": window,
            "window_sizes": [[k, v * n_intervals] for k, v in sizes.items()],
            "overlap": bool(stats_all[0].get("overlap", False)),
            "footprint": tuple(case["footprint"]),
            "compile_s": compile_s,
            "peaks": peaks_for(root, devs[0].device_kind),
            "layers": _layers(root),
        }
        for m in cell.per_layer:
            value = cell.readers()[m["name"]](t, facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(f"traced {n_intervals} intervals: windows "
            f"{[int(st['n_windows']) for st in stats_all]}, waves "
            f"{[int(st['total_waves']) for st in stats_all]}")
        device["busy_s"] = t.busy_s()
        device["window_s"] = t.window_s
        breakdown = {"device_ops": t.device_ops(facts["layers"]),
                     "idle_gaps": t.idle_gaps()}
    else:
        values = {
            "tasks_per_s": n_intervals * n_tasks / window_s,
            "interval_ms_p95": _p95(lat) * 1e3,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    med = statistics.median(lat)
    slow = [x for x in lat if x > 2 * med]
    log(f"intervals: median {med * 1e3:.3f} ms, max {max(lat) * 1e3:.3f} ms; "
        f"{len(slow)} over twice the median, {sum(slow) - len(slow) * med:.3f}"
        f" s beyond it")
    log(f"window {window_s:.3f} s, {n_intervals} intervals, "
        f"{n_intervals * n_tasks} tasks")

    # the check: the program's state freed first, then the reference
    del call, state, new, case["model"]
    items = sample.items()
    sample = None
    ref = cell.reference().Reference(case["ref_data"])
    t_c = time.perf_counter()
    diff, failed = 0, 0
    for i, s, n, before, after in items:
        d = _mismatches(after, ref.replay(before, s, n))
        diff += d
        failed += d > 0
    log(f"reference replayed {len(items)} intervals "
        f"({sum(it[2] for it in items)} tasks) in "
        f"{time.perf_counter() - t_c:.3f} s")
    check = {"mismatches": {"value": diff, "limit": 0}}
    for name, c in check.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    out = {"correct": diff <= 0, "attempted": len(items), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    return out


def _p95(values) -> float:
    """95th percentile (inclusive method) of ``values``."""
    if len(values) < 2:
        return values[0]
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def _layers(root: Path) -> dict:
    with open(Path(root) / manifest.BENCH / "layers.json") as f:
        return json.load(f)


