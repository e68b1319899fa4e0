"""Engine sweep: wavefront vs sharded throughput across device counts.

For each scenario in {voter, SIS, Axelrod} x window size x device count,
runs the same task stream through the ``wavefront`` (single-device),
``wavefront_overlap`` (cross-window overlapped waves), ``sharded``
(per-wave halo split over the agent axis), ``sharded_overlap`` (overlap
+ per-fused-wave slabs), ``sharded_window_halo`` (the monolithic
window/pair-halo middle rung) and ``sharded_replicated`` (full-state
all_gather) engines and reports end-to-end throughput (tasks/s,
scheduling + execution included), the schedule shape, for the sharded
engines the per-wave communication volume (rows / payload bytes
actually shipped per device per wave vs the monolithic window halo and
the full state — ``comm_reduction_vs_window_halo`` is the split's win),
and for the overlapped engines the carry-over columns (mean/max overlap
depth, early-task counts and the carry frontier), so BENCH_engine.json
captures the per-wave split win, the halo win and the barrier-removal
win alongside tasks/s.

A second row family (``kind: "tn"``) is the fig3-style T(W, n) cost-
model sweep (ROADMAP item): wavefront-engine seconds/task for voter and
SIS over the five topology families × agent counts × window sizes, the
MABS analog of the paper's T(s, n) subset-size sweep — it runs in the
single-device subprocess so its timings share the engine rows'
conditions.

On a TPU the sweep runs in this one process on the real devices (a
parent that has touched JAX holds the chip, so it starts no children),
and it fails when JAX finds no TPU. The CPU rehearsal is explicit:
with ``JAX_PLATFORMS=cpu`` the parent stays off JAX and runs one
subprocess per device count, realized via
``--xla_force_host_platform_device_count`` — virtual devices that check
control flow and counts, never speed.

Emits BENCH_engine.json next to the repo root (or --out PATH):

  {"meta": {...}, "rows": [{"kind": "engine", "model", "engine",
   "window", "n_devices", "n_agents", "total_tasks", "tasks_per_s",
   "total_waves", "mean_parallelism", "seconds", ...comm/overlap...},
   {"kind": "tn", "model", "topology", "n_agents", "window", ...}, ...]}

Run:  PYTHONPATH=src python benchmarks/engine_sweep.py [--quick]            (TPU)
      JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/engine_sweep.py --quick
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ENGINES = ("wavefront", "wavefront_overlap", "sharded", "sharded_overlap",
           "sharded_window_halo", "sharded_replicated")

#: T(W, n) sweep grid (fig3-style): families × agent counts × windows
TN_FAMILIES = ("ring", "lattice2d", "watts_strogatz", "erdos_renyi",
               "barabasi_albert")
TN_AGENTS = (1024, 4096, 16384)
TN_WINDOWS = (64, 256)


def _tn_topology(name: str, n: int, key):
    from repro.topology import (
        barabasi_albert,
        connect_isolated,
        erdos_renyi,
        lattice2d,
        ring,
        watts_strogatz,
    )

    import jax

    if name == "ring":
        return ring(n, 4)
    if name == "lattice2d":
        side = int(round(n ** 0.5))
        assert side * side == n, n
        return lattice2d(side, side, neighborhood="von_neumann")
    k1, k2 = jax.random.split(key)
    if name == "watts_strogatz":
        return connect_isolated(watts_strogatz(n, 4, 0.1, k1), k2)
    if name == "erdos_renyi":
        return connect_isolated(erdos_renyi(n, 4.0 / (n - 1), k1), k2)
    if name == "barabasi_albert":
        return barabasi_albert(n, 2, k1)
    raise ValueError(name)


def _tn_sweep(args) -> list[dict]:
    """fig3-style T(W, n): single-device wavefront seconds/task for
    voter and SIS over the topology families."""
    import jax

    from repro.engine import make_engine
    from repro.mabs.sis import SISModel
    from repro.mabs.voter import VoterModel
    from repro.utils.timing import median_time

    rows = []
    for fam in TN_FAMILIES:
        for n in TN_AGENTS:
            topo = _tn_topology(fam, n, jax.random.key(11))
            for mname, make in (("voter", VoterModel), ("sis", SISModel)):
                model = make(topo)
                state = model.init_state(jax.random.key(1))
                for window in TN_WINDOWS:
                    total = window * 2
                    eng = make_engine("wavefront", model, window=window)
                    _, stats = eng.run(state, total, seed=2)  # warmup
                    sec = median_time(
                        lambda: eng.run(state, total, seed=2)[0],
                        repeats=args.repeats, warmup=0)
                    rows.append({
                        "kind": "tn",
                        "model": mname,
                        "topology": fam,
                        "engine": "wavefront",
                        "n_agents": int(n),
                        "window": int(window),
                        "total_tasks": int(total),
                        "seconds": float(sec),
                        "seconds_min": float(sec.samples[0]),
                        "seconds_samples": list(sec.samples),
                        "tasks_per_s": float(total / sec),
                        "total_waves": int(stats["total_waves"]),
                        "mean_parallelism": float(stats["mean_parallelism"]),
                    })
                    print("ROW " + json.dumps(rows[-1]), flush=True)
    return rows


def _compiled_cost_fields(eng, state, stats) -> dict:
    """Compiled-cost telemetry for one engine row: AOT cost_analysis
    FLOPs/bytes + memory decomposition of the window executor the run
    dispatched, with the HLO-parsed collective bytes resolved against
    the runtime comm ledger's executed iteration counts. The hlo/ledger
    ratio (1.0 = exact) rides along as the in-artifact bug detector.
    Overlapped runs dispatch the pair executors and mix per-iteration
    widths across drain modes, so cost capture is barrier-mode only."""
    # read the last timed run's comm ledger BEFORE compiled_costs — its
    # _prepare_state call resets it (stats came from the warmup run, but
    # every run executes the same schedule, so the counts agree)
    iters = (eng.comm_iteration_counts(stats)
             if hasattr(eng, "comm_iteration_counts") else None)
    costs = eng.compiled_costs(state, seed=2)
    if not costs:
        return {}
    (_, cost), = costs.items()
    ledger_ratio = None
    ledger = stats.get("comm_bytes_total")
    # cross-check only on real meshes: a 1-device shard_map may elide
    # its collectives entirely, which is not a comm-accounting bug
    if iters is not None and ledger and getattr(eng, "n_devices", 1) > 1:
        ledger_ratio = cost.collectives.total_bytes(iters) / ledger
    return {"cost": cost.as_row(iters),
            "coll_ledger_ratio": ledger_ratio}


def _inner(args) -> None:
    """Runs inside one subprocess with a fixed device count. With
    ``--profile DIR`` the whole sweep runs under ``jax.profiler.trace``
    (one subdirectory per device count), so the device timeline carries
    the ``protocol.*`` named scopes of the kernels and halo gathers."""
    import jax

    from repro.obs.profiler import profile_session
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    logdir = (os.path.join(args.profile, f"d{jax.device_count()}")
              if args.profile else None)
    with profile_session(logdir):
        _inner_body(args)


def _inner_body(args) -> None:
    import jax

    from repro.engine import make_engine
    from repro.mabs.axelrod import AxelrodConfig, AxelrodModel
    from repro.mabs.sis import SISModel
    from repro.mabs.voter import VoterModel
    from repro.obs.stats import row_keys
    from repro.topology import watts_strogatz
    from repro.utils.timing import block_all, median_time

    if args.tn_only:
        _tn_sweep(args)
        return
    n = args.n
    topo = watts_strogatz(n, 4, 0.1, jax.random.key(0))
    models = {
        "voter": VoterModel(topo),
        "sis": SISModel(topo),
        "axelrod": AxelrodModel(AxelrodConfig(n_agents=n, n_features=3)),
    }
    rows = []
    for mname, model in models.items():
        state = model.init_state(jax.random.key(1))
        for window in args.windows:
            total = window * args.windows_per_run
            for ename in ENGINES:
                if ename.startswith("sharded") and jax.device_count() == 1 \
                        and args.skip_sharded_1dev:
                    continue
                eng = make_engine(ename, model, window=window)
                # warmup + stats; fence the warmup state so no queued
                # device work leaks into the first timed repeat
                out, stats = eng.run(state, total, seed=2)
                block_all(out)
                sec = median_time(lambda: eng.run(state, total, seed=2)[0],
                                  repeats=args.repeats, warmup=0)
                row = {
                    "kind": "engine",
                    "model": mname,
                    "engine": ename,
                    "window": int(window),
                    "n_devices": jax.device_count(),
                    "n_agents": int(n),
                    "total_tasks": int(total),
                    "tasks_per_s": float(total / sec),
                    "total_waves": int(stats["total_waves"]),
                    "mean_parallelism": float(stats["mean_parallelism"]),
                    "seconds": float(sec),
                    "seconds_min": float(sec.samples[0]),
                    "seconds_samples": list(sec.samples),
                }
                # the nullable comm + overlap columns (per-wave rows/bytes
                # shipped, the monolithic references, the carry-over
                # accounting) are derived from the stats registry — the
                # declarations in repro/obs/stats.py own the row schema
                row.update({k: stats.get(k)
                            for k in row_keys("comm", "overlap")})
                row.update(_compiled_cost_fields(eng, state, stats))
                rows.append(row)
                print("ROW " + json.dumps(rows[-1]), flush=True)
    if args.tn_sweep:
        _tn_sweep(args)


def _spawn(device_count: int, argv) -> list[dict]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={device_count} "
        + env.get("XLA_FLAGS", "")).strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--run-inner", *argv],
                       capture_output=True, text=True, env=env)
    if p.returncode != 0:
        raise RuntimeError(f"inner sweep (d={device_count}) failed:\n"
                           + p.stderr[-4000:])
    rows = [json.loads(line[4:]) for line in p.stdout.splitlines()
            if line.startswith("ROW ")]
    for r in rows:
        if r.get("kind") == "tn":
            print(f"tn {r['model']:8s} {r['topology']:16s} "
                  f"n={r['n_agents']:6d} W={r['window']:4d} "
                  f"{r['tasks_per_s']:10.0f} tasks/s "
                  f"par={r['mean_parallelism']:6.2f}")
            continue
        comm = ("" if r.get("per_wave_comm_bytes") is None else
                f" comm/wave={r['per_wave_comm_bytes']:>8d}B"
                f" (halo={r['window_halo_bytes'] or '—'}B"
                f" full={r['full_state_bytes']}B)")
        ov = ("" if not r.get("overlap") else
              f" depth={r['mean_overlap_depth']:5.2f}"
              f" carry={r['carry_frontier_mean']:5.2f}")
        print(f"{r['model']:8s} {r['engine']:18s} W={r['window']:5d} "
              f"d={r['n_devices']} {r['tasks_per_s']:10.0f} tasks/s "
              f"par={r['mean_parallelism']:6.2f}{comm}{ov}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    # default sized so the monolithic halo rung beats the full state for
    # every scenario: the widest halo below is SIS at W=256 with
    # nr = max_degree+1 on the WS(n, 4, 0.1) graph (max_degree ~8-10)
    # -> ~256·(10+1+1) ≈ 3k rows, which must stay < n for that rung to
    # engage (the per-wave split rung has no width guard)
    ap.add_argument("--n", type=int, default=4096, help="agents")
    ap.add_argument("--windows", type=int, nargs="+", default=[128, 256])
    ap.add_argument("--devices", type=int, nargs="+", default=[1, 4, 8])
    ap.add_argument("--windows-per-run", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-sharded-1dev", action="store_true",
                    help="skip the sharded engine on 1-device meshes")
    ap.add_argument("--no-tn-sweep", dest="tn_sweep", action="store_false",
                    help="skip the fig3-style T(W, n) cost-model rows")
    ap.add_argument("--tn-sweep", action="store_true", default=True,
                    help=argparse.SUPPRESS)
    ap.add_argument("--tn-only", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="run under jax.profiler.trace, writing a "
                         "TensorBoard/Perfetto device profile per device "
                         "count into DIR (protocol phases show up via the "
                         "protocol.* named scopes)")
    ap.add_argument("--run-inner", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_engine.json"))
    ap.add_argument("--no-ledger", dest="ledger", action="store_false",
                    help="skip appending a benchmarks/ledger/ run record")
    ap.add_argument("--ledger-dir", default=None, metavar="DIR",
                    help="ledger directory (default benchmarks/ledger/)")
    args = ap.parse_args()
    if args.quick:
        args.n, args.windows, args.devices = 256, [64, 128], [1, 8]
        args.windows_per_run, args.repeats = 2, 1
        args.tn_sweep = False

    if args.run_inner:
        _inner(args)
        return

    def inner_argv(with_tn: bool, tn_only: bool = False) -> list[str]:
        return (["--n", str(args.n), "--windows",
                 *map(str, args.windows),
                 "--windows-per-run", str(args.windows_per_run),
                 "--repeats", str(args.repeats)]
                + (["--skip-sharded-1dev"] if args.skip_sharded_1dev
                   else [])
                + (["--profile", os.path.abspath(args.profile)]
                   if args.profile else [])
                + ([] if with_tn else ["--no-tn-sweep"])
                + (["--tn-only"] if tn_only else []))

    cpu_rehearsal = os.environ.get("JAX_PLATFORMS") == "cpu"
    rows = []
    if cpu_rehearsal:
        # the parent stays off JAX until every child has exited
        for d in args.devices:
            # the T(W, n) rows are single-device by construction: attach
            # them to the d=1 subprocess so timings share its conditions
            rows.extend(_spawn(d, inner_argv(args.tn_sweep and d == 1)))
        if args.tn_sweep and 1 not in args.devices:
            # no d=1 lane requested: run the T(W, n) rows in their own
            # single-device subprocess rather than silently dropping them
            rows.extend(_spawn(1, inner_argv(True, tn_only=True)))
    else:
        import jax

        platform = jax.devices()[0].platform
        if platform != "tpu":
            raise SystemExit(
                f"engine_sweep: JAX found no TPU (platform {platform!r}); "
                "set JAX_PLATFORMS=cpu for the virtual-device CPU rehearsal")
        import io
        from contextlib import redirect_stdout

        buf = io.StringIO()
        with redirect_stdout(buf):
            _inner(args)   # ends with the T(W, n) rows when tn_sweep is on
        rows = [json.loads(line[4:]) for line in buf.getvalue().splitlines()
                if line.startswith("ROW ")]
        print(buf.getvalue(), end="")

    from repro.obs import provenance

    engine_rows = [r for r in rows if r.get("kind") != "tn"]
    payload = {
        "meta": {
            # environment header (jax version, backend/device kind, git
            # sha, stats schema version) — rendered by report.py mabs.
            # NB: device_count is the parent process's view; the swept
            # mesh sizes are in device_counts below.
            "provenance": provenance(),
            "n_agents": args.n,
            "windows": [int(w) for w in args.windows],
            # from the rows, not the request: on TPU the sweep runs on the
            # one real mesh regardless of --devices
            "device_counts": sorted({r["n_devices"] for r in engine_rows}),
            "backend": "cpu" if cpu_rehearsal else "tpu",
            "virtual_devices": cpu_rehearsal,
            "strict": True,
            "tn_sweep": {"families": list(TN_FAMILIES),
                         "n_agents": list(TN_AGENTS),
                         "windows": list(TN_WINDOWS)} if args.tn_sweep
                        else None,
        },
        "rows": rows,
    }
    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {out} ({len(rows)} rows)")
    if args.ledger:
        # the append-only history: BENCH_engine.json is overwritten per
        # sweep, the ledger record is forever (report.py compare reads
        # either)
        try:
            from benchmarks.ledger import append_record
        except ImportError:  # run as a script: sys.path[0] is benchmarks/
            from ledger import append_record

        print(f"ledger record {append_record(payload, args.ledger_dir)}")


if __name__ == "__main__":
    main()
