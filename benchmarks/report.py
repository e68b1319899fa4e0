"""Render benchmark artifacts as markdown tables.

Four report families share this entry point:

  * LM dry-run/roofline (the historic default):
      PYTHONPATH=src python -m benchmarks.report [artifacts/dryrun]
  * MABS protocol benchmarks — aggregates BENCH_topology.json and
    BENCH_engine.json (scheduling parallelism, sparse-builder scaling,
    engine throughput + halo comm volume) into one markdown report:
      PYTHONPATH=src python -m benchmarks.report mabs [repo-root]
  * Schedule "explain" — decodes one exported protocol trace
    (repro.obs span tracer -> Chrome trace-event JSON) into the
    schedule's shape: wave-size histogram, critical-path length,
    per-device load imbalance and the comm-ledger breakdown per rung:
      PYTHONPATH=src python -m benchmarks.report explain TRACE.json
  * Trace timing summary — where the traced run's host time went
    (schedule vs execute vs boundary vs read-back, per-window table):
      PYTHONPATH=src python -m benchmarks.report trace TRACE.json
  * Benchmark regression compare — thresholded per-row verdicts between
    two BENCH_engine.json artifacts / ledger records (same schema);
    ``--gate`` exits nonzero on a regression (CI's bench-regression job):
      PYTHONPATH=src python -m benchmarks.report compare OLD NEW [--gate]

``explain`` dispatches on content: a Chrome-trace payload renders the
schedule shape (above); a BENCH_engine.json / ledger record renders the
compiled-cost MABS roofline and the fitted T(W, n) cost model.

Writes markdown to stdout (EXPERIMENTS.md / docs embed the output).
"""
from __future__ import annotations

import glob
import json
import os
import sys

from benchmarks.roofline import analytic_costs, roofline_row

ORDER = ["h2o-danube-3-4b", "smollm-360m", "qwen1.5-32b", "deepseek-7b",
         "rwkv6-3b", "seamless-m4t-medium", "arctic-480b",
         "qwen3-moe-235b-a22b", "hymba-1.5b", "internvl2-76b"]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load(artifact_dir, opt=False):
    recs = {}
    for path in glob.glob(os.path.join(artifact_dir, "*.json")):
        with open(path) as f:
            r = json.load(f)
        if bool(r.get("opt")) != opt:
            continue
        recs[(r.get("arch"), r.get("shape"), r.get("mesh"))] = r
    return recs


def fmt_bytes(b):
    return f"{b/2**30:.2f}"


def dryrun_table(recs, mesh):
    print(f"\n#### Mesh: {mesh} "
          f"({'2×16×16 = 512 chips' if mesh == 'multi' else '16×16 = 256 chips'})\n")
    print("| arch | shape | status | compile s | args GiB/dev | temp GiB/dev"
          " | HLO GFLOPs (raw) | wire GiB/dev | dominant colls |")
    print("|---|---|---|---|---|---|---|---|---|")
    for arch in ORDER:
        for shape in SHAPE_ORDER:
            r = recs.get((arch, shape, mesh))
            if r is None:
                print(f"| {arch} | {shape} | MISSING | | | | | | |")
                continue
            if r["status"] != "ok":
                reason = r.get("skip_reason") or r.get("status")
                print(f"| {arch} | {shape} | skip | | | | | |"
                      f" {reason[:60]} |")
                continue
            colls = r["collectives"]["wire_bytes"]
            dom = max(colls, key=colls.get) if colls else "-"
            print(f"| {arch} | {shape} | ok | {r['compile_s']:.1f} "
                  f"| {fmt_bytes(r['memory']['argument_bytes'])} "
                  f"| {fmt_bytes(r['memory']['temp_bytes'])} "
                  f"| {r['cost_analysis']['flops']/1e9:.1f} "
                  f"| {fmt_bytes(r['collectives']['total_wire_bytes'])} "
                  f"| {dom} |")


def roofline_table(recs, mesh="single"):
    print("\n| arch | shape | compute s | memory s | collective s | dominant"
          " | MODEL_TFLOPs | useful/analytic | roofline frac | note |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for arch in ORDER:
        for shape in SHAPE_ORDER:
            r = recs.get((arch, shape, mesh))
            if r is None or r.get("status") != "ok":
                if r is not None and r.get("status") == "skipped":
                    print(f"| {arch} | {shape} | — | — | — | — | — | — | — |"
                          f" skipped: sub-quadratic-only shape |")
                continue
            row = roofline_row(r)
            note = {
                "compute": "compute-bound: near ideal if comms overlap",
                "memory": "HBM-bound: fuse/cache-resident or quantize",
                "collective": "comm-bound: reshard or overlap collectives",
            }[row["dominant"]]
            print(f"| {arch} | {shape} | {row['t_compute_s']:.4f} "
                  f"| {row['t_memory_s']:.4f} | {row['t_collective_s']:.4f} "
                  f"| **{row['dominant']}** "
                  f"| {row['model_flops']/1e12:.1f} "
                  f"| {row['useful_vs_analytic']:.2f} "
                  f"| {row['roofline_fraction']:.3f} | {note} |")


# --------------------------------------------------------------------------
# MABS protocol report (BENCH_topology.json + BENCH_engine.json)


def _load_bench(root, name):
    path = os.path.join(root, name)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _fmt_kb(b):
    if b is None:
        return "—"
    return f"{b / 1024:.1f} KiB" if b >= 1024 else f"{b} B"


def mabs_topology_tables(bench):
    meta, rows = bench["meta"], bench["rows"]
    sched = [r for r in rows if r.get("kind", "schedule") == "schedule"]
    builds = [r for r in rows if r.get("kind") == "build"]
    if sched:
        print(f"\n#### Scheduling parallelism "
              f"(n = {meta.get('n_nodes')}, backend = "
              f"{meta.get('backend')}, strict rule)\n")
        print("| topology | model | W | waves | mean par | conflict dens"
              " | sched ms/window |")
        print("|---|---|---|---|---|---|---|")
        for r in sched:
            print(f"| {r['topology']} | {r['model']} | {r['window']} "
                  f"| {r['n_waves']} | {r['mean_parallelism']:.2f} "
                  f"| {r['conflict_density']:.4f} "
                  f"| {r['sched_seconds'] * 1e3:.2f} |")
    if builds:
        print("\n#### Sparse builder scaling "
              "(edge-list path, no [n, n] allocation)\n")
        print("| topology | n | build s | edges | max deg "
              "| SIS sched ms/window |")
        print("|---|---|---|---|---|---|")
        for r in builds:
            sched_ms = (f"{r['sched_seconds'] * 1e3:.2f}"
                        if "sched_seconds" in r else "—")
            print(f"| {r['topology']} | {r['n_nodes']:,} "
                  f"| {r['build_seconds']:.2f} | {r['n_edges']:,} "
                  f"| {r['max_degree']} | {sched_ms} |")


def mabs_engine_table(bench):
    meta, rows = bench["meta"], bench["rows"]
    engine_rows = [r for r in rows if r.get("kind", "engine") == "engine"]
    tn_rows = [r for r in rows if r.get("kind") == "tn"]
    print(f"\n#### Engine throughput, comm volume and window overlap "
          f"(n = {meta.get('n_agents')} agents, backend = "
          f"{meta.get('backend')}"
          f"{', virtual devices' if meta.get('virtual_devices') else ''})\n")
    print("| model | W | devices | engine | tasks/s | mean par "
          "| comm/wave/device | window halo | full state "
          "| red. ×halo | red. ×full | overlap depth | carry frontier |")
    print("|---|---|---|---|---|---|---|---|---|---|---|---|---|")
    for r in engine_rows:
        comm = r.get("per_wave_comm_bytes")
        halo_ref = r.get("window_halo_bytes")
        full = r.get("full_state_bytes")
        # red. ×halo: the per-wave split's win over the monolithic
        # window/pair halo; red. ×full: any halo layout's win over the
        # replicated all_gather
        red_h = (f"{r['comm_reduction_vs_window_halo']:.1f}×"
                 if r.get("comm_reduction_vs_window_halo")
                 and r.get("halo") else "—")
        red_f = (f"{full / comm:.1f}×" if comm and full
                 and r.get("halo") else "—")
        if r.get("overlap"):
            # mean/max waves of window k shared with window k+1's head,
            # and the carry-over level floor the cross block imposed
            depth = (f"{r['mean_overlap_depth']:.2f} "
                     f"(max {r['max_overlap_depth']})")
            carry = (f"{r['carry_frontier_mean']:.2f} "
                     f"(max {r['carry_frontier_max']})")
        else:
            depth = carry = "—"
        print(f"| {r['model']} | {r['window']} | {r['n_devices']} "
              f"| {r['engine']} | {r['tasks_per_s']:,.0f} "
              f"| {r['mean_parallelism']:.2f} | {_fmt_kb(comm)} "
              f"| {_fmt_kb(halo_ref)} | {_fmt_kb(full)} "
              f"| {red_h} | {red_f} | {depth} | {carry} |")
    if tn_rows:
        mabs_tn_table(tn_rows)


def mabs_tn_table(rows):
    """fig3-style T(W, n) cost-model sweep: wavefront seconds per task
    for voter/SIS across the topology families (the MABS analog of the
    paper's T(s, n) subset-size figure)."""
    print("\n#### Cost-model T(W, n) sweep "
          "(wavefront engine, µs per task)\n")
    byn = sorted({r["n_agents"] for r in rows})
    print("| model | topology | W | "
          + " | ".join(f"n={n:,}" for n in byn) + " | waves/window |")
    print("|---|---|---|" + "---|" * (len(byn) + 1))
    keys = sorted({(r["model"], r["topology"], r["window"])
                   for r in rows})
    for model, topo, window in keys:
        cells, waves = [], []
        for n in byn:
            match = [r for r in rows
                     if (r["model"], r["topology"], r["window"],
                         r["n_agents"]) == (model, topo, window, n)]
            if match:
                r = match[0]
                cells.append(f"{1e6 * r['seconds'] / r['total_tasks']:.1f}")
                waves.append(f"{r['total_waves'] / max(r['total_tasks'] // r['window'], 1):.1f}")
            else:
                cells.append("—")
                waves.append("—")
        # one waves-per-window entry per n column (schedule contention
        # varies with n), in the same order as the time cells
        print(f"| {model} | {topo} | {window} | "
              + " | ".join(cells) + f" | {'/'.join(waves)} |")


def _provenance_line(meta):
    """One-line environment header (benchmarks stamp it into meta)."""
    p = (meta or {}).get("provenance")
    if not p:
        return None
    return (f"jax {p.get('jax_version')} · backend {p.get('backend')} "
            f"({p.get('device_kind')} ×{p.get('device_count')}) · "
            f"git {p.get('git_sha') or 'unknown'} · "
            f"stats v{p.get('stats_version')} · {p.get('timestamp')}")


def mabs_report(root="."):
    print("### MABS protocol benchmarks (generated by benchmarks/report.py)")
    topo = _load_bench(root, "BENCH_topology.json")
    eng = _load_bench(root, "BENCH_engine.json")
    if topo is None and eng is None:
        print("\n(no BENCH_topology.json / BENCH_engine.json found under "
              f"{os.path.abspath(root)} — run benchmarks/topology_sweep.py "
              "and benchmarks/engine_sweep.py first)")
        return
    for name, bench in (("topology", topo), ("engine", eng)):
        line = _provenance_line(bench.get("meta")) if bench else None
        if line:
            print(f"\n*{name} sweep: {line}*")
    if topo is not None:
        mabs_topology_tables(topo)
    if eng is not None:
        mabs_engine_table(eng)


# --------------------------------------------------------------------------
# protocol-trace reports (repro.obs span tracer -> Chrome trace JSON)


def _load_trace(path):
    """Load + schema-validate an exported protocol trace; returns the
    event list."""
    from repro.obs import validate_chrome_trace

    with open(path) as f:
        payload = json.load(f)
    validate_chrome_trace(payload)
    return payload["traceEvents"] if isinstance(payload, dict) else payload


def _span_durations(events):
    """Pair B/E events per (pid, tid) lane into (name, ts, dur, args)
    tuples (the validator guarantees proper nesting)."""
    spans = []
    stacks: dict = {}
    for ev in sorted((e for e in events if e.get("ph") in ("B", "E")),
                     key=lambda e: e["ts"]):
        lane = stacks.setdefault((ev["pid"], ev["tid"]), [])
        if ev["ph"] == "B":
            lane.append(ev)
        else:
            b = lane.pop()
            spans.append((b["name"], b["ts"], ev["ts"] - b["ts"],
                          b.get("args", {})))
    return spans


def _bar(frac, width=30):
    n = int(round(frac * width))
    return "█" * n + "·" * (width - n)


def _trace_header(events):
    runs = [e for e in events if e["name"] == "repro.run" and e["ph"] == "B"]
    if runs:
        a = runs[0].get("args", {})
        print(f"\nengine `{a.get('engine')}` · window {a.get('window')} · "
              f"{a.get('total_tasks')} tasks · "
              f"overlap {'on' if a.get('overlap') else 'off'}")
    return runs[0].get("args", {}) if runs else {}


def explain_report(path):
    """Content dispatch: a BENCH/ledger payload ({"meta", "rows"})
    renders the compiled-cost roofline + T(W, n) fit; a Chrome-trace
    payload renders the schedule's shape."""
    with open(path) as f:
        payload = json.load(f)
    if isinstance(payload, dict) and "rows" in payload:
        bench_explain(payload, path)
        return
    trace_explain(path)


def bench_explain(bench, path):
    """The static-cost half of explain: MABS roofline (compiled cost
    bounds vs measured seconds per engine row) and the fitted T(W, n)
    cost model with per-family residuals."""
    from benchmarks.roofline import fit_tn_cost_model, mabs_roofline_rows

    print(f"### Bench explain — {os.path.basename(path)}")
    line = _provenance_line(bench.get("meta"))
    if line:
        print(f"\n*{line}*")

    roof = mabs_roofline_rows(bench)
    if roof:
        kind = bench["meta"]["provenance"]["device_kind"]
        print(f"\n#### MABS roofline (compiled costs, {kind} peaks; "
              "bound = max of the three terms)\n")
        print("| model | engine | W | dev | executor | compute s "
              "| memory s | collective s | dominant | bound s "
              "| measured s | ×bound | hlo/ledger |")
        print("|" + "---|" * 13)
        for r in roof:
            ratio = r.get("coll_ledger_ratio")
            print(f"| {r['model']} | {r['engine']} | {r['window']} "
                  f"| {r['n_devices']} | {r['executor']} "
                  f"| {r['t_compute_s']:.2e} | {r['t_memory_s']:.2e} "
                  f"| {r['t_collective_s']:.2e} | **{r['dominant']}** "
                  f"| {r['bound_s']:.2e} | {r['measured_s']:.2e} "
                  f"| {r['above_bound']:.1f}× "
                  f"| {f'{ratio:.3f}' if ratio is not None else '—'} |")
        bad = [r for r in roof if r.get("coll_ledger_ratio") is not None
               and abs(r["coll_ledger_ratio"] - 1.0) > 1e-9]
        print(f"\nhlo/ledger = HLO-parsed collective bytes / runtime comm "
              f"ledger — {'ALL EXACT (1.000)' if not bad else f'{len(bad)} MISMATCHED rows (bug detector fired)'} "
              f"on {sum(1 for r in roof if r.get('coll_ledger_ratio') is not None)} "
              "cross-checked rows")
    else:
        print("\n(no engine rows with compiled-cost telemetry — rerun "
              "benchmarks/engine_sweep.py to capture the `cost` field)")

    tn_rows = [r for r in bench.get("rows", []) if r.get("kind") == "tn"]
    if tn_rows:
        fits = fit_tn_cost_model(tn_rows)
        print("\n#### Fitted T(W, n) cost model "
              "(per model, least squares over the tn sweep)\n")
        print("| model | rows | c_sched [s/W²] | c_wave [s/wave] "
              "| c_agent [s/(wave·n)] | c0 [s] | R² | rel RMS |")
        print("|---|---|---|---|---|---|---|---|")
        for f_ in fits:
            c = f_["coef"]
            print(f"| {f_['model']} | {f_['n_rows']} "
                  f"| {c['c_sched[s/W^2]']:.3e} | {c['c_wave[s/wave]']:.3e} "
                  f"| {c['c_agent[s/(wave*n)]']:.3e} | {c['c0[s]']:.3e} "
                  f"| {f_['r2']:.3f} | {f_['rms_rel']:.3f} |")
        print("\n| model | topology family | rows | residual rel RMS |")
        print("|---|---|---|---|")
        for f_ in fits:
            for fam, res in f_["residuals_by_family"].items():
                print(f"| {f_['model']} | {fam} | {res['n']} "
                      f"| {res['rms_rel']:.3f} |")
    else:
        print("\n(no kind:\"tn\" rows — run the sweep without "
              "--no-tn-sweep to fit the T(W, n) cost model)")


def trace_explain(path):
    """Decode one protocol trace into the schedule's shape, from the
    per-wave lists each ``repro.execute`` span carries."""
    events = _load_trace(path)
    print(f"### Schedule explain — {os.path.basename(path)}")
    run_args = _trace_header(events)
    execs = [e.get("args", {}) for e in events
             if e.get("ph") == "B" and e["name"] == "repro.execute"
             and "widths" in e.get("args", {})]
    if not execs:
        print("\n(no execute spans with per-wave widths in this trace — "
              "nothing to explain)")
        return

    # ---- wave-size histogram (log2 buckets) + critical path
    widths = [int(w) for a in execs for w in a["widths"]]
    total_tasks = sum(widths)
    n_waves = len(widths)
    print(f"\n#### Wave-size histogram ({n_waves} executed waves, "
          f"{total_tasks} tasks)\n")
    buckets: dict = {}
    for w in widths:
        b = 0 if w == 0 else 1 << max(w - 1, 0).bit_length()
        buckets[b] = buckets.get(b, 0) + 1
    print("| wave width ≤ | waves | share |")
    print("|---|---|---|")
    for b in sorted(buckets):
        frac = buckets[b] / n_waves
        print(f"| {b} | {buckets[b]} | `{_bar(frac)}` {frac:5.1%} |")
    # the waves of a run execute strictly in sequence (each is one fused
    # vectorized step), so the executed wave count IS the schedule's
    # critical-path length
    print(f"\ncritical path: **{n_waves} waves** for {total_tasks} tasks "
          f"-> mean parallelism {total_tasks / max(n_waves, 1):.2f} "
          f"tasks/wave")
    if run_args.get("window"):
        seq = total_tasks  # the oracle's critical path: one task per step
        print(f"(sequential baseline {seq} steps; wavefront speedup "
              f"upper bound {seq / max(n_waves, 1):.2f}×)")

    # ---- per-device load imbalance (sharded traces carry owned counts)
    owned = [o for a in execs for o in a.get("owned", [])]
    if owned:
        d = len(owned[0])
        totals = [sum(o[i] for o in owned) for i in range(d)]
        mean = sum(totals) / d
        print(f"\n#### Per-device load ({d} devices, owned tasks/device)\n")
        print("| device | owned tasks | vs mean |")
        print("|---|---|---|")
        for i, t in enumerate(totals):
            rel = t / mean if mean else 0.0
            print(f"| {i} | {t} | `{_bar(min(rel / 2, 1.0))}` {rel:4.2f}× |")
        # per-wave imbalance: max/mean owned across devices, averaged
        per_wave = [max(o) * len(o) / max(sum(o), 1) for o in owned
                    if sum(o)]
        if per_wave:
            print(f"\nper-wave imbalance (max/mean owned): mean "
                  f"{sum(per_wave) / len(per_wave):.2f}×, "
                  f"worst {max(per_wave):.2f}×  (1.0× = perfectly even)")

    # ---- comm-ledger breakdown per rung: the waves that shipped rows
    rungs: dict = {}
    for a in execs:
        for rows, nbytes in zip(a.get("rows", []), a.get("bytes", [])):
            if not rows:
                continue
            r = rungs.setdefault(a.get("rung", "?"),
                                 {"waves": 0, "rows": 0, "bytes": 0})
            r["waves"] += 1
            r["rows"] += int(rows)
            r["bytes"] += int(nbytes)
    if rungs:
        print("\n#### Comm ledger (per-device receive volume, by "
              "comm-ladder rung)\n")
        total_b = sum(r["bytes"] for r in rungs.values()) or 1
        print("| rung | waves | rows | bytes | share |")
        print("|---|---|---|---|---|")
        for name in ("split", "window_halo", "pair_halo", "full_state"):
            if name not in rungs:
                continue
            r = rungs[name]
            frac = r["bytes"] / total_b
            print(f"| {name} | {r['waves']} | {r['rows']:,} "
                  f"| {_fmt_kb(r['bytes'])} | `{_bar(frac)}` {frac:5.1%} |")
    else:
        print("\n(no per-wave rows — single-device trace, no comm)")


def trace_report(path):
    """Where a traced run's host time went. Spans never fence: a
    dispatch span holds the host's own work and any wait on the device
    queue, the read-back span the wait for the device to finish."""
    events = _load_trace(path)
    print(f"### Trace timing — {os.path.basename(path)}")
    _trace_header(events)
    spans = _span_durations(events)
    if not spans:
        print("\n(no B/E spans in this trace)")
        return
    run_dur = sum(d for n, _, d, _ in spans if n == "repro.run") or 1.0
    by_name: dict = {}
    for name, _, dur, _ in spans:
        if name == "repro.run":
            continue
        c, t = by_name.get(name, (0, 0.0))
        by_name[name] = (c + 1, t + dur)
    print("\n#### Phase totals (host time; unfenced spans)\n")
    print("| phase | spans | total host ms | share of run |")
    print("|---|---|---|---|")
    for name, (c, t) in sorted(by_name.items(), key=lambda kv: -kv[1][1]):
        frac = t / run_dur
        print(f"| {name} | {c} | {t / 1e3:.3f} "
              f"| `{_bar(frac)}` {frac:5.1%} |")
    # per-window host split
    phases = {"repro.schedule": "schedule", "repro.boundary": "boundary",
              "repro.execute": "execute"}
    windows: dict = {}
    for name, _, dur, args in spans:
        if name not in phases:
            continue
        w = windows.setdefault(args.get("index", "?"),
                               {"n_waves": None, "rung": None})
        w[phases[name]] = w.get(phases[name], 0.0) + dur
        if name == "repro.execute":
            w["n_waves"] = args.get("n_waves")
            w["rung"] = args.get("rung")
    if windows:
        print("\n#### Per-window split (host ms)\n")
        print("| window | schedule host ms | boundary host ms "
              "| execute host ms | waves | rung |")
        print("|---|---|---|---|---|---|")
        for i in sorted(windows, key=str):
            w = windows[i]
            cells = [f"{w[k] / 1e3:.3f}" if k in w else "—"
                     for k in ("schedule", "boundary", "execute")]
            print(f"| {i} | {' | '.join(cells)} "
                  f"| {w['n_waves'] if w['n_waves'] is not None else '—'} "
                  f"| {w['rung'] or '—'} |")


# --------------------------------------------------------------------------
# benchmark regression compare (BENCH artifacts / ledger records)

#: row identity for the compare join — everything that pins a scenario
COMPARE_KEY = ("kind", "model", "engine", "topology", "window",
               "n_devices", "n_agents")

#: default relative threshold on tasks/s before a row is verdicted
COMPARE_THRESHOLD = 0.15


def _row_key(r):
    return tuple(r.get(k) for k in COMPARE_KEY)


def _rel_spread(r):
    """Dispersion of one row's timing repeats: (max-min)/median over
    ``seconds_samples`` (0.0 when the row predates the samples column)."""
    samples = r.get("seconds_samples") or []
    med = r.get("seconds")
    if len(samples) < 2 or not med:
        return 0.0
    return (max(samples) - min(samples)) / med


def compare_benches(old: dict, new: dict,
                    threshold: float = COMPARE_THRESHOLD) -> dict:
    """Thresholded per-row verdicts between two bench payloads.

    Joins rows on ``COMPARE_KEY`` and verdicts the ``tasks_per_s`` ratio
    new/old: ``regressed`` below ``1 - t``, ``improved`` above ``1 + t``,
    ``neutral`` between — where ``t`` is the *effective* threshold:
    ``max(threshold, 2 × timing spread)`` of whichever side is noisier
    (dispersion-aware — a noisy row needs a bigger move to be verdicted).
    A provenance backend mismatch (cpu baseline vs tpu run, or vice
    versa) makes the whole comparison ``warn_only``: verdicts still
    render, but the gate never fails on them."""
    def backend(b):
        meta = b.get("meta", {})
        return (meta.get("provenance") or {}).get("backend") \
            or meta.get("backend")

    warn_only = (backend(old) is not None and backend(new) is not None
                 and backend(old) != backend(new))
    old_rows = {_row_key(r): r for r in old.get("rows", [])}
    results = []
    for r in new.get("rows", []):
        key = _row_key(r)
        base = old_rows.pop(key, None)
        if base is None:
            results.append({"key": key, "verdict": "new",
                            "ratio": None, "threshold": None})
            continue
        spread = max(_rel_spread(r), _rel_spread(base))
        eff = max(threshold, 2.0 * spread)
        o, n = base.get("tasks_per_s"), r.get("tasks_per_s")
        if not o or not n:
            verdict, ratio = "incomparable", None
        else:
            ratio = n / o
            verdict = ("regressed" if ratio < 1.0 - eff
                       else "improved" if ratio > 1.0 + eff
                       else "neutral")
        results.append({"key": key, "verdict": verdict, "ratio": ratio,
                        "threshold": eff, "old": o, "new": n,
                        "spread": spread})
    counts: dict = {}
    for r in results:
        counts[r["verdict"]] = counts.get(r["verdict"], 0) + 1
    return {
        "warn_only": warn_only,
        "old_backend": backend(old), "new_backend": backend(new),
        "rows": results,
        "counts": counts,
        "unmatched_old": len(old_rows),
        "regressed": [r for r in results if r["verdict"] == "regressed"],
    }


def compare_report(old_path: str, new_path: str,
                   threshold: float = COMPARE_THRESHOLD,
                   gate: bool = False) -> int:
    """Render the compare as markdown; returns the process exit code
    (nonzero only under ``--gate`` with a non-warn-only regression)."""
    with open(old_path) as f:
        old = json.load(f)
    with open(new_path) as f:
        new = json.load(f)
    cmp = compare_benches(old, new, threshold)
    print(f"### Bench compare — {os.path.basename(old_path)} → "
          f"{os.path.basename(new_path)}")
    for name, b in (("old", old), ("new", new)):
        line = _provenance_line(b.get("meta"))
        if line:
            print(f"\n*{name}: {line}*")
    if cmp["warn_only"]:
        print(f"\n**backend mismatch ({cmp['old_backend']} → "
              f"{cmp['new_backend']}): warn-only — verdicts are "
              "informational, the gate will not fail**")
    print(f"\nthreshold {threshold:.0%} relative on tasks/s, widened per "
          "row to 2× its timing spread (seconds_samples)\n")
    print("| kind | model | engine | topology | W | dev | old tasks/s "
          "| new tasks/s | ratio | eff. thr | verdict |")
    print("|" + "---|" * 11)
    marker = {"regressed": "**regressed**", "improved": "improved",
              "neutral": "neutral", "new": "new row",
              "incomparable": "incomparable"}
    for r in sorted(cmp["rows"],
                    key=lambda r: (r["verdict"] != "regressed", r["key"])):
        kind, model, engine, topo, w, dev, n = r["key"]
        ratio = f"{r['ratio']:.2f}×" if r["ratio"] is not None else "—"
        thr = (f"{r['threshold']:.0%}" if r["threshold"] is not None
               else "—")
        old_v = f"{r['old']:,.0f}" if r.get("old") else "—"
        new_v = f"{r['new']:,.0f}" if r.get("new") else "—"
        print(f"| {kind} | {model} | {engine or '—'} | {topo or '—'} "
              f"| {w} | {dev or '—'} | {old_v} | {new_v} | {ratio} "
              f"| {thr} | {marker[r['verdict']]} |")
    c = cmp["counts"]
    print(f"\nsummary: {c.get('regressed', 0)} regressed · "
          f"{c.get('improved', 0)} improved · {c.get('neutral', 0)} "
          f"neutral · {c.get('new', 0)} new · "
          f"{cmp['unmatched_old']} baseline rows not re-measured")
    if cmp["regressed"] and not cmp["warn_only"]:
        if gate:
            print("\nGATE: FAIL (regressions above, exit 1)")
            return 1
        print("\n(regressions above; pass --gate to make this fail)")
    elif gate:
        print("\nGATE: PASS")
    return 0


def compare_main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="benchmarks.report compare")
    ap.add_argument("old", help="baseline BENCH json / ledger record")
    ap.add_argument("new", help="candidate BENCH json / ledger record")
    ap.add_argument("--threshold", type=float, default=COMPARE_THRESHOLD,
                    help="relative tasks/s threshold before a verdict "
                         f"(default {COMPARE_THRESHOLD})")
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero on a non-warn-only regression")
    a = ap.parse_args(argv)
    return compare_report(a.old, a.new, a.threshold, a.gate)


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "compare":
        sys.exit(compare_main(sys.argv[2:]))
    if len(sys.argv) > 1 and sys.argv[1] == "mabs":
        mabs_report(sys.argv[2] if len(sys.argv) > 2 else ".")
        return
    if len(sys.argv) > 1 and sys.argv[1] in ("explain", "trace"):
        if len(sys.argv) < 3:
            sys.exit(f"usage: benchmarks.report {sys.argv[1]} TRACE.json")
        (explain_report if sys.argv[1] == "explain"
         else trace_report)(sys.argv[2])
        return
    d = sys.argv[1] if len(sys.argv) > 1 else "artifacts/dryrun"
    recs = load(d)
    print("### §Dry-run results (generated by benchmarks/report.py)")
    for mesh in ("single", "multi"):
        dryrun_table(recs, mesh)
    print("\n### §Roofline (single-pod, 256 chips)")
    roofline_table(recs, "single")


if __name__ == "__main__":
    main()
