"""Roofline builders: the LM dry-run table (historic) and the MABS
engine roofline + T(W, n) cost-model fit (the protocol half).

MABS section (``mabs_roofline_rows`` / ``fit_tn_cost_model``, rendered
by ``report.py explain BENCH_engine.json``):

  * Per engine row carrying compiled-cost telemetry (the ``cost`` field
    ``engine_sweep`` captures via ``Engine.compiled_costs``), three
    bound terms in seconds — compute, memory, collective. XLA's
    cost_analysis counts ``while`` bodies ONCE, and the engines' wave
    loops have data-dependent trips, so the per-call FLOPs/bytes are
    multiplied by the *executed* wave count; the collective term uses
    the HLO-parsed per-device receive bytes already resolved against the
    runtime comm ledger (``collective_bytes`` — exact by the cross-check
    identity). ``max(terms)`` is the roofline bound; measured/bound says
    how far the engine sits above it.
  * The fig3-style T(W, n) cost model is fitted against the
    ``kind:"tn"`` rows: per model, least squares over
    T ≈ c_sched·n_windows·W² + c_wave·waves + c_task·tasks + c0 —
    the schedule's O(W²) record check, the per-wave dispatch overhead,
    the per-task execute work, and a constant. Per-family residuals
    validate it (closing the ROADMAP item's open fitting half).

LM section (below) — unchanged dry-run roofline.

Three terms per (arch × shape × mesh), in seconds (v5e constants):

  compute    = FLOPs_analytic            / (chips · 197e12 FLOP/s)
  memory     = bytes_analytic            / (chips · 819e9 B/s)
  collective = wire_bytes_per_device     / (50e9 B/s per ICI link)

FLOPs/bytes use analytic per-architecture formulas (documented below and
cross-checked against compiled cost_analysis): XLA's cost analysis counts
`while` bodies ONCE (verified on this toolchain), so raw numbers
undercount scanned layers by ~n_layers×; the HLO-parsed collective bytes
ARE loop-corrected via recovered trip counts (launch/hlo_analysis.py).
Both raw and corrected values are kept in the artifacts for audit.

MODEL_FLOPS (the "useful" floor) = 6·N·tokens (dense) / 6·N_active·tokens
(MoE); the compute term additionally carries the quadratic attention term
where applicable — their ratio exposes remat/attention overhead.
"""
from __future__ import annotations

import glob
import json
import os

PEAK_FLOPS = 197e12        # bf16 per chip
HBM_BW = 819e9             # per chip
LINK_BW = 50e9             # per ICI link

ARTIFACT_DIR = "artifacts/dryrun"


def _arch_cfg(name):
    from repro.configs import get_config

    return get_config(name)


def analytic_costs(rec: dict) -> dict:
    """Analytic FLOPs and HBM bytes for the whole step (all chips)."""
    cfg = _arch_cfg(rec["arch"])
    b, t = rec["global_batch"], rec["seq_len"]
    n_active = cfg.n_active_params()
    n_total = cfg.n_params()
    L, hq, hd = cfg.n_layers, cfg.n_heads, cfg.hd
    win = cfg.sliding_window

    if rec["kind"] == "train":
        tokens = b * t
        model_flops = 6 * n_active * tokens
        # attention logits+value matmuls, fwd+bwd (x3 of fwd 4·T·Teff·H·hd)
        teff = t / 2 if win is None else min(win, t)
        attn = 12 * L * hq * hd * t * teff * b
        if cfg.is_encdec:
            attn *= 2  # encoder + cross attention, coarse
        if cfg.family == "ssm":
            attn = 0
        flops = model_flops + attn
        # bytes: params read fwd+bwd + grads w + opt (m,v rw, p rw f32) +
        # activations (residual stream rw per layer, bf16)
        pbytes = n_total * 2
        opt_bytes = n_total * 4 * 6
        act = L * tokens * cfg.d_model * 2 * 4
        bytes_ = 3 * pbytes + opt_bytes + act
    elif rec["kind"] == "prefill":
        tokens = b * t
        model_flops = 2 * n_active * tokens
        teff = t / 2 if win is None else min(win, t)
        attn = 4 * L * hq * hd * t * teff * b
        if cfg.family == "ssm":
            attn = 0
        flops = model_flops + attn
        bytes_ = n_total * 2 + L * tokens * cfg.d_model * 2 * 2
    else:  # decode: one token against a cache of length t
        model_flops = 2 * n_active * b
        s_eff = t if win is None else min(win, t)
        attn = 4 * L * hq * hd * s_eff * b
        cache_bytes = (2 * L * cfg.n_kv_heads * hd * s_eff * b * 2)
        if cfg.family == "ssm":
            attn = 0
            cache_bytes = L * (cfg.d_model // hd) * hd * hd * 4 * b
        if cfg.family == "hybrid":
            # 3 global layers full cache, rest windowed + SSM state
            glob_l = len(cfg.global_layers)
            cache_bytes = 2 * b * cfg.n_kv_heads * hd * 2 * (
                glob_l * t + (L - glob_l) * min(win or t, t))
            nh = cfg.ssm.n_heads or cfg.d_model // cfg.ssm.head_dim
            cache_bytes += L * b * nh * cfg.ssm.head_dim * \
                cfg.ssm.state_dim * 4
        flops = model_flops + attn
        # params + cache read once per decode step
        bytes_ = n_total * 2 + cache_bytes
        model_flops = model_flops  # per-token useful work
    return {
        "flops_analytic": float(flops),
        "bytes_analytic": float(bytes_),
        "model_flops": float(6 * n_active * b * t if rec["kind"] == "train"
                             else (2 * n_active * b * t
                                   if rec["kind"] == "prefill"
                                   else 2 * n_active * b)),
    }


def roofline_row(rec: dict) -> dict:
    chips = 512 if rec["mesh"] == "multi" else 256
    an = analytic_costs(rec)
    t_compute = an["flops_analytic"] / (chips * PEAK_FLOPS)
    t_memory = an["bytes_analytic"] / (chips * HBM_BW)
    wire = rec["collectives"]["total_wire_bytes"]  # per-device already
    t_coll = wire / LINK_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    useful = an["model_flops"] / (chips * PEAK_FLOPS)
    row = {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": an["model_flops"],
        "flops_analytic": an["flops_analytic"],
        "hlo_flops_raw": rec["cost_analysis"]["flops"],
        "max_loop_multiplier": rec.get("max_loop_multiplier", 1),
        "roofline_fraction": useful / bound if bound > 0 else 0.0,
        "useful_vs_analytic": (an["model_flops"] / an["flops_analytic"]
                               if an["flops_analytic"] else 0.0),
        "temp_gib": rec["memory"]["temp_bytes"] / 2**30,
        "args_gib": rec["memory"]["argument_bytes"] / 2**30,
    }
    return row


def build_table(artifact_dir: str = ARTIFACT_DIR, mesh: str | None = None,
                opt: bool = False):
    rows = []
    for path in sorted(glob.glob(os.path.join(artifact_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if bool(rec.get("opt")) != opt:
            continue
        if rec.get("status") != "ok":
            rows.append({"arch": rec.get("arch"), "shape": rec.get("shape"),
                         "mesh": rec.get("mesh"),
                         "status": rec.get("status"),
                         "skip_reason": rec.get("skip_reason", "")})
            continue
        if mesh and rec["mesh"] != mesh:
            continue
        rows.append(roofline_row(rec) | {"status": "ok"})
    return rows


# --------------------------------------------------------------------------
# MABS engine roofline + T(W, n) cost-model fit (BENCH_engine.json rows)

#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``
#: (what ``repro.obs.provenance`` stamps into a BENCH file's meta). TPU
#: v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 393
#: TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
#: interconnect. A device kind missing here is an error, never a default:
#: a CPU or virtual-device run has no device roofline.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "mem_bw": 819e9, "link_bw": 200e9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def device_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; raises for a kind with no
    published figures in ``DEVICE_PEAKS``."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); a roofline needs a chip "
            "run") from None


def mabs_roofline_rows(bench: dict) -> list[dict]:
    """Roofline terms for every engine row carrying compiled-cost
    telemetry (the ``cost`` field captured by engine_sweep). Per-call
    cost_analysis FLOPs/bytes count the wave loop's body once, so both
    scale by the executed wave count; the collective term is the
    ledger-cross-checked HLO receive-byte total for the whole run.
    Peaks come from ``device_peaks`` of the run's device kind."""
    meta = bench.get("meta", {})
    peaks = device_peaks((meta.get("provenance") or {}).get("device_kind"))
    out = []
    for r in bench.get("rows", []):
        c = r.get("cost")
        if not c or r.get("kind") != "engine":
            continue
        waves = max(int(r["total_waves"]), 1)
        t_comp = c["flops"] * waves / peaks["flops"]
        t_mem = c["bytes_accessed"] * waves / peaks["mem_bw"]
        coll = c.get("collective_bytes") or 0
        t_coll = coll / peaks["link_bw"]
        terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
        bound = max(terms.values())
        measured = float(r["seconds"])
        out.append({
            "model": r["model"], "engine": r["engine"],
            "window": r["window"], "n_devices": r["n_devices"],
            "n_agents": r["n_agents"],
            "t_compute_s": t_comp, "t_memory_s": t_mem,
            "t_collective_s": t_coll,
            "dominant": max(terms, key=terms.get),
            "bound_s": bound, "measured_s": measured,
            "above_bound": measured / bound if bound > 0 else float("inf"),
            "executor": c.get("executor"),
            "peak_bytes": c.get("peak_bytes"),
            "coll_ledger_ratio": r.get("coll_ledger_ratio"),
        })
    return out


#: T(W, n) fit features, in coefficient order (see fit_tn_cost_model)
TN_FEATURES = ("c_sched[s/W^2]", "c_wave[s/wave]", "c_agent[s/(wave*n)]",
               "c0[s]")


def fit_tn_cost_model(tn_rows: list[dict]) -> list[dict]:
    """Least-squares fit of the fig3-style T(W, n) cost model against
    the ``kind:"tn"`` sweep rows, one fit per model:

        T(run) ≈ c_sched · n_windows·W²  (the O(W²) record check)
               + c_wave  · waves         (per-wave dispatch overhead)
               + c_agent · waves·n       (per-wave full-state traffic)
               + c0                      (constant dispatch floor)

    Returns per-model coefficient dicts with overall relative-RMS /
    R² and per-topology-family residuals — the validation half of the
    ROADMAP's cost-model item; the future cost-aware scheduler picks W
    from these coefficients."""
    import numpy as np

    fits = []
    for model in sorted({r["model"] for r in tn_rows}):
        rows = [r for r in tn_rows if r["model"] == model]
        if len(rows) < len(TN_FEATURES):
            continue
        feats, y = [], []
        for r in rows:
            n_windows = max(int(r["total_tasks"]) // int(r["window"]), 1)
            feats.append([n_windows * float(r["window"]) ** 2,
                          float(r["total_waves"]),
                          float(r["total_waves"]) * float(r["n_agents"]),
                          1.0])
            y.append(float(r["seconds"]))
        X = np.asarray(feats)
        y = np.asarray(y)
        # column scaling for conditioning (W² vs waves·n span ~6 decades)
        scale = X.max(axis=0)
        scale[scale == 0] = 1.0
        coef_s, *_ = np.linalg.lstsq(X / scale, y, rcond=None)
        coef = coef_s / scale
        pred = X @ coef
        resid = y - pred
        ss_res = float((resid ** 2).sum())
        ss_tot = float(((y - y.mean()) ** 2).sum()) or 1.0
        by_family: dict = {}
        for r, p, yy in zip(rows, pred, y):
            fam = by_family.setdefault(r["topology"], [])
            fam.append((yy - p) / yy if yy else 0.0)
        fits.append({
            "model": model,
            "n_rows": len(rows),
            "coef": dict(zip(TN_FEATURES, (float(c) for c in coef))),
            "r2": 1.0 - ss_res / ss_tot,
            "rms_rel": float(np.sqrt(np.mean((resid / y) ** 2))),
            "residuals_by_family": {
                fam: {"rms_rel": float(np.sqrt(np.mean(np.square(v)))),
                      "n": len(v)}
                for fam, v in sorted(by_family.items())},
        })
    return fits


def main():
    rows = build_table()
    print("arch,shape,mesh,t_compute_s,t_memory_s,t_collective_s,dominant,"
          "roofline_fraction,useful_vs_analytic,temp_gib")
    for r in rows:
        if r.get("status") != "ok":
            print(f"{r.get('arch')},{r.get('shape')},{r.get('mesh')},"
                  f"SKIP/{r.get('status')},{r.get('skip_reason', '')[:40]}")
            continue
        print(f"{r['arch']},{r['shape']},{r['mesh']},"
              f"{r['t_compute_s']:.4g},{r['t_memory_s']:.4g},"
              f"{r['t_collective_s']:.4g},{r['dominant']},"
              f"{r['roofline_fraction']:.3f},{r['useful_vs_analytic']:.3f},"
              f"{r['temp_gib']:.1f}")


if __name__ == "__main__":
    main()
