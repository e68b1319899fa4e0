"""Trace-export smoke: the observability acceptance path in one script.

Runs a small sharded-overlap scenario with the span tracer installed and
``jax.block_until_ready`` made to fail (a traced run never fences),
asserts the traced run stays bit-exact vs the sequential oracle, exports
the Chrome trace-event JSON, schema-validates it, and checks the spans
the docs promise (``repro.run`` / ``schedule`` / ``boundary`` /
``execute`` / ``readback``; per-wave ``widths`` on every execute span,
and ``rows``/``bytes``/``owned`` lists with a ``rung`` on a sharded one)
and the ``host_syncs`` counter. CI runs it under 8 virtual host devices
and uploads the exported trace as an artifact; load it in
ui.perfetto.dev to browse the run.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python benchmarks/trace_smoke.py [--out TRACE.json]
"""
from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "TRACE_smoke.json"))
    ap.add_argument("--engine", default="sharded_overlap")
    ap.add_argument("--total", type=int, default=100)
    ap.add_argument("--window", type=int, default=32)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.core import ProtocolConfig, run_oracle
    from repro.engine import make_engine
    from repro.mabs.voter import VoterModel
    from repro.obs import tracing, validate_chrome_trace
    from repro.topology import watts_strogatz

    model = VoterModel(watts_strogatz(64, 4, 0.2, jax.random.key(5)))
    st0 = model.init_state(jax.random.key(1))
    cfg = ProtocolConfig(window=args.window, strict=True)
    oracle = run_oracle(model, st0, args.total, seed=2, config=cfg)

    eng = make_engine(args.engine, model, window=args.window, strict=True)

    def fence(*a, **k):
        raise AssertionError("a traced run fenced")

    block, jax.block_until_ready = jax.block_until_ready, fence
    try:
        with tracing() as tr:
            out, stats = eng.run(st0, args.total, seed=2)
    finally:
        jax.block_until_ready = block

    # tracing must not perturb the protocol: bit-exact vs the oracle
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(oracle)):
        assert bool(jnp.all(a == b)), "traced run diverged from the oracle"

    path = os.path.abspath(args.out)
    payload = tr.export(path)
    n_events = validate_chrome_trace(payload)
    events = payload["traceEvents"]
    names = {e["name"] for e in events}
    want = {"repro.run", "repro.schedule", "repro.execute", "repro.readback"}
    if args.engine.endswith("_overlap"):
        want.add("repro.boundary")
    missing = want - names
    assert not missing, f"trace is missing spans: {sorted(missing)}"
    execs = [e["args"] for e in events
             if e["name"] == "repro.execute" and e["ph"] == "B"]
    assert sum(len(a["widths"]) for a in execs) == stats["total_waves"]
    assert sum(sum(a["widths"]) for a in execs) == stats["total_tasks"]
    if args.engine.startswith("sharded"):
        for a in execs:
            assert "rung" in a, "sharded execute span without a rung"
            for k in ("rows", "bytes", "owned"):
                assert len(a[k]) == a["n_waves"], (
                    f"execute span's {k!r} is not one entry a wave")
    syncs = payload["otherData"]["counters"]["host_syncs"]
    assert syncs >= stats["n_windows"], "host_syncs undercounts the reads"
    print(f"TRACE-OK {path} ({n_events} events, "
          f"{jax.device_count()} devices, engine={args.engine}, "
          f"waves={stats['total_waves']}, host_syncs={syncs})")


if __name__ == "__main__":
    main()
