#!/usr/bin/env python3
"""Chip smoke test: the window/record protocol end to end on a TPU.

Runs the simulation's main path through its public entry points
(``repro.core.run_engine`` / ``run_oracle``), all in this one process,
and checks every result bit for bit against the sequential oracle on the
same seed and task stream.

  Phase A  SIS on ``connect_isolated(watts_strogatz(10**6, 4, 0.1))``
           (max degree 9: read footprint nr = 10, write nw = 1), W = 256,
           64 windows = 16 384 tasks, through ``wavefront`` (triangular
           ``conflict`` + ``levels`` kernels) and ``wavefront_overlap``
           (adds ``conflict_block`` and the floored ``levels`` call).
  Phase B  Axelrod at the paper's widest Fig. 2 setting, 10**4 agents and
           F = 500 features, through the same engines at W = 256.

With ``--chips 4`` only Phase A runs, through the multi-device
``sharded`` (per-wave halo split) and ``sharded_overlap`` engines on a
4-device ``agents`` mesh, against the same oracle; it checks that the
state is spread over 4 distinct devices and prints the comm ledger.

Each phase prints, per engine, the seconds of the first (compiling) call
and of a second fenced call, waves and mean parallelism, the backend each
scheduling kernel resolved to, XLA compile seconds and persistent-cache
hits, and the device's ``peak_bytes_in_use``. The script refuses to run
off a TPU, and fails if a scheduling kernel resolved to the jnp fallback
or to the Pallas interpreter. The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Run:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: the kernels every window's schedule must run compiled on the chip
SCHEDULING_KERNELS = ("conflict_matrix", "conflict_block", "wave_levels")

WINDOW = 256
N_WINDOWS = 64
SEED = 3


def log(msg: str) -> None:
    print(msg, flush=True)


def fenced(fn):
    """(seconds, result) of ``fn()`` with every output leaf awaited."""
    from repro.utils.timing import block_all

    t0 = time.perf_counter()
    out = block_all(fn())
    return time.perf_counter() - t0, out


def same_state(a, b) -> bool:
    import jax
    import numpy as np

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def sis_case(n_agents: int):
    """SIS on a seeded Watts-Strogatz network. The network is set-up, not
    the path under test: it is built on the host CPU, where the
    generators' scatter-heavy edge-list compaction runs in seconds (it
    took 169 s op by op on a v5e), then handed to the default device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.mabs.sis import SISModel
    from repro.topology import connect_isolated, watts_strogatz

    def build():
        return connect_isolated(
            watts_strogatz(n_agents, 4, 0.1, jax.random.key(0)),
            jax.random.key(1))

    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:  # JAX_PLATFORMS leaves the CPU backend out
        host = None
    with jax.default_device(host):
        t, topo = fenced(build)
    topo = jax.tree_util.tree_map(lambda x: jnp.asarray(np.asarray(x)), topo)
    log(f"  topology: WS({n_agents}, 4, 0.1) + connect_isolated, "
        f"max_degree={topo.max_degree} (nr={topo.max_degree + 1}, nw=1), "
        f"built on {'the host CPU' if host else 'the default device'} "
        f"in {t:.3f} s")
    model = SISModel(topo)
    return model, model.init_state(jax.random.key(2))


def axelrod_case(n_agents: int, n_features: int):
    import jax

    from repro.mabs.axelrod import AxelrodConfig, AxelrodModel

    model = AxelrodModel(AxelrodConfig(n_agents=n_agents,
                                       n_features=n_features))
    log(f"  Axelrod: {n_agents} agents, F={n_features}, q={model.cfg.q} "
        "(complete-graph mixing, nr=2, nw=1)")
    return model, model.init_state(jax.random.key(2))


def run_phase(label: str, model, state, engines, *, total: int,
              engine_kwargs=None) -> dict:
    """Oracle once, then each engine twice through ``run_engine``; every
    result compared bit for bit with the oracle, and every scheduling
    kernel checked to have run compiled. Returns ``{engine: state}``."""
    import jax

    import repro.kernels as kernels
    from repro.core import ProtocolConfig, run_engine, run_oracle
    from repro.utils.compile_cache import compile_stats

    cfg = ProtocolConfig(window=WINDOW)
    kernels.RESOLVED.clear()
    c0 = compile_stats()
    t_or, ref = fenced(lambda: run_oracle(model, state, total, seed=SEED,
                                          config=cfg))
    log(f"  oracle (sequential): {total} tasks, first call {t_or:.3f} s")
    outs = {}
    for name in engines:
        kw = dict(engine_kwargs or {})
        call = (lambda: run_engine(model, state, total, seed=SEED,
                                   config=cfg, engine=name, **kw))
        t1, (out1, _) = fenced(call)
        t2, (out2, stats) = fenced(call)
        exact = same_state(out1, ref) and same_state(out2, ref)
        line = (f"  {name}: first call {t1:.3f} s, second call {t2:.3f} s, "
                f"windows={stats['n_windows']} waves={stats['total_waves']} "
                f"mean_parallelism={stats['mean_parallelism']:.3f} "
                f"bit_exact_vs_oracle={exact}")
        if "comm_modes" in stats:
            line += (f" per_wave_comm_bytes={stats['per_wave_comm_bytes']}"
                     f" comm_modes={stats['comm_modes']}")
        log(line)
        outs[name] = out2
        if not exact:
            raise AssertionError(f"{label}/{name} differs from the oracle")
    c1 = compile_stats()
    log(f"  kernels resolved: {dict(sorted(kernels.RESOLVED.items()))}")
    log(f"  XLA compile (and cache load) {c1['compile_s'] - c0['compile_s']:.3f}"
        f" s, persistent cache hits={c1['hits'] - c0['hits']} "
        f"misses={c1['misses'] - c0['misses']}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"  device 0 peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    bad = {k: kernels.RESOLVED.get(k, "not traced")
           for k in SCHEDULING_KERNELS
           if kernels.RESOLVED.get(k) != "pallas"}
    if bad:
        raise AssertionError(
            f"{label}: scheduling kernels off the compiled Pallas path: {bad}")
    return outs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only Phase A through the sharded engines "
                         "on a 4-device mesh")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2

    from repro.utils.compile_cache import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    total = WINDOW * N_WINDOWS

    if args.chips == 4:
        devices = jax.devices()[:4]
        log("phase A (4 chips): SIS on a 10^6-agent Watts-Strogatz network")
        model, state = sis_case(10**6)
        outs = run_phase("A4", model, state, ("sharded", "sharded_overlap"),
                         total=total, engine_kwargs={"devices": devices})
        for name, out in outs.items():
            shard_devs = {s.device for x in jax.tree_util.tree_leaves(out)
                          for s in x.addressable_shards}
            log(f"  {name}: state shards on {sorted(d.id for d in shard_devs)}")
            if len(shard_devs) != 4 or shard_devs == {devices[0]}:
                raise AssertionError(
                    f"{name}: state not spread over 4 devices: {shard_devs}")
    else:
        log("phase A: SIS on a 10^6-agent Watts-Strogatz network")
        model, state = sis_case(10**6)
        run_phase("A", model, state, ("wavefront", "wavefront_overlap"),
                  total=total)
        log("phase B: Axelrod at the paper's width (Fig. 2, F = 500)")
        model, state = axelrod_case(10_000, 500)
        run_phase("B", model, state, ("wavefront", "wavefront_overlap"),
                  total=total)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
