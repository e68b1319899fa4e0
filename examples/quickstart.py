"""Quickstart: run a cultural-dynamics MABS through the adaptive
parallelization protocol, three ways:

  1. sequential oracle          (the chain, executed in order)
  2. SPMD wavefront engine      (the TPU-native adaptation — bit-identical)
  3. protocol DES               (paper-faithful n-worker simulation: T(n))

Usage:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import ProtocolConfig, run_oracle, run_wavefront, \
    simulate_protocol
from repro.mabs.axelrod import AxelrodConfig, AxelrodModel
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    model = AxelrodModel(AxelrodConfig(n_agents=500, n_features=20, q=3))
    state0 = model.init_state(jax.random.key(0))
    n_tasks = 2_000
    cfg = ProtocolConfig(window=256, strict=True)

    print("== sequential oracle ==")
    seq = run_oracle(model, state0, n_tasks, seed=42, config=cfg)

    print("== wavefront engine ==")
    wave, stats = run_wavefront(model, state0, n_tasks, seed=42, config=cfg)
    identical = bool(jnp.all(seq["traits"] == wave["traits"]))
    print(f"   bit-identical to sequential: {identical}")
    print(f"   mean wave parallelism: {stats['mean_parallelism']:.1f} "
          f"tasks/wave over {stats['total_waves']} waves")
    assert identical

    print("== protocol simulation (paper §3.3 workflow) ==")
    for n in (1, 2, 4):
        r = simulate_protocol(model.des_model(seed=42), n_tasks,
                              config=ProtocolConfig(n_workers=n))
        print(f"   n={n} workers: T={r.makespan*1e3:.2f} ms, "
              f"per-worker tasks={r.executed_per_worker}")


if __name__ == "__main__":
    main()
