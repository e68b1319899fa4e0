"""Axelrod cultural dynamics: the program's model, traits from the seed.

Complete-graph mixing, as in the paper's Fig. 2: each task picks a
source uniformly and a distinct target uniformly. The start traits are
drawn uniformly from {0..q-1} on the device from the run's seed.
"""
from __future__ import annotations


def build(cfg: dict, state_seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.mabs.axelrod import AxelrodConfig, AxelrodModel

    n, f, q = int(cfg["n_agents"]), int(cfg["n_features"]), int(cfg["q"])
    model = AxelrodModel(AxelrodConfig(n_agents=n, n_features=f, q=q,
                                       omega=cfg["omega"]))

    @jax.jit
    def init(key):
        return {"traits": jax.random.randint(key, (n, f), 0, q,
                                             dtype=jnp.int32)}

    return {
        "model": model,
        "state": init(jax.random.key(state_seed)),
        # sum of all traits: moves with every copied feature
        "observe": jax.jit(lambda s: jnp.sum(s["traits"])),
        # one task updates one agent: a sweep is n_agents tasks
        "tasks_per_sweep": n,
        "footprint": (2, 1),
        "ref_data": {"n_agents": n, "n_features": f,
                     "omega": float(cfg["omega"])},
        "describe": f"Axelrod, {n} agents, F={f}, q={q}, complete graph",
    }
