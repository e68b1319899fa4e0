"""SIS on a contact network: data from the seed, the program's model on it.

The network is the deployment's: built on the host from the
configuration's own ``network_seed`` (one network that every run of the
configuration reuses, as a modeller reuses one contact network across
runs), so its shapes, and the executables that embed it, are the same
in every run. The infected agents at the start are drawn from the run's
seed, on the device.

Network: Watts-Strogatz small world, the program's convention — each
clockwise ring edge (v, v+j), j = 1..k/2, is rewired with probability p
to (v, u), u uniform among the other nodes; a rewire that lands on an
edge already there is dropped; then every isolated node is joined to one
uniform other node. Rows of the padded table ascend by node id, -1 past
the degree.
"""
from __future__ import annotations

import numpy as np


def _table(n: int, src: np.ndarray, dst: np.ndarray):
    """Padded neighbor table of the simple undirected graph on the given
    edges: ([n, max degree] int32 with -1 padding, degrees [n] int32)."""
    a = np.concatenate([src, dst]).astype(np.int64)
    b = np.concatenate([dst, src]).astype(np.int64)
    keep = a != b
    key = np.unique(a[keep] * n + b[keep])
    s, d = key // n, key % n
    deg = np.bincount(s, minlength=n)
    start = np.concatenate([[0], np.cumsum(deg)[:-1]])
    slot = np.arange(key.size) - start[s]
    nbrs = np.full((n, max(int(deg.max()), 1)), -1, np.int32)
    nbrs[s, slot] = d
    return nbrs, deg.astype(np.int32)


def network(cfg: dict):
    """The configuration's contact network as (neighbors, degrees)."""
    n, half, p = int(cfg["n_agents"]), int(cfg["k"]) // 2, float(cfg["p_rewire"])
    rng = np.random.default_rng(int(cfg["network_seed"]))
    src = np.repeat(np.arange(n, dtype=np.int64), half)
    dst = (src + np.tile(np.arange(1, half + 1), n)) % n
    rewire = rng.random(src.size) < p
    u = rng.integers(0, n - 1, size=src.size)
    u = np.where(u >= src, u + 1, u)
    dst = np.where(rewire, u, dst)
    nbrs, deg = _table(n, src, dst)
    iso = np.flatnonzero(deg == 0)
    if iso.size:
        partner = rng.integers(0, n - 1, size=iso.size)
        partner = np.where(partner >= iso, partner + 1, partner)
        s2, d2 = np.nonzero(nbrs >= 0)
        nbrs, deg = _table(n, np.concatenate([s2, iso]),
                           np.concatenate([nbrs[s2, d2], partner]))
    return nbrs, deg


def build(cfg: dict, state_seed: int) -> dict:
    """The program's SIS model on the configuration's network, its start
    state from ``state_seed``, and the data the plain reference gets."""
    import jax
    import jax.numpy as jnp

    from repro.mabs.sis import SISConfig, SISModel
    from repro.topology import Topology

    nbrs, deg = network(cfg)
    model = SISModel(
        Topology(neighbors=jnp.asarray(nbrs), degrees=jnp.asarray(deg)),
        SISConfig(beta=cfg["beta"], gamma=cfg["gamma"], i0=cfg["i0"]))
    n, i0 = int(cfg["n_agents"]), float(cfg["i0"])

    @jax.jit
    def init(key):
        u = jax.random.uniform(key, (n,))
        return {"states": (u < i0).astype(jnp.int8)}

    return {
        "model": model,
        "state": init(jax.random.key(state_seed)),
        # infected share, the epidemic's prevalence
        "observe": jax.jit(lambda s: jnp.mean(s["states"] == 1)),
        # one task updates one agent: a sweep is n_agents tasks
        "tasks_per_sweep": n,
        "footprint": (nbrs.shape[1] + 1, 1),
        "ref_data": {"neighbors": nbrs, "degrees": deg,
                     "beta": float(cfg["beta"]), "gamma": float(cfg["gamma"])},
        "describe": (f"SIS on WS({n}, {cfg['k']}, {cfg['p_rewire']}), "
                     f"max degree {nbrs.shape[1]}, "
                     f"{int(deg.sum()) // 2} edges"),
    }
