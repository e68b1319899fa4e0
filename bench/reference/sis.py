"""Plain reference for SIS: the task chain, one task at a time, in order.

Written from the model's definition, with nothing of the program:

  task i of the stream keyed by ``seed``:
      k = fold_in(key(seed), i); kv, kx = split(k)
      v = randint(kv, 0, N)                       the agent updated
      u = uniform(kx)                             its one draw
  execution, on the state as the chain left it:
      f = (infected neighbours of v) / max(deg v, 1)      (float32)
      S -> I if u < beta * f;  I -> S if u < gamma;  else unchanged

``control`` is the same chain with the ordering guarantee broken: each
window of W tasks reads the state as it was before the window (one
synchronous wave, no conflict check), and its writes land in chain
order. It is what a run that dropped the schedule would compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _stream(key, n_tasks: int, n_agents: int):
    def one(i):
        kv, kx = jax.random.split(jax.random.fold_in(key, i))
        return jax.random.randint(kv, (), 0, n_agents), jax.random.uniform(kx)

    return jax.vmap(one)(jnp.arange(n_tasks, dtype=jnp.int32))


def _update(states, nbrs, deg, v, u, beta, gamma):
    """New state of agent v, read from ``states``."""
    nb = nbrs[v]
    ok = nb >= 0
    inf = jnp.where(ok, states[jnp.where(ok, nb, 0)] == 1, False)
    frac = (jnp.sum(inf.astype(jnp.float32))
            / jnp.maximum(deg[v], 1).astype(jnp.float32))
    cur = states[v]
    return jnp.where((cur == 0) & (u < beta * frac), 1,
                     jnp.where((cur == 1) & (u < gamma), 0, cur)
                     ).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("n_tasks", "beta", "gamma",
                                             "window"))
def _run(states, nbrs, deg, key, *, n_tasks, beta, gamma, window):
    vs, us = _stream(key, n_tasks, states.shape[0])
    if window is None:
        def step(i, st):
            return st.at[vs[i]].set(
                _update(st, nbrs, deg, vs[i], us[i], beta, gamma))

        return jax.lax.fori_loop(0, n_tasks, step, states)

    def wave(start, size, st):
        idx = start + jnp.arange(size)
        nxt = jax.vmap(lambda v, u: _update(st, nbrs, deg, v, u, beta, gamma)
                       )(vs[idx], us[idx])
        return jax.lax.fori_loop(
            0, size, lambda j, s: s.at[vs[idx[j]]].set(nxt[j]), st)

    full, rest = divmod(n_tasks, window)
    states = jax.lax.fori_loop(
        0, full, lambda w, st: wave(w * window, window, st), states)
    return wave(full * window, rest, states) if rest else states


class Reference:
    """The reference on the benchmark's own copy of the network."""

    def __init__(self, data: dict):
        self.nbrs = jnp.asarray(np.asarray(data["neighbors"]))
        self.deg = jnp.asarray(np.asarray(data["degrees"]))
        self.beta, self.gamma = float(data["beta"]), float(data["gamma"])

    def replay(self, state: dict, seed: int, n_tasks: int, *,
               window: int | None = None) -> dict:
        """Tasks 0..n_tasks-1 of the stream keyed by ``seed``, in chain
        order; with ``window``, the control."""
        out = _run(state["states"], self.nbrs, self.deg, jax.random.key(seed),
                   n_tasks=n_tasks, beta=self.beta, gamma=self.gamma,
                   window=window)
        return {"states": out}
