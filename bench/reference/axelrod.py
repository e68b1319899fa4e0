"""Plain reference for Axelrod: the task chain, one task at a time.

Written from the model's definition (arXiv:2304.01724 §4.1, after
Babeanu et al. 2018), with nothing of the program:

  task i of the stream keyed by ``seed``:
      ks, kt, kx = split(fold_in(key(seed), i), 3)
      src = randint(ks, 0, N); tgt = randint(kt, 0, N - 1), shifted past src
      ku, kf = split(kx); u = uniform(ku); g = uniform(kf, (F,))
  execution, on the traits as the chain left them:
      o = mean over features of [src_f == tgt_f]           (float32)
      if u < o and o < 1 and o >= 1 - omega: tgt copies src's feature
      argmax of g over the features where they differ.

``control`` is the same chain with the ordering guarantee broken: each
window of W tasks reads the traits as they were before the window, and
its writes land in chain order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _stream(key, n_tasks: int, n_agents: int, n_features: int):
    def one(i):
        ks, kt, kx = jax.random.split(jax.random.fold_in(key, i), 3)
        src = jax.random.randint(ks, (), 0, n_agents)
        tgt = jax.random.randint(kt, (), 0, n_agents - 1)
        tgt = jnp.where(tgt >= src, tgt + 1, tgt)
        ku, kf = jax.random.split(kx)
        return (src, tgt, jax.random.uniform(ku),
                jax.random.uniform(kf, (n_features,)))

    return jax.vmap(one)(jnp.arange(n_tasks, dtype=jnp.int32))


def _update(traits, src, tgt, u, g, omega):
    """(feature, value) that task (src, tgt) writes into tgt's row, read
    from ``traits``; the value is tgt's own when nothing is copied."""
    s, t = traits[src], traits[tgt]
    eq = s == t
    o = jnp.mean(eq.astype(jnp.float32))
    act = (u < o) & (o < 1.0) & (o >= 1.0 - omega)
    feat = jnp.argmax(jnp.where(~eq, g, -1.0))
    return feat, jnp.where(act, s[feat], t[feat])


@functools.partial(jax.jit, static_argnames=("n_tasks", "omega", "window"))
def _run(traits, key, *, n_tasks, omega, window):
    n, f = traits.shape
    src, tgt, u, g = _stream(key, n_tasks, n, f)
    if window is None:
        def step(i, tr):
            feat, val = _update(tr, src[i], tgt[i], u[i], g[i], omega)
            return tr.at[tgt[i], feat].set(val)

        return jax.lax.fori_loop(0, n_tasks, step, traits)

    def wave(start, size, tr):
        idx = start + jnp.arange(size)
        feat, val = jax.vmap(
            lambda a, b, c, d: _update(tr, a, b, c, d, omega)
        )(src[idx], tgt[idx], u[idx], g[idx])
        return jax.lax.fori_loop(
            0, size, lambda j, t: t.at[tgt[idx[j]], feat[j]].set(val[j]), tr)

    full, rest = divmod(n_tasks, window)
    traits = jax.lax.fori_loop(
        0, full, lambda w, tr: wave(w * window, window, tr), traits)
    return wave(full * window, rest, traits) if rest else traits


class Reference:
    def __init__(self, data: dict):
        self.omega = float(data["omega"])

    def replay(self, state: dict, seed: int, n_tasks: int, *,
               window: int | None = None) -> dict:
        """Tasks 0..n_tasks-1 of the stream keyed by ``seed``, in chain
        order; with ``window``, the control."""
        out = _run(state["traits"], jax.random.key(seed), n_tasks=n_tasks,
                   omega=self.omega, window=window)
        return {"traits": out}
