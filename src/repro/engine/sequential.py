"""Sequential oracle engine: the chain order, one task at a time.

This is the correctness reference every other engine is property-tested
against (bit-exact under the strict hazard rule). ``run_sequential`` is
the bare-function form kept for the existing call sites.
"""
from __future__ import annotations

from typing import Any

import jax

from repro.engine.base import Engine, register_engine
from repro.obs.stats import finalize_stats
from repro.obs.trace import current_tracer, optional_span


def run_sequential(model, state, total_tasks: int, *, seed: int = 0,
                   window: int = 256):
    """Oracle runner: same task stream, strictly sequential execution."""
    tr = current_tracer()
    base_key = jax.random.key(seed)
    t = 0
    index = 0
    seq = jax.jit(
        lambda st, key, start, count: model.execute_sequential(
            st, model.create_tasks(key, start, window), count
        )
    )
    while t < total_tasks:
        k = min(window, total_tasks - t)
        if tr is None:
            state = seq(state, base_key, t, k)
        else:
            with tr.span("repro.execute", index=index, start=t, count=k,
                         sequential=True):
                state = seq(state, base_key, t, k)
        t += k
        index += 1
    return state


@register_engine
class SequentialEngine(Engine):
    """Registry wrapper around ``run_sequential`` (stats are trivial:
    every task is its own wave)."""

    name = "sequential"

    def run(self, state: Any, total_tasks: int, *, seed: int = 0):
        with optional_span(current_tracer(), "repro.run", engine=self.name,
                           window=self.window, total_tasks=total_tasks,
                           overlap=False):
            state = run_sequential(self.model, state, total_tasks,
                                   seed=seed, window=self.window)
        stats = {
            "total_tasks": total_tasks,
            "n_windows": -(-total_tasks // self.window) if total_tasks else 0,
            "total_waves": total_tasks,
            "mean_parallelism": 1.0,
        }
        return state, finalize_stats(stats)
