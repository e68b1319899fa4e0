"""Single-device wavefront engine (formerly ``core.wavefront
.WavefrontRunner``, now behind the engine registry).

Streams the chain through windows of W tasks: each window is scheduled
(prefix-conflict matrix through the conflict kernel, wave levels through
the levels kernel — backend chosen at trace time) and executed one
vectorized wave at a time. By default the window boundary is a
conservative barrier, so cross-window ordering is trivially preserved,
and the whole run is one device program (``run_windows``): a
``lax.while_loop`` over windows that schedules and executes each in
turn, so the host dispatches once and reads back one wave count a run.

With ``overlap=True`` (or the ``wavefront_overlap`` registry entry) the
barrier falls: window k+1 is re-leveled against the carry-over conflict
frontier of window k's tail (``WindowedEngine`` docstring) and the two
windows drain in *fused* waves — each wave executes window k's tasks at
that level and then window k+1's, which never conflict with them by
construction of the frontier. Bit-exactness vs the sequential oracle is
unchanged (differential-harness-tested); what changes is the wave count:
independent head waves of k+1 ride along with k's tail instead of
waiting behind it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.base import WindowedEngine, register_engine
from repro.obs.stats import finalize_stats
from repro.obs.trace import current_tracer, optional_span


@register_engine
class WavefrontEngine(WindowedEngine):
    name = "wavefront"

    def __init__(self, model, *, window: int = 256, strict: bool = True,
                 jit: bool = True, overlap: bool | None = None):
        super().__init__(model, window=window, strict=strict,
                         overlap=overlap)
        self._jit = jit
        # deferred so `import repro.engine` works before repro.core's
        # package init has run (core's init imports this module for the
        # WavefrontRunner compat re-export)
        from repro.core.wavefront import execute_window

        def _execute(state, sched):
            recipes, valid, levels = sched
            return execute_window(model, state, recipes, valid,
                                  strict=self.strict, levels=levels)

        # NB: no donation in any executor here — callers hand this engine
        # externally owned state (and often reuse it for the oracle run);
        # the sharded engine donates because it owns its device_put copy.
        self._schedule = (jax.jit(self._schedule_window) if jit
                          else self._schedule_window)
        self._execute = jax.jit(_execute) if jit else _execute

        def run_windows(state, seed, total):
            """Every window of a barrier run: schedule window [t, t+W)
            and execute it, until ``total`` tasks ran. ``t`` and
            ``total`` are traced int32, so one executable serves every
            run length; the run's key is made here from its uint32
            ``seed``, so the host makes no call of its own for it.
            Returns (state, total_waves)."""
            w = self.window
            base_key = jax.random.key(seed)

            def body(carry):
                t, st, waves = carry
                recipes, valid, levels = self._schedule_window(
                    base_key, t, jnp.minimum(w, total - t))
                st, n_waves = execute_window(model, st, recipes, valid,
                                             strict=self.strict,
                                             levels=levels)
                return t + w, st, waves + n_waves

            _, state, waves = jax.lax.while_loop(
                lambda c: c[0] < total, body,
                (jnp.int32(0), state, jnp.int32(0)))
            return state, waves

        self._run_windows = jax.jit(run_windows) if jit else run_windows

        def _schedule_ov(base_key, start, count):
            recipes, valid, conf = self._schedule_window_ov(
                base_key, start, count)
            return recipes, valid, conf, None

        def _execute_pair(state, cur, lv_a, nxt, lv_b):
            rec_a, rec_b = cur[0], nxt[0]
            n_waves = jnp.max(lv_a) + 1

            def body(carry):
                w, st = carry
                # fused wave: window k's tasks at level w, then window
                # k+1's — the carry frontier guarantees the two masks
                # never hold conflicting tasks, so order is immaterial
                st = model.execute_wave(st, rec_a, lv_a == w)
                st = model.execute_wave(st, rec_b, lv_b == w)
                return w + 1, st

            from repro.obs.profiler import annotate

            with annotate("protocol.execute_pair"):
                _, state = jax.lax.while_loop(
                    lambda c: c[0] < n_waves, body, (jnp.int32(0), state))
            # rebase the next window onto the new level clock; executed
            # (and invalid) tasks drop to -1
            lv_b = jnp.where(lv_b >= n_waves, lv_b - n_waves, -1)
            return state, n_waves, lv_b

        self._schedule_ov = jax.jit(_schedule_ov) if jit else _schedule_ov
        self._execute_pair = (jax.jit(_execute_pair) if jit
                              else _execute_pair)
        # partnerless drain (last / only window): the barrier executor
        # already takes (recipes, valid, levels) — reuse it so no
        # empty-mask partner waves are executed
        self._execute_drain = lambda state, cur, lv: self._execute(
            state, (cur[0], cur[1], lv))

    def run(self, state, total_tasks: int, *, seed: int = 0):
        if self.overlap:
            return super().run(state, total_tasks, seed=seed)
        tr = current_tracer()
        total_tasks = int(total_tasks)
        n_windows = -(-total_tasks // self.window)
        with optional_span(tr, "repro.run", engine=self.name,
                           window=self.window, total_tasks=total_tasks,
                           overlap=False):
            # numpy scalars cross the jit boundary faster than Python
            # ints; a uint32 seed makes the key jax.random.key(seed) makes
            # on the host (32-bit mode keeps the low 32 bits of the seed)
            args = (np.uint32(seed & 0xFFFFFFFF), np.int32(total_tasks))
            if tr is None:
                state, waves = self._run_windows(state, *args)
            else:
                with tr.span("repro.execute", windows=n_windows, start=0,
                             count=total_tasks, device_loop=True) as sp:
                    state, waves = self._run_windows(state, *args)
                sp.defer(_loop_facts, waves)
                tr.count("device_loop_windows", n_windows)
            with optional_span(tr, "repro.readback", windows=n_windows):
                # the run's one host sync
                total_waves = int(waves)
                stats = finalize_stats(self._extend_stats({
                    "total_tasks": total_tasks,
                    "n_windows": n_windows,
                    "total_waves": total_waves,
                    "mean_parallelism": total_tasks / max(total_waves, 1),
                    "overlap": False,
                }))
            if tr is not None:
                tr.count("host_syncs", 1 + self._stats_syncs())
        return state, stats

    def _trace_parts(self, sched, levels=None):
        # barrier schedule carries its levels in slot 2; the overlapped
        # loop re-levels and passes them explicitly. Single device: no
        # write-owner or halo-row attributes.
        lv = sched[2] if levels is None else levels
        return lv, None, None

    def _cost_targets(self, base_key, state):
        if not self._jit:
            return None
        sched = self._schedule(base_key, 0, self.window)
        return [("execute_window", self._execute, (state, sched))]


@register_engine
class WavefrontOverlapEngine(WavefrontEngine):
    """``wavefront`` with cross-window overlap on by default — the
    registry entry the differential harness and benchmarks sweep; the
    plain ``wavefront`` engine stays the registered barrier fallback."""

    name = "wavefront_overlap"
    default_overlap = True


def _loop_facts(waves):
    """The device loop's execute span facts, read after the run."""
    return {"n_waves": int(waves)}


#: Backwards-compatible name for the pre-registry runner class.
WavefrontRunner = WavefrontEngine
