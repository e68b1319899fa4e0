"""Single-device wavefront engine (formerly ``core.wavefront
.WavefrontRunner``, now behind the engine registry).

Streams the chain through windows of W tasks: each window is scheduled
(prefix-conflict matrix through the conflict kernel, wave levels through
the levels kernel — backend chosen at trace time) and executed one
vectorized wave at a time. By default the window boundary is a
conservative barrier, so cross-window ordering is trivially preserved;
the shared ``WindowedEngine`` loop overlaps window t+1's scheduling with
window t's execution.

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

from repro.engine.base import WindowedEngine, register_engine


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

        # NB: no donation here — callers hand this engine externally owned
        # state (and often reuse it for the oracle run); the sharded engine
        # donates because it owns its device_put copy.
        self._schedule = (jax.jit(self._schedule_window) if jit
                          else self._schedule_window)
        self._execute = jax.jit(_execute) if jit else _execute

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


#: Backwards-compatible name for the pre-registry runner class.
WavefrontRunner = WavefrontEngine
