"""Execution-engine interface and registry.

An *engine* binds a ``MABSModel`` to a way of actually running its task
chain: strictly sequentially (the oracle), by vectorized waves on one
device, or by waves sharded over the agent axis of a device mesh. All
engines consume the identical task stream (``create_tasks`` keyed by the
global chain index) and — by the protocol's sequential-equivalence
argument — produce bit-identical state for the strict hazard rule, so the
choice of engine is a pure performance decision.

Registry:

    from repro.engine import make_engine
    eng = make_engine("sharded", model, window=256)
    state, stats = eng.run(state, total_tasks, seed=0)

``WindowedEngine`` additionally fixes the streaming structure shared by
the wavefront and sharded engines: windows of W tasks, each scheduled
(conflict matrix + wave levels, both replicated window-local objects) and
then executed wave by wave — with a double-buffered *window pipeline*:
the schedule for window t+1 is dispatched before the engine blocks on
window t's execution, so the O(W²) record check of the next window
overlaps the wave execution of the current one on the device queue.
The barrier ``wavefront`` engine runs the same windows as one device
program instead (``WavefrontEngine.run``); this host loop serves the
sharded engines and the overlapped modes.
"""
from __future__ import annotations

import abc
from typing import Any, Type

import jax
import jax.numpy as jnp

from repro.obs.stats import finalize_stats
from repro.obs.trace import current_tracer, optional_span

ENGINES: dict[str, Type["Engine"]] = {}


def register_engine(cls: Type["Engine"]) -> Type["Engine"]:
    """Class decorator: add an Engine subclass to the registry."""
    assert cls.name not in ENGINES or ENGINES[cls.name] is cls, cls.name
    ENGINES[cls.name] = cls
    return cls


def get_engine(name: str) -> Type["Engine"]:
    try:
        return ENGINES[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; registered: {sorted(ENGINES)}"
        ) from None


def make_engine(name: str, model, **kwargs) -> "Engine":
    return get_engine(name)(model, **kwargs)


class Engine(abc.ABC):
    """One way of executing a model's task chain."""

    #: registry key
    name: str = "engine"

    #: default for the cross-window overlap knob (the ``*_overlap``
    #: registry entries flip it; ``overlap=None`` keeps the class default)
    default_overlap: bool = False

    def __init__(self, model, *, window: int = 256, strict: bool = True,
                 overlap: bool | None = None):
        self.model = model
        self.window = int(window)
        self.strict = strict
        self.overlap = (self.default_overlap if overlap is None
                        else bool(overlap))

    @abc.abstractmethod
    def run(self, state: Any, total_tasks: int, *, seed: int = 0
            ) -> tuple[Any, dict]:
        """Execute total_tasks tasks from the chain; returns (state, stats).

        stats always carries ``total_tasks``, ``n_windows``,
        ``total_waves`` and ``mean_parallelism``; engines may add keys.
        """


class WindowedEngine(Engine):
    """Shared streaming loop: schedule window t+1 while window t executes.

    Subclasses provide
      * ``_schedule(base_key, start, count)`` — create + schedule one
        window; returns an opaque pytree (dispatched asynchronously), and
      * ``_execute(state, sched)`` — execute one scheduled window;
        returns (state, n_waves),
    plus optional ``_prepare_state`` / ``_finalize_state`` hooks (e.g. the
    sharded engine pads and device_puts the agent axis there). The run
    loop never blocks between windows: the only host sync is the final
    stats reduction after the last window was dispatched.

    **Cross-window overlap** (``overlap=True``, or the ``*_overlap``
    registry entries): the window boundary stops being a conservative
    barrier. When window k+1 is scheduled, the loop computes the
    carry-over conflict frontier between window k's not-yet-drained tail
    and window k+1's tasks (``records.cross_window_conflicts`` — the
    rectangular [W_next, W_tail] block through the conflict kernel — and
    ``records.carry_frontier``), then re-levels window k+1 with that
    frontier as a per-task floor (``wave_levels(base=carry)``). Execution
    proceeds in *fused* waves: each wave of window k's drain also runs
    the window k+1 tasks whose (floored) level matches, so independent
    head waves of k+1 start while k's tail drains. Tasks sharing a fused
    wave never conflict — a cross conflict (i, j) forces
    ``level_next[i] >= level_tail[j] + 1`` — so bit-exactness vs the
    sequential oracle is preserved (the differential harness pins it).
    At most two windows are ever in flight: pair step k drains window k
    completely, so window k+2 only needs the frontier against k+1's
    remainder. Overlapped subclasses provide
      * ``_schedule_ov(base_key, start, count)`` — returns
        ``(recipes, valid, conf, extra)`` (conflict matrix kept for the
        carry re-leveling; ``extra`` is engine-specific), and
      * ``_execute_pair(state, cur, lv_cur, nxt, lv_nxt)`` — runs the
        fused waves that drain ``cur``; returns
        ``(state, n_waves, lv_nxt_shifted)`` where the shifted levels
        mark executed tasks -1 and rebase the rest to the new clock.
    Engines without the pair hooks fall back to the barrier loop.
    """

    #: overlapped-mode hooks; None = barrier-only engine. ``_execute_drain``
    #: drains a window with no live partner (the run's last window, or a
    #: single-window run) through the engine's barrier executor — no
    #: dummy-partner execute_wave calls, no pair-halo gather.
    _schedule_ov = None
    _execute_pair = None
    _execute_drain = None

    def _prepare_state(self, state):
        return state

    def _finalize_state(self, state):
        return state

    def _schedule_window(self, base_key, start, count):
        """The shared scheduling recipe: create one window of tasks and
        reduce it to wave levels (conflict + levels kernels, backend
        chosen at trace time). Returns (recipes, valid, levels)."""
        from repro.core.records import wave_levels

        recipes, valid, conf = self._schedule_window_ov(
            base_key, start, count)
        return recipes, valid, wave_levels(conf, valid)

    def _schedule_window_ov(self, base_key, start, count):
        """Overlap-mode scheduling recipe: like ``_schedule_window`` but
        the conflict matrix is kept (the boundary step re-levels against
        the carry frontier). Returns (recipes, valid, conf)."""
        from repro.core.records import window_conflicts

        recipes = self.model.create_tasks(base_key, start, self.window)
        valid = jnp.arange(self.window) < count
        conf = window_conflicts(self.model, recipes, valid,
                                strict=self.strict)
        return recipes, valid, conf

    def _schedule(self, base_key, start, count):  # pragma: no cover - abstract
        raise NotImplementedError

    def _execute(self, state, sched):  # pragma: no cover - abstract
        raise NotImplementedError

    # ------------------------------------------------------ compiled costs
    def _cost_targets(self, base_key, state):
        """``(name, jitted_fn, example_args)`` triples for the engine's
        jit-boundary window executors (the functions ``_execute``
        dispatches to), lowered AOT by ``compiled_costs``. ``state`` is
        already prepared (``_prepare_state`` has run, so the sharded
        executors are built). None = no AOT-lowerable executors (jit
        disabled, or no hook)."""
        return None

    def compiled_costs(self, state, *, seed: int = 0):
        """Compiled-cost telemetry of this engine's window executors:
        ``{name: repro.obs.costs.ExecutorCost}`` with cost_analysis
        FLOPs/bytes, the memory decomposition, and the HLO-parsed
        collective ops (classified by dynamic-loop depth — resolve
        against executed iteration counts, e.g. the sharded engine's
        ``comm_iteration_counts``). Lowering compiles but never runs, so
        ``state`` is not consumed. Returns None for engines/configs with
        no AOT-lowerable executors; overlapped runs dispatch the pair
        executors instead of these, so cost capture is barrier-mode only.
        """
        if self.overlap:
            return None
        from repro.obs.costs import executor_cost

        state = self._prepare_state(state)
        targets = self._cost_targets(jax.random.key(seed), state)
        if not targets:
            return None
        return {name: executor_cost(fn, *args, name=name)
                for name, fn, args in targets}

    # ------------------------------------------------------------- tracing
    #
    # Reached only with a tracer installed (``repro.obs.tracing()``); the
    # run loops check ``current_tracer()`` once per run, so the untraced
    # loops hold no trace call. Spans never fence: a traced run issues the
    # same jitted calls in the same order as an untraced one, and what
    # only the device knows (wave counts, levels, shipped rows) is kept by
    # reference on the span and read after the run (``Span.defer``).

    def _trace_parts(self, sched, levels=None):  # pragma: no cover - abstract
        """(levels, write_agents, rows) of one window's schedule, for the
        per-wave facts. ``levels`` overrides the schedule's own level
        vector (the overlapped loop re-levels and rebases)."""
        raise NotImplementedError

    def _trace_comm(self):
        """The comm-ledger entry of the window just executed, or None for
        engines that ship nothing (single device)."""
        return None

    def _trace_wave_comm(self, np_parts, n_waves, entry):
        """Comm facts of one executed window from its ledger ``entry``:
        a dict of per-execute args (e.g. the sharded engine's rung and
        per-wave ``rows``/``bytes``/``owned`` lists); empty when
        ``entry`` is None."""
        return {}

    def _stats_syncs(self) -> int:
        """Device values ``_extend_stats`` reads, each a blocking read."""
        return 0

    def _dispatch_schedule(self, tr, fn, base_key, start, count, *, index):
        """Dispatch one window's schedule (``fn``), inside a
        ``repro.schedule`` span when tracing is on."""
        if tr is None:
            return fn(base_key, start, count)
        with tr.span("repro.schedule", index=index, start=start, count=count):
            return fn(base_key, start, count)

    def _traced_execute(self, tr, fn, args, parts, **span_args):
        """Dispatch one window's executor ``fn(*args)`` inside a
        ``repro.execute`` span, whose per-wave facts are read after the
        run from ``parts`` (the live windows' ``_trace_parts``: two for a
        fused pair drain) and the executor's wave count."""
        with tr.span("repro.execute", **span_args) as sp:
            out = fn(*args)
        sp.defer(self._wave_facts, parts, out[1], self._trace_comm())
        return out

    def _wave_facts(self, parts, n_waves, entry):
        import numpy as np

        n = int(n_waves)
        widths = np.zeros(n, np.int64)
        np_parts = []
        for lv, wa, rows in parts:
            lv = np.asarray(lv)
            np_parts.append((lv,
                             None if wa is None else np.asarray(wa),
                             None if rows is None else np.asarray(rows)))
            sel = lv[(lv >= 0) & (lv < n)]
            widths += np.bincount(sel, minlength=n)[:n]
        return {"n_waves": n, "widths": widths.tolist(),
                **self._trace_wave_comm(np_parts, n, entry)}

    # ------------------------------------------------- cross-window overlap
    def _make_boundary(self):
        """Jitted boundary step (``_boundary_step``)."""
        return jax.jit(self._boundary_step())

    def _boundary_step(self):
        """Boundary step for one window transition k -> k+1: cross-window
        record check, carry frontier, floored re-leveling, and the
        per-boundary overlap statistics."""
        from repro.core.records import (
            carry_frontier,
            cross_window_conflicts,
            wave_levels,
        )

        model, strict, w = self.model, self.strict, self.window

        def boundary(rec_a, lv_a, rec_b, valid_b, conf_b):
            alive_a = lv_a >= 0          # window k's not-yet-drained tail
            cross = cross_window_conflicts(model, rec_a, alive_a,
                                           rec_b, valid_b, strict=strict)
            carry = carry_frontier(cross, lv_a)
            lv_b = wave_levels(conf_b, valid_b, base=carry)
            n_waves_a = jnp.max(lv_a) + 1
            # overlap depth: tail waves of k during which k+1 tasks run
            early = valid_b & (lv_b < n_waves_a)
            occ = jnp.zeros((w,), bool).at[
                jnp.where(early, lv_b, w)].set(True, mode="drop")
            n_valid = jnp.maximum(jnp.sum(valid_b), 1)
            bstats = (jnp.sum(occ),                              # depth
                      jnp.sum(early),                            # early tasks
                      jnp.sum(jnp.where(valid_b, carry, 0)) / n_valid,
                      jnp.max(jnp.where(valid_b, carry, 0), initial=0))
            return lv_b, bstats

        return boundary

    def _schedule_first(self, base_key, start, count):
        """Overlap mode's first window: its schedule and its levels (no
        predecessor, so no carry floor)."""
        if getattr(self, "_levels0_fn", None) is None:
            from repro.core.records import wave_levels

            self._levels0_fn = jax.jit(wave_levels)
        cur = self._schedule_ov(base_key, start, count)
        return cur, self._levels0_fn(cur[2], cur[1])

    def _run_overlapped(self, state: Any, total_tasks: int, *, seed: int = 0):
        tr = current_tracer()
        t = 0
        n_windows = 0
        wave_counts = []
        bstats = []
        with optional_span(tr, "repro.run", engine=self.name,
                           window=self.window, total_tasks=total_tasks,
                           overlap=True):
            base_key = jax.random.key(seed)
            state = self._prepare_state(state)
            if getattr(self, "_boundary_fn", None) is None:
                self._boundary_fn = self._make_boundary()
            cur, lv = self._dispatch_schedule(
                tr, self._schedule_first, base_key, 0,
                min(self.window, total_tasks), index=0)
            while t < total_tasks:
                k = min(self.window, total_tasks - t)
                if t + k < total_tasks:
                    # dispatch window k+1's schedule + boundary (cross
                    # block, carry frontier, floored levels) before
                    # blocking on the fused drain of window k — same
                    # double buffering as the barrier loop, now with the
                    # carry-over record check
                    nxt = self._dispatch_schedule(
                        tr, self._schedule_ov, base_key, t + k,
                        min(self.window, total_tasks - t - k),
                        index=n_windows + 1)
                    if tr is None:
                        lv_nxt, b = self._boundary_fn(cur[0], lv,
                                                      nxt[0], nxt[1], nxt[2])
                        state, n_waves, lv_nxt = self._execute_pair(
                            state, cur, lv, nxt, lv_nxt)
                    else:
                        with tr.span("repro.boundary",
                                     index=n_windows) as bsp:
                            lv_nxt, b = self._boundary_fn(
                                cur[0], lv, nxt[0], nxt[1], nxt[2])
                        bsp.defer(_boundary_facts, b)
                        state, n_waves, lv_nxt = self._traced_execute(
                            tr, self._execute_pair,
                            (state, cur, lv, nxt, lv_nxt),
                            [self._trace_parts(cur, lv),
                             self._trace_parts(nxt, lv_nxt)],
                            index=n_windows, start=t, count=k, fused=True)
                    bstats.append(b)
                    cur, lv = nxt, lv_nxt
                else:
                    # last window: no partner — drain through the barrier
                    # executor (skips the empty-mask partner waves and,
                    # for the sharded engine, the doubled pair-halo
                    # gather)
                    if tr is None:
                        state, n_waves = self._execute_drain(state, cur, lv)
                    else:
                        state, n_waves = self._traced_execute(
                            tr, self._execute_drain, (state, cur, lv),
                            [self._trace_parts(cur, lv)],
                            index=n_windows, start=t, count=k, drain=True)
                wave_counts.append(n_waves)
                n_windows += 1
                t += k
            with optional_span(tr, "repro.readback", windows=n_windows):
                # the run's host syncs: one read a window, four a boundary
                total_waves = int(sum(int(w) for w in wave_counts))
                state = self._finalize_state(state)
                depths = [int(b[0]) for b in bstats]
                earlies = [int(b[1]) for b in bstats]
                cmeans = [float(b[2]) for b in bstats]
                cmaxs = [int(b[3]) for b in bstats]
                stats = {
                    "total_tasks": total_tasks,
                    "n_windows": n_windows,
                    "total_waves": total_waves,
                    "mean_parallelism": total_tasks / max(total_waves, 1),
                    "overlap": True,
                    "n_boundaries": len(bstats),
                    "mean_overlap_depth": (sum(depths) / len(depths)
                                           if depths else 0.0),
                    "max_overlap_depth": max(depths, default=0),
                    "overlap_tasks_early": sum(earlies),
                    "carry_frontier_mean": (sum(cmeans) / len(cmeans)
                                            if cmeans else 0.0),
                    "carry_frontier_max": max(cmaxs, default=0),
                }
                stats = finalize_stats(self._extend_stats(stats))
            if tr is not None:
                tr.count("host_syncs", n_windows + 4 * len(bstats)
                         + self._stats_syncs())
        return state, stats

    def run(self, state: Any, total_tasks: int, *, seed: int = 0):
        if self.overlap:
            # NB: only the schedule hook is checked here — engines may
            # defer building the pair executor until the state shape is
            # known (_prepare_state), as the sharded engine does
            if self._schedule_ov is None:
                raise ValueError(
                    f"engine {self.name!r} does not implement cross-window "
                    "overlap; use overlap=False (the barrier fallback)")
            return self._run_overlapped(state, total_tasks, seed=seed)
        tr = current_tracer()
        t = 0
        n_windows = 0
        wave_counts = []
        with optional_span(tr, "repro.run", engine=self.name,
                           window=self.window, total_tasks=total_tasks,
                           overlap=False):
            base_key = jax.random.key(seed)
            state = self._prepare_state(state)
            nxt = self._dispatch_schedule(
                tr, self._schedule, base_key, 0,
                min(self.window, total_tasks), index=0)
            while t < total_tasks:
                k = min(self.window, total_tasks - t)
                cur = nxt
                if t + k < total_tasks:
                    # double buffering: dispatch window t+1's schedule
                    # (conflict matrix + levels) before blocking on window
                    # t's execution
                    nxt = self._dispatch_schedule(
                        tr, self._schedule, base_key, t + k,
                        min(self.window, total_tasks - t - k),
                        index=n_windows + 1)
                if tr is None:
                    state, n_waves = self._execute(state, cur)
                else:
                    state, n_waves = self._traced_execute(
                        tr, self._execute, (state, cur),
                        [self._trace_parts(cur)],
                        index=n_windows, start=t, count=k)
                wave_counts.append(n_waves)
                n_windows += 1
                t += k
            with optional_span(tr, "repro.readback", windows=n_windows):
                # the run's host syncs: one read a window
                total_waves = int(sum(int(w) for w in wave_counts))
                state = self._finalize_state(state)
                stats = {
                    "total_tasks": total_tasks,
                    "n_windows": n_windows,
                    "total_waves": total_waves,
                    "mean_parallelism": total_tasks / max(total_waves, 1),
                    "overlap": False,
                }
                stats = finalize_stats(self._extend_stats(stats))
            if tr is not None:
                tr.count("host_syncs", n_windows + self._stats_syncs())
        return state, stats

    def _extend_stats(self, stats: dict) -> dict:
        return stats


def _boundary_facts(b):
    """A boundary span's overlap accounting, read after the run."""
    return {"overlap_depth": int(b[0]), "early_tasks": int(b[1]),
            "carry_mean": float(b[2]), "carry_max": int(b[3])}
