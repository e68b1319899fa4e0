"""Continuous-batching serving engine — the paper's protocol applied to LLM
inference (DESIGN.md §4).

Mapping onto the paper's constructs:

  task      — one unit of request work: a prefill chunk or one decode step
  recipe    — (request id, kind, chunk index); created when the request's
              previous task completes (bottom-up, asynchronous arrival)
  record    — "which requests already have a task ahead of me in this
              window" — the conflict rule is simply `same request id`
              (each request's tasks read/write only its own slot state =
              localized dynamics; different requests commute)
  chain     — the engine's pending-task window, rebuilt every iteration
              from per-request progress + the arrival queue
  wave      — the set of commuting front tasks, executed as ONE batched
              decode step (plus prefill chunk calls); exactly the paper's
              "different workers may handle different agents at different
              times", realized SPMD

Straggler mitigation: long prompts are split into `prefill_chunk` tasks, so
a 32k-prompt request never blocks the decode wave of other requests —
adaptive handling of heterogeneous work, the paper's headline property.

The engine is scheduler-faithful rather than throughput-tuned on CPU: the
wavefront schedule it produces is asserted (tests) to give bit-identical
tokens to per-request sequential decoding.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.records import prefix_conflicts, wave_levels
from repro.obs.profiler import annotate
from repro.obs.stats import finalize_stats
from repro.obs.trace import current_tracer


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # [T] int32
    max_new_tokens: int
    eos_token: Optional[int] = None
    out_tokens: list = field(default_factory=list)
    slot: Optional[int] = None
    prefill_done: int = 0               # prompt tokens already prefilled
    done: bool = False


class _SlotConflicts:
    """Recipe/record adapter for the scheduler: same-request tasks conflict
    (serial chain per request); distinct requests commute."""

    @staticmethod
    def conflicts(a, b, *, strict: bool = True):
        return a["rid"] == b["rid"]


class ServingEngine:
    def __init__(self, model, params, *, n_slots: int, max_len: int,
                 prefill_chunk: int = 64, greedy: bool = True):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.greedy = greedy

        self.states = model.init_states(n_slots, max_len)
        self.queue: list[Request] = []
        self.active: dict[int, Request] = {}      # slot -> request
        self.free_slots = list(range(n_slots))
        self.finished: list[Request] = []
        self.iterations = 0
        self.wave_sizes: list[int] = []
        self.prefill_tasks = 0
        self.decode_tasks = 0

        def _decode_step(params, last, states):
            with annotate("protocol.decode_wave"):
                return model.decode_step(params, last, states)

        self._decode = jax.jit(_decode_step)
        self._prefill_chunk_fns: dict[int, object] = {}

    # ------------------------------------------------------------ admit
    def submit(self, req: Request):
        self.queue.append(req)

    def _admit(self):
        while self.queue and self.free_slots:
            req = self.queue.pop(0)
            req.slot = self.free_slots.pop(0)
            # reset the slot's streaming state (previous occupant's KV ring
            # / SSM state / position counter must not leak)
            self._scatter_state(
                self.model.init_states(1, self.max_len), req.slot)
            self.active[req.slot] = req

    # -------------------------------------------------------- scheduling
    def _build_window(self):
        """One pending task per active request (its chain head), in request
        arrival order — the engine's view of the paper's chain."""
        recipes = []
        for slot, req in sorted(self.active.items(), key=lambda kv: kv[1].rid):
            if req.done:
                continue
            if req.prefill_done < len(req.prompt):
                recipes.append({"rid": req.rid, "kind": 0, "slot": slot})
            elif len(req.out_tokens) < req.max_new_tokens:
                recipes.append({"rid": req.rid, "kind": 1, "slot": slot})
        return recipes

    def _schedule_wave(self, recipes):
        """Run the paper's scheduler over the window; return wave-0 tasks.
        With one task per request the wave is the whole window — the
        machinery matters when chains interleave (tests exercise windows
        with multiple tasks per request)."""
        if not recipes:
            return []
        w = len(recipes)
        arr = {
            "rid": jnp.asarray([r["rid"] for r in recipes], jnp.int32),
        }
        valid = jnp.ones((w,), bool)
        conf = prefix_conflicts(_SlotConflicts.conflicts, arr, valid)
        levels = np.asarray(wave_levels(conf, valid))
        return [r for r, l in zip(recipes, levels) if l == 0]

    # -------------------------------------------------------- execution
    def _scatter_state(self, slot_states, slot: int):
        """Write a single-slot state pytree into the batched states."""

        def merge(path, big, small):
            if path == "pos" or path.endswith("enc_out"):
                return big.at[slot].set(small[0])
            # seg leaves: [Lseg, B, ...]
            return big.at[:, slot].set(small[:, 0])

        from repro.utils.pytree import tree_map_with_path_str

        flat_big, tdef = jax.tree_util.tree_flatten(self.states)
        # paths must match between big and small: map with path over big,
        # pulling the corresponding small leaf positionally
        small_leaves = jax.tree_util.tree_leaves(slot_states)
        paths = []

        def collect(path, leaf):
            paths.append(path)
            return leaf

        tree_map_with_path_str(collect, self.states)
        merged = [merge(p, b, s)
                  for p, b, s in zip(paths, flat_big, small_leaves)]
        self.states = jax.tree_util.tree_unflatten(tdef, merged)

    def _gather_state(self, slot: int):
        def take(path, big):
            if path == "pos" or path.endswith("enc_out"):
                return big[slot:slot + 1]
            return big[:, slot:slot + 1]

        from repro.utils.pytree import tree_map_with_path_str

        return tree_map_with_path_str(take, self.states)

    def _exec_prefill(self, task):
        req = self.active[task["slot"]]
        first = req.prefill_done == 0
        chunk = req.prompt[req.prefill_done:
                           req.prefill_done + self.prefill_chunk]
        t = len(chunk)
        slot_states = self._gather_state(task["slot"])
        batch = {"tokens": jnp.asarray(chunk, jnp.int32)[None]}
        key = (t, first)
        if key not in self._prefill_chunk_fns:
            import functools

            prefill = functools.partial(
                self.model.prefill, chunked=True, include_prefix=first)

            def _prefill_chunk(params, batch, states, _fn=prefill):
                with annotate("protocol.prefill_chunk"):
                    return _fn(params, batch, states)

            self._prefill_chunk_fns[key] = jax.jit(_prefill_chunk)
        logits, slot_states = self._prefill_chunk_fns[key](
            self.params, batch, slot_states)
        self._scatter_state(slot_states, task["slot"])
        req.prefill_done += t
        if req.prefill_done >= len(req.prompt):
            # prompt complete: the prefill's last logits seed decoding
            tok = int(np.asarray(jnp.argmax(logits[0])))
            self._append_token(req, tok)

    def _exec_decode_wave(self, tasks):
        slots = [t["slot"] for t in tasks]
        last = np.zeros((self.n_slots, 1), np.int32)
        for s in slots:
            last[s, 0] = self.active[s].out_tokens[-1]
        logits, new_states = self._decode(
            self.params, jnp.asarray(last), self.states)
        # commit only wave slots (masked merge = conflict-free wave write)
        mask = np.zeros((self.n_slots,), bool)
        for s in slots:
            mask[s] = True
        mask_j = jnp.asarray(mask)

        def merge(path, old, new):
            if path == "pos" or path.endswith("enc_out"):
                m = mask_j.reshape((-1,) + (1,) * (old.ndim - 1))
                return jnp.where(m, new, old)
            m = mask_j.reshape((1, -1) + (1,) * (old.ndim - 2))
            return jnp.where(m, new, old)

        from repro.utils.pytree import tree_map_with_path_str

        flat_old, tdef = jax.tree_util.tree_flatten(self.states)
        new_leaves = jax.tree_util.tree_leaves(new_states)
        paths = []

        def collect(path, leaf):
            paths.append(path)
            return leaf

        tree_map_with_path_str(collect, self.states)
        self.states = jax.tree_util.tree_unflatten(
            tdef, [merge(p, o, n)
                   for p, o, n in zip(paths, flat_old, new_leaves)])

        toks = np.asarray(jnp.argmax(logits, axis=-1))
        for s in slots:
            self._append_token(self.active[s], int(toks[s]))

    def _append_token(self, req: Request, tok: int):
        req.out_tokens.append(tok)
        if ((req.eos_token is not None and tok == req.eos_token)
                or len(req.out_tokens) >= req.max_new_tokens):
            req.done = True
            self.finished.append(req)
            self.free_slots.append(req.slot)
            del self.active[req.slot]

    # ------------------------------------------------------------- run
    def step(self) -> bool:
        """One protocol iteration. Returns False when fully idle.

        With a span tracer installed (``repro.obs.tracing()``) each
        iteration emits a ``repro.schedule`` span (admit + window build
        + wave-0 selection) and a ``repro.execute`` span (prefill chunks
        + the batched decode wave) — the same names the batch engines
        use, so serving traces render through ``report.py trace``. The
        spans never fence. The untraced path is guarded by one
        ``current_tracer()`` check."""
        tr = current_tracer()
        if tr is None:
            self._admit()
            wave = self._schedule_wave(self._build_window())
        else:
            with tr.span("repro.schedule", index=self.iterations):
                self._admit()
                wave = self._schedule_wave(self._build_window())
        if not wave:
            return bool(self.queue or self.active)
        self.wave_sizes.append(len(wave))
        prefills = [t for t in wave if t["kind"] == 0]
        decodes = [t for t in wave if t["kind"] == 1]
        if tr is None:
            self._exec_wave(prefills, decodes)
        else:
            with tr.span("repro.execute", index=self.iterations,
                         prefills=len(prefills), decodes=len(decodes),
                         wave=len(prefills) + len(decodes)):
                self._exec_wave(prefills, decodes)
        self.prefill_tasks += len(prefills)
        self.decode_tasks += len(decodes)
        self.iterations += 1
        return True

    def _exec_wave(self, prefills, decodes):
        for t in prefills:
            self._exec_prefill(t)
        if decodes:
            self._exec_decode_wave(decodes)

    def run(self, max_iterations: int = 100_000):
        tr = current_tracer()
        if tr is None:
            it = 0
            while self.step():
                it += 1
                if it > max_iterations:
                    raise RuntimeError("engine did not converge")
            return self.finished
        with tr.span("repro.run", engine="serving", window=self.n_slots,
                     total_tasks=0) as sp:
            it = 0
            while self.step():
                it += 1
                if it > max_iterations:
                    raise RuntimeError("engine did not converge")
            sp.args["total_tasks"] = self.prefill_tasks + self.decode_tasks
        return self.finished

    def run_stats(self) -> dict:
        """Engine-run statistics through the same typed registry boundary
        as every batch engine (``repro.obs.stats.finalize_stats``): the
        core keys map one iteration -> one window with one executed wave,
        plus the serving-group task/request counters."""
        waves = self.wave_sizes
        total = self.prefill_tasks + self.decode_tasks
        return finalize_stats({
            "total_tasks": total,
            "n_windows": self.iterations,
            "total_waves": len(waves),
            "mean_parallelism": total / max(len(waves), 1),
            "serving_prefill_tasks": self.prefill_tasks,
            "serving_decode_tasks": self.decode_tasks,
            "serving_requests_finished": len(self.finished),
        })
