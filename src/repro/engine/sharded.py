"""Multi-device wavefront engine: waves sharded over the agent axis.

One engine body serves a *ladder* of communication layouts, decided per
run from the model's row contracts and the schedule shape (most to least
specialized — each rung degrades to the next when it cannot win):

**Per-wave halo split** (``sharded``, the default top rung) — the paper's
protocol only pays off when per-wave work *and communication* stay
proportional to the localized update footprint. Wave levels are known at
schedule time, so the window's halo (the read ∪ write state rows of its
tasks) is split into per-wave slabs: wave w gathers only the rows of
tasks at level w. Slab widths are heavily skewed (level 0 usually holds
most of a window, tail waves a handful), so instead of a rectangular
[n_waves, max_slab] padding the slabs are laid out wave-major in
fixed-width *chunks* (``distributed.sharding.wave_halo_split``): wave w
owns a dynamic number of static-width chunk gathers, shipping
ceil(rows_w / chunk)·chunk ≈ rows_w rows. Summed over a window that is
≈ *one* window halo instead of n_waves of them — per-wave comm drops by
~n_waves vs the monolithic layout below. All shapes are static: the
layout builds inside the jitted executors on replicated values, no host
sync, no per-window recompilation.

**Window halo** (``sharded_window_halo``, the monolithic middle rung) —
the PR-3 layout: every state leaf leads with the agent axis and is
sharded into contiguous row blocks over a 1-D ``("agents",)`` mesh. At
schedule time (replicated, so no extra comm) the engine derives the
window's halo from the model's ``task_read_agents`` /
``task_write_agents`` contracts — degree-bounded, padded to the static
width W·(nr+nw) — and every wave, inside ``shard_map``:

  1. gathers the halo rows: each row has a unique owner shard; owners
     contribute, one ``psum`` over the agent axis delivers the rows
     everywhere — O(halo) values per device instead of the all_gather's
     O(N);
  2. scatters them into a full-size scratch buffer and refreshes the
     local row block from the authoritative local shard (a local copy,
     no comm) — every row an owned task can read is now current; rows
     outside halo ∪ local block stay stale zeros and are provably never
     read;
  3. restricts the wave mask to *owned* tasks (a task executes on every
     device whose row block contains one of its write targets) and runs
     the model's vectorized ``execute_wave`` on the scratch;
  4. keeps only the local row block of the result — writes land directly
     on their owners, so no write scatter is communicated at all.

The split executor replaces step 1-2 with the per-wave chunk loop; steps
3-4 are identical, so bit-exactness is untouched.

**Replicated all_gather** (``sharded_replicated``, the bottom rung) —
the historic layout: per wave, ``all_gather`` the state shards into the
full agent state and execute on that. Models that do not declare the
read/write row contracts route here automatically, as does any
monolithic run whose halo would not beat the full state (halo width
>= N; the split rung only needs a chunk narrower than the state).

**Cross-window overlap** (``overlap=True`` / ``sharded_overlap``): the
window boundary stops draining at a barrier — window k+1's head waves
execute fused with window k's tail (see ``WindowedEngine``). Per fused
wave the gather must deliver every row *either* window can touch. The
split rung handles this natively: the pair's rows and levels concatenate
and re-split into fused-wave slabs (rebuilt every boundary, because the
carry re-leveling moves tasks between waves), so fused waves still ship
only what they read. The monolithic rung falls back to the *pair halo* —
the union of both windows' read ∪ write rows
(``distributed.sharding.pair_halo``, static width 2·W·(nr+nw)) — and its
halo-vs-full-state decision uses that doubled width. Each fused wave
executes window k's owned tasks at that level, then window k+1's on the
same scratch — legal because the carry frontier guarantees a fused wave
never holds conflicting tasks.

Window-local objects (recipes, validity, conflict matrix, wave levels,
slab layouts) are O(W)/O(W²) and stay replicated in every mode;
scheduling runs once and its outputs broadcast to the mesh. All modes
are bit-exact vs the sequential oracle under the strict rule
(property-tested under 8 virtual devices), and report their comm volume
in ``run`` stats (``per_wave_comm_bytes`` actually shipped vs
``window_halo_bytes`` monolithic vs ``full_state_bytes``).

The ``WindowedEngine`` loop double-buffers windows: window t+1's schedule
is dispatched before the engine blocks on window t's waves.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (
    AGENT_AXIS as AXIS,
    agent_state_shardings,
    agents_mesh,
    halo_gather,
    halo_scatter,
    pair_halo,
    wave_halo_gather,
    wave_halo_split,
    window_halo,
)
from repro.engine.base import WindowedEngine, register_engine
from repro.obs.profiler import annotate


@register_engine
class ShardedEngine(WindowedEngine):
    name = "sharded"

    #: None = probe the model for the halo contracts; False = always
    #: replicate (the ``sharded_replicated`` registry entry).
    halo: bool | None = None

    #: per-wave halo splitting — the top rung of the comm ladder. None =
    #: on whenever the halo contracts are available; False pins the
    #: monolithic window/pair halo (the ``sharded_window_halo`` entry).
    split: bool | None = None

    def __init__(self, model, *, window: int = 256, strict: bool = True,
                 devices=None, jit: bool = True, halo: bool | None = None,
                 split: bool | None = None, chunk: int = 16,
                 overlap: bool | None = None):
        super().__init__(model, window=window, strict=strict,
                         overlap=overlap)
        self.mesh = agents_mesh(devices)
        self.n_devices = self.mesh.devices.size
        self._jit = jit
        self._built_for: int | None = None  # n_agents the fns were built for
        self._win_comm: list = []           # per-window comm ledger
        if halo is not None:
            self.halo = halo
        if split is not None:
            self.split = split
        #: slab chunk width (rows per collective) for the split rung —
        #: trades collective count (latency) against padding (bandwidth)
        self.chunk = int(chunk)
        assert self.chunk >= 1, "chunk must be a positive row count"
        self._halo_slots = 0
        if self.halo is None or self.halo:
            # one-shot host probe: the halo layout needs both row contracts
            probe = model.create_tasks(jax.random.key(0), 0, 1)
            reads = model.task_read_agents(probe)
            writes = model.task_write_agents(probe)
            if self.halo is None:
                self.halo = reads is not None and writes is not None
            elif reads is None or writes is None:
                raise ValueError(
                    f"halo=True needs {type(model).__name__} to implement "
                    "both task_read_agents and task_write_agents; use the "
                    "'sharded_replicated' engine (or halo=None auto-probe) "
                    "for models without the row contracts")
            if self.halo:
                self._halo_slots = reads.shape[-1] + writes.shape[-1]

        def _halo_parts(recipes):
            """(writes, monolithic halo, per-task rows) — the last two
            None without the row contracts."""
            writes = model.task_write_agents(recipes)
            if not self.halo:
                return writes, None, None
            reads = model.task_read_agents(recipes)
            return (writes, window_halo(reads, writes),
                    jnp.concatenate([reads, writes], axis=1))

        def _schedule(base_key, start, count):
            recipes, _, levels = self._schedule_window(base_key, start, count)
            return (recipes, levels) + _halo_parts(recipes)

        self._schedule = jax.jit(_schedule) if jit else _schedule

        def _schedule_ov(base_key, start, count):
            recipes, valid, conf = self._schedule_window_ov(
                base_key, start, count)
            return recipes, valid, conf, _halo_parts(recipes)

        self._schedule_ov = jax.jit(_schedule_ov) if jit else _schedule_ov

    # ------------------------------------------------------------ build
    def _build(self, n_agents: int):
        """Compile the sharded window executors for one agent count."""
        if self._built_for == n_agents:
            return
        model, d = self.model, self.n_devices
        n_pad = -(-n_agents // d) * d
        shard_n = n_pad // d
        halo_width = self.window * self._halo_slots
        # monolithic fallback-rung decisions: a degenerate halo (>= full
        # state) means replication ships fewer bytes. The barrier/drain
        # executor decides on the single-window width; monolithic fused
        # waves gather the union of both windows' halos, so the pair
        # executor decides on the doubled width independently (a window
        # size whose single halo wins can lose once doubled). The split
        # rung needs no such guard: it ships ~one halo per *window*, so
        # it only degrades when a single chunk cannot beat the state.
        use_halo = self.halo and halo_width < n_agents
        use_halo_pair = self.halo and 2 * halo_width < n_agents
        use_split = (self.halo and self.split is not False
                     and self.chunk < n_agents)

        def _pad(x):
            return jnp.pad(x, [(0, n_pad - n_agents)]
                           + [(0, 0)] * (x.ndim - 1))

        def read_view(loc, halo, local_rows, use):
            """Every row the wave's owned tasks may read, fresh —
            monolithic variant (whole halo, or the full state)."""
            if not use:
                return jax.tree_util.tree_map(
                    lambda x: jax.lax.all_gather(
                        x, AXIS, axis=0, tiled=True)[:n_agents], loc)

            def one(x):
                g = halo_gather(x, halo, shard_n=shard_n)
                scratch = jnp.zeros((n_agents,) + x.shape[1:], x.dtype)
                scratch = halo_scatter(scratch, halo, g)
                # local block is authoritative — refresh it so the
                # end-of-wave slice keeps unwritten rows exact
                return scratch.at[local_rows].set(x, mode="drop")
            return jax.tree_util.tree_map(one, loc)

        def slab_view(loc, slabs, chunk_start, w, local_rows):
            """Per-wave variant: refresh only wave w's slab chunks —
            a dynamic number of static-width gathers; an empty wave
            (zero chunks) issues no collective at all."""
            c1 = chunk_start[w + 1]

            def chunk_body(carry):
                c, scr = carry

                def one(x, s):
                    g, slab = wave_halo_gather(x, slabs, c, shard_n=shard_n)
                    return halo_scatter(s, slab, g)
                return c + 1, jax.tree_util.tree_map(one, loc, scr)

            scratch = jax.tree_util.tree_map(
                lambda x: jnp.zeros((n_agents,) + x.shape[1:], x.dtype), loc)
            _, scratch = jax.lax.while_loop(
                lambda c: c[0] < c1, chunk_body,
                (chunk_start[w], scratch))
            return jax.tree_util.tree_map(
                lambda x, s: s.at[local_rows].set(x, mode="drop"),
                loc, scratch)

        def owned_mask(levels, write_agents, w, lo):
            mask = levels == w
            if write_agents is not None:
                owned = jnp.any(
                    (write_agents >= lo) & (write_agents < lo + shard_n),
                    axis=-1)
                mask = mask & owned
            return mask

        def keep_local(new, lo):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_slice_in_dim(
                    _pad(x), lo, shard_n, axis=0), new)

        def window_local(local_state, recipes, levels, write_agents, halo):
            # runs per-device inside shard_map; local leaves are [N/d, ...]
            lo = jax.lax.axis_index(AXIS) * shard_n
            local_rows = lo + jnp.arange(shard_n)
            n_waves = jnp.max(levels) + 1

            def body(carry):
                w, loc = carry
                full = read_view(loc, halo, local_rows, use_halo)
                new = model.execute_wave(
                    full, recipes, owned_mask(levels, write_agents, w, lo))
                return w + 1, keep_local(new, lo)

            _, local_state = jax.lax.while_loop(
                lambda c: c[0] < n_waves, body,
                (jnp.int32(0), local_state))
            return local_state, n_waves

        def window_split_local(local_state, recipes, levels, write_agents,
                               slabs, chunk_start):
            lo = jax.lax.axis_index(AXIS) * shard_n
            local_rows = lo + jnp.arange(shard_n)
            n_waves = jnp.max(levels) + 1

            def body(carry):
                w, loc = carry
                full = slab_view(loc, slabs, chunk_start, w, local_rows)
                new = model.execute_wave(
                    full, recipes, owned_mask(levels, write_agents, w, lo))
                return w + 1, keep_local(new, lo)

            _, local_state = jax.lax.while_loop(
                lambda c: c[0] < n_waves, body,
                (jnp.int32(0), local_state))
            return local_state, n_waves

        def window_pair_local(local_state, rec_a, lv_a, wa_a,
                              rec_b, lv_b, wa_b, halo):
            # fused drain of window k (a) overlapped with window k+1 (b);
            # halo is the pair union, so one gather serves both windows
            lo = jax.lax.axis_index(AXIS) * shard_n
            local_rows = lo + jnp.arange(shard_n)
            n_waves = jnp.max(lv_a) + 1

            def body(carry):
                w, loc = carry
                full = read_view(loc, halo, local_rows, use_halo_pair)
                new = model.execute_wave(
                    full, rec_a, owned_mask(lv_a, wa_a, w, lo))
                # b's reads never overlap a's same-wave writes (the carry
                # frontier forbids conflicts inside a fused wave), so
                # executing b on a's output scratch is exact
                new = model.execute_wave(
                    new, rec_b, owned_mask(lv_b, wa_b, w, lo))
                return w + 1, keep_local(new, lo)

            _, local_state = jax.lax.while_loop(
                lambda c: c[0] < n_waves, body,
                (jnp.int32(0), local_state))
            return local_state, n_waves

        def window_pair_split_local(local_state, rec_a, lv_a, wa_a,
                                    rec_b, lv_b, wa_b, slabs, chunk_start):
            # fused drain on the split rung: slabs hold the per-fused-wave
            # union of both windows' rows, so one chunk loop serves both
            lo = jax.lax.axis_index(AXIS) * shard_n
            local_rows = lo + jnp.arange(shard_n)
            n_waves = jnp.max(lv_a) + 1

            def body(carry):
                w, loc = carry
                full = slab_view(loc, slabs, chunk_start, w, local_rows)
                new = model.execute_wave(
                    full, rec_a, owned_mask(lv_a, wa_a, w, lo))
                new = model.execute_wave(
                    new, rec_b, owned_mask(lv_b, wa_b, w, lo))
                return w + 1, keep_local(new, lo)

            _, local_state = jax.lax.while_loop(
                lambda c: c[0] < n_waves, body,
                (jnp.int32(0), local_state))
            return local_state, n_waves

        window_sharded = jax.shard_map(
            window_local, mesh=self.mesh,
            in_specs=(P(AXIS), P(), P(), P(), P()),
            out_specs=(P(AXIS), P()),
            check_vma=False)

        window_split_sharded = jax.shard_map(
            window_split_local, mesh=self.mesh,
            in_specs=(P(AXIS), P(), P(), P(), P(), P()),
            out_specs=(P(AXIS), P()),
            check_vma=False)

        window_pair_sharded = jax.shard_map(
            window_pair_local, mesh=self.mesh,
            in_specs=(P(AXIS), P(), P(), P(), P(), P(), P(), P()),
            out_specs=(P(AXIS), P()),
            check_vma=False)

        window_pair_split_sharded = jax.shard_map(
            window_pair_split_local, mesh=self.mesh,
            in_specs=(P(AXIS), P(), P(), P(), P(), P(), P(), P(), P()),
            out_specs=(P(AXIS), P()),
            check_vma=False)

        chunk, n_waves_max = self.chunk, self.window

        def _exec_mono(state, recipes, levels, write_agents, halo):
            with annotate("protocol.execute_window"):
                return window_sharded(state, recipes, levels, write_agents,
                                      halo)

        def _exec_split(state, recipes, levels, write_agents, rows):
            slabs, chunk_start = wave_halo_split(
                rows, levels, n_waves_max=n_waves_max, chunk=chunk)
            with annotate("protocol.execute_window"):
                state, n_waves = window_split_sharded(
                    state, recipes, levels, write_agents, slabs, chunk_start)
            # rows actually gathered this window (every executed wave's
            # chunk range) — the comm ledger entry for the stats
            shipped = chunk_start[n_waves] * chunk
            return state, n_waves, shipped

        def _exec_pair_mono(state, rec_a, lv_a, wa_a, rec_b, lv_b, wa_b,
                            halo):
            with annotate("protocol.execute_pair"):
                state, n_waves = window_pair_sharded(
                    state, rec_a, lv_a, wa_a, rec_b, lv_b, wa_b, halo)
            # rebase the next window onto the new level clock; executed
            # (and invalid) tasks drop to -1
            lv_b = jnp.where(lv_b >= n_waves, lv_b - n_waves, -1)
            return state, n_waves, lv_b

        def _exec_pair_split(state, rec_a, lv_a, wa_a, rec_b, lv_b, wa_b,
                             rows_a, rows_b):
            # re-split at every boundary: the carry re-leveling moves
            # window b's tasks between fused waves, and rebasing retires
            # window a's drained tasks (level -1 rows drop from the slabs)
            rows = jnp.concatenate([rows_a, rows_b], axis=0)
            lvs = jnp.concatenate([lv_a, lv_b])
            slabs, chunk_start = wave_halo_split(
                rows, lvs, n_waves_max=n_waves_max, chunk=chunk)
            with annotate("protocol.execute_pair"):
                state, n_waves = window_pair_split_sharded(
                    state, rec_a, lv_a, wa_a, rec_b, lv_b, wa_b,
                    slabs, chunk_start)
            lv_b = jnp.where(lv_b >= n_waves, lv_b - n_waves, -1)
            shipped = chunk_start[n_waves] * chunk
            return state, n_waves, lv_b, shipped

        if self._jit:
            _exec_mono = jax.jit(_exec_mono, donate_argnums=(0,))
            _exec_split = jax.jit(_exec_split, donate_argnums=(0,))
            _exec_pair_mono = jax.jit(_exec_pair_mono, donate_argnums=(0,))
            _exec_pair_split = jax.jit(_exec_pair_split, donate_argnums=(0,))

        dummy_halo = jnp.full((1,), -1, jnp.int32)
        # jit-boundary hooks for the compiled-cost telemetry
        # (repro.obs.costs): which executor a barrier run dispatches to,
        # and the rung decisions that pick it
        self._jit_execs = {"mono": _exec_mono, "split": _exec_split,
                           "pair_mono": _exec_pair_mono,
                           "pair_split": _exec_pair_split}
        self._use_halo = use_halo
        self._use_halo_pair = use_halo_pair
        self._use_split = use_split
        self._dummy_halo = dummy_halo

        def _execute(state, sched):
            recipes, levels, write_agents, halo, rows = sched
            if use_split and rows is not None:
                state, n_waves, shipped = _exec_split(
                    state, recipes, levels, write_agents, rows)
                self._win_comm.append(("split", shipped, n_waves))
                return state, n_waves
            state, n_waves = _exec_mono(
                state, recipes, levels, write_agents,
                halo if halo is not None else dummy_halo)
            self._win_comm.append(
                ("halo", halo_width, n_waves) if use_halo
                else ("full", n_pad, n_waves))
            return state, n_waves

        def _execute_pair(state, cur, lv_a, nxt, lv_b):
            rec_a, _, _, (wa_a, halo_a, rows_a) = cur
            rec_b, _, _, (wa_b, halo_b, rows_b) = nxt
            if use_split and rows_a is not None:
                state, n_waves, lv_b, shipped = _exec_pair_split(
                    state, rec_a, lv_a, wa_a, rec_b, lv_b, wa_b,
                    rows_a, rows_b)
                self._win_comm.append(("split", shipped, n_waves))
                return state, n_waves, lv_b
            halo = (pair_halo(halo_a, halo_b) if halo_a is not None
                    else dummy_halo)
            state, n_waves, lv_b = _exec_pair_mono(
                state, rec_a, lv_a, wa_a, rec_b, lv_b, wa_b, halo)
            self._win_comm.append(
                ("pair", 2 * halo_width, n_waves) if use_halo_pair
                else ("full", n_pad, n_waves))
            return state, n_waves, lv_b

        def _execute_drain(state, cur, lv):
            # partnerless drain (last / only window): the barrier
            # dispatcher re-splits by the current (possibly rebased)
            # levels — drained tasks carry level -1 and gather nothing
            wa, halo_idx, rows = cur[3]
            return _execute(state, (cur[0], lv, wa, halo_idx, rows))

        self._execute = _execute
        self._execute_pair = _execute_pair
        self._execute_drain = _execute_drain
        self._n_agents, self._n_pad = n_agents, n_pad
        # layout facts the tracer's per-wave comm attribution reads
        # (repro/obs — only touched when a tracer is installed)
        self._shard_n = shard_n
        self._halo_width = halo_width
        # the monolithic per-wave reference the split is measured against
        # (the mode that dominates the run: pair width for overlapped
        # runs — the final drain ships the single-window halo, slightly
        # less than reported — plain window halo otherwise; padded N
        # when the monolithic ladder itself would replicate)
        if self.overlap:
            self._gather_rows = 2 * halo_width if use_halo_pair else n_pad
        else:
            self._gather_rows = halo_width if use_halo else n_pad
        self._built_for = n_agents

    def _make_boundary(self):
        """The boundary step runs replicated on every mesh device: from
        the second boundary on, the tail's rebased levels come out of the
        mesh-wide pair executor, and a Mosaic kernel under a multi-device
        jit must sit inside a shard_map (it cannot be auto-partitioned)."""
        return jax.jit(jax.shard_map(
            self._boundary_step(), mesh=self.mesh, in_specs=P(),
            out_specs=P(), check_vma=False))

    # ------------------------------------------------------- state hooks
    def _prepare_state(self, state):
        leaves = jax.tree_util.tree_leaves(state)
        assert leaves, "empty state"
        n = leaves[0].shape[0]
        assert all(x.shape[0] == n for x in leaves), (
            "sharded engine expects every state leaf to lead with the "
            f"agent axis; got shapes {[x.shape for x in leaves]}")
        self._build(n)
        n_pad = self._n_pad
        # per-agent-row bytes across leaves -> comm accounting for stats
        self._row_bytes = sum(
            x.dtype.itemsize * int(x.size) // n for x in leaves)
        self._full_bytes = n_pad * self._row_bytes
        self._win_comm = []
        padded = jax.tree_util.tree_map(
            lambda x: jnp.pad(x, [(0, n_pad - n)] + [(0, 0)] * (x.ndim - 1)),
            state)
        return jax.device_put(padded, agent_state_shardings(padded, self.mesh))

    def _finalize_state(self, state):
        return jax.tree_util.tree_map(
            lambda x: x[:self._n_agents], state)

    def _extend_stats(self, stats: dict) -> dict:
        stats["n_devices"] = self.n_devices
        # the comm ledger holds one entry per executed window / fused
        # drain: "split" entries carry the window's total shipped rows
        # (the chunk ranges of its executed waves), monolithic entries
        # the static per-wave width. Converting the wave counts here is
        # the run's existing final host sync — nothing new blocks.
        ledger = [(kind, int(r), int(w)) for kind, r, w in self._win_comm]
        total_rows = sum(r if kind == "split" else r * w
                        for kind, r, w in ledger)
        waves = max(int(stats["total_waves"]), 1)
        rb = self._row_bytes
        mean_rows = total_rows / waves
        split_used = any(kind == "split" for kind, _, _ in ledger)
        stats["halo"] = any(kind in ("split", "halo", "pair")
                            for kind, _, _ in ledger)
        stats["halo_split"] = split_used
        # per-window layout composition — e.g. an overlapped run whose
        # pair halo tripped the width guard still drains its final
        # window through the single-window halo: {"full": 4, "halo": 1}
        modes: dict = {}
        for kind, _, _ in ledger:
            modes[kind] = modes.get(kind, 0) + 1
        stats["comm_modes"] = modes
        # rows/bytes actually delivered to each device per wave (mean
        # over executed waves — the split rung varies per wave), plus the
        # monolithic window/pair-halo reference it is measured against
        stats["per_wave_gather_rows"] = int(round(mean_rows))
        stats["per_wave_comm_bytes"] = int(round(mean_rows * rb))
        stats["full_state_bytes"] = int(self._full_bytes)
        stats["comm_bytes_total"] = int(total_rows * rb)
        stats["per_wave_split_rows"] = (round(mean_rows, 2) if split_used
                                        else None)
        if self.halo:
            stats["window_halo_rows"] = int(self._gather_rows)
            stats["window_halo_bytes"] = int(self._gather_rows * rb)
            stats["comm_reduction_vs_window_halo"] = (
                round(stats["window_halo_bytes"]
                      / stats["per_wave_comm_bytes"], 2)
                if stats["per_wave_comm_bytes"] else None)
        else:
            stats["window_halo_rows"] = None
            stats["window_halo_bytes"] = None
            stats["comm_reduction_vs_window_halo"] = None
        return stats

    # ------------------------------------------------------ compiled costs
    def _cost_targets(self, base_key, state):
        if not self._jit:
            return None
        recipes, levels, write_agents, halo, rows = self._schedule(
            base_key, 0, self.window)
        if self._use_split and rows is not None:
            return [("execute_split", self._jit_execs["split"],
                     (state, recipes, levels, write_agents, rows))]
        h = halo if halo is not None else self._dummy_halo
        return [("execute_window", self._jit_execs["mono"],
                 (state, recipes, levels, write_agents, h))]

    def comm_iteration_counts(self, stats: dict) -> dict[int, int]:
        """Executed dynamic-loop iterations per nesting depth, from the
        runtime comm ledger of the run that produced ``stats``: depth 1
        is the wave loop (total executed waves), depth 2 the split rung's
        chunk loop nested inside it (total chunk gathers = shipped rows /
        chunk). This is the resolution map for the HLO collectives
        ``compiled_costs`` parses (their per-iteration bytes × these
        counts must reproduce ``comm_bytes_total`` — the cross-check)."""
        chunk_iters = sum(int(r) // self.chunk
                          for kind, r, _ in self._win_comm
                          if kind == "split")
        return {1: int(stats["total_waves"]), 2: chunk_iters}

    # ------------------------------------------------------------ tracing
    # Reached only with a tracer installed (repro.obs). The ledger entry
    # the window's executor appended names the rung; after the run, the
    # schedule's replicated level/row/write-target arrays reproduce the
    # per-wave shipped volume on the host (the split math below mirrors
    # ``wave_halo_split``: valid row slots per wave, ceil'd to chunks).

    _RUNG_NAMES = {"split": "split", "halo": "window_halo",
                   "pair": "pair_halo", "full": "full_state"}

    def _trace_parts(self, sched, levels=None):
        if levels is None:
            _, lv, wa, _, rows = sched          # barrier schedule
        else:
            lv = levels                          # overlapped: re-leveled
            wa, _, rows = sched[3]
        return lv, wa, rows

    def _trace_comm(self):
        return self._win_comm[-1] if self._win_comm else None

    def _stats_syncs(self) -> int:
        # the split entries' shipped rows; every entry's wave count is
        # the run's own, already read
        return sum(isinstance(r, jax.Array) for _, r, _ in self._win_comm)

    def _trace_wave_comm(self, np_parts, n_waves, entry):
        import numpy as np

        if entry is None:
            return {}
        kind = entry[0]
        if kind == "split":
            per_wave = np.zeros(n_waves, np.int64)
            for lv, _, rows in np_parts:
                if rows is None:
                    continue
                ok = (lv >= 0) & (lv < n_waves)
                np.add.at(per_wave, lv[ok], (rows[ok] >= 0).sum(axis=1))
            per_wave = -(-per_wave // self.chunk) * self.chunk
        else:
            width = {"halo": self._halo_width,
                     "pair": 2 * self._halo_width,
                     "full": self._n_pad}[kind]
            per_wave = np.full(n_waves, width, np.int64)
        # per-device owned-task counts (a task runs on every device whose
        # row block holds one of its write targets) -> load imbalance
        owned = np.zeros((n_waves, self.n_devices), np.int64)
        for lv, wa, _ in np_parts:
            if wa is None:
                continue
            dev = np.where(wa >= 0, wa // self._shard_n, -1)
            for i in np.nonzero((lv >= 0) & (lv < n_waves))[0]:
                devs = np.unique(dev[i])
                owned[lv[i], devs[devs >= 0]] += 1
        return {"rung": self._RUNG_NAMES[kind], "n_devices": self.n_devices,
                "rows": per_wave.tolist(),
                "bytes": (per_wave * self._row_bytes).tolist(),
                "owned": owned.tolist()}


@register_engine
class ShardedWindowHaloEngine(ShardedEngine):
    """The monolithic window/pair-halo layout (the PR-3/4 behavior): the
    whole halo row list is gathered every wave. Kept as the registered
    middle rung of the comm ladder — and as the baseline the per-wave
    split's comm stats (``comm_reduction_vs_window_halo``) are measured
    against."""

    name = "sharded_window_halo"
    split = False


@register_engine
class ShardedReplicatedEngine(ShardedEngine):
    """The historic full-state layout, kept as an explicit registry
    fallback (and as the measurement baseline the halo engines' comm
    stats are compared against)."""

    name = "sharded_replicated"
    halo = False


@register_engine
class ShardedOverlapEngine(ShardedEngine):
    """``sharded`` with cross-window overlap on by default: fused tail/
    head waves with per-fused-wave slab gathers (pair-halo gather on the
    monolithic rung). The plain ``sharded`` engine stays the registered
    barrier fallback."""

    name = "sharded_overlap"
    default_overlap = True
