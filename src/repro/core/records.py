"""Worker-record machinery, vectorized.

In the paper, each worker carries a *record*: an accumulator over the recipes
of the tasks it has skipped, answering "does the task at hand depend on any
task I have passed?". On SPMD hardware the equivalent object is the
*prefix-conflict matrix* over a window of W tasks:

    C[i, j] = 1  iff  j < i  and  task_i conflicts with task_j

Row i of C is exactly the record a worker would have accumulated after
skipping tasks j<i — materialized for all workers/positions at once. The
matrix is the protocol's O(W²) overhead term; the Pallas kernel in
kernels/conflict implements the id-matching variant with 128×128 tiling.

The same record algebra extends across the window boundary: the
rectangular block ``cross_window_conflicts`` is the check a worker's
record would perform against the *previous* window's undrained tail,
``carry_frontier`` reduces it to a per-task release level, and
``wave_levels(base=...)`` schedules the next window under that floor —
the machinery behind the engines' cross-window overlap (record
carry-over, docs/engine.md).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def prefix_conflicts(
    conflict_fn: Callable,
    recipes,
    valid: jax.Array,
    *,
    strict: bool = True,
) -> jax.Array:
    """Build the strictly-lower-triangular conflict matrix.

    conflict_fn(a, b, strict=...) is the model's pairwise predicate
    (later a vs earlier b). recipes is a pytree with leading dim W;
    valid is a [W] bool mask for padded windows.
    Returns C [W, W] bool with C[i, j] == later-task-i-conflicts-with-j,
    zero outside j < i or where either task is invalid.
    """
    from repro.obs.profiler import annotate

    w = valid.shape[0]

    with annotate("protocol.conflict_predicate"):
        # Broadcast: rows = later task i, cols = earlier task j.
        rows = jax.tree_util.tree_map(lambda x: x[:, None], recipes)
        cols = jax.tree_util.tree_map(lambda x: x[None, :], recipes)
        conf = conflict_fn(rows, cols, strict=strict)  # [W, W] broadcast

        lower = jnp.tril(jnp.ones((w, w), dtype=bool), k=-1)
        return conf & lower & valid[:, None] & valid[None, :]


def window_conflicts(model, recipes, valid: jax.Array, *,
                     strict: bool = True,
                     backend: str | None = None) -> jax.Array:
    """Model-agnostic conflict matrix for one window.

    Footprint models (task_footprint != None) route through the conflict
    kernel — tiled Pallas on TPU, a fused jnp elementwise pass elsewhere
    (kernels/conflict/ops.py). Predicate-only models fall back to the
    broadcast ``prefix_conflicts`` path. Both produce the identical
    strictly-lower-triangular [W, W] bool matrix.
    """
    fp = model.task_footprint(recipes)
    if fp is not None:
        from repro.kernels.conflict.ops import conflict_matrix

        read_ids, write_ids = fp
        return conflict_matrix(read_ids, write_ids, valid, strict=strict,
                               backend=backend)
    return prefix_conflicts(model.conflicts, recipes, valid, strict=strict)


def cross_window_conflicts(model, recipes_prev, valid_prev,
                           recipes_next, valid_next, *,
                           strict: bool = True,
                           backend: str | None = None) -> jax.Array:
    """Cross-window conflict block [W_next, W_prev] (bool).

    Row i = task i of the *later* window (k+1), column j = task j of the
    *earlier* window (k): C[i, j] == 1 iff next-task-i conflicts with
    prev-task-j. In chain order every prev task precedes every next task,
    so the block is a full rectangle — no triangular mask, only validity.
    ``valid_prev`` doubles as the *alive* mask of window k's not-yet-
    drained tail: columns of already-executed tasks are masked out (they
    impose no ordering constraint on the next window).

    Footprint models route through the rectangular-tile conflict kernel
    (Pallas on TPU, fused jnp elsewhere — kernels/conflict/ops.py);
    predicate-only models fall back to the broadcast pairwise predicate.
    This is the record carry-over of the overlapped engines: the check a
    worker's record would perform against tasks of the previous window.
    """
    fp_next = model.task_footprint(recipes_next)
    if fp_next is not None:
        from repro.kernels.conflict.ops import conflict_block

        reads_n, writes_n = fp_next
        reads_p, writes_p = model.task_footprint(recipes_prev)
        return conflict_block(reads_n, writes_n, reads_p, writes_p,
                              valid_next, valid_prev, strict=strict,
                              backend=backend)
    rows = jax.tree_util.tree_map(lambda x: x[:, None], recipes_next)
    cols = jax.tree_util.tree_map(lambda x: x[None, :], recipes_prev)
    conf = model.conflicts(rows, cols, strict=strict)
    return conf & valid_next[:, None] & valid_prev[None, :]


def carry_frontier(cross: jax.Array, levels_prev: jax.Array) -> jax.Array:
    """Per-task level floor imposed by the previous window's tail.

        carry[i] = max{ levels_prev[j] + 1 : cross[i, j] }   (else 0)

    ``levels_prev`` holds the previous window's *remaining* wave levels
    on the current level clock (-1 = already drained or padded), so a
    drained task contributes ``-1 + 1 = 0`` — no constraint. The result
    is the carry-over frontier: feeding it to ``wave_levels(base=...)``
    pins every next-window task strictly after the tail waves it
    conflicts with, which is exactly the cross-window record guarantee.
    """
    from repro.obs.profiler import annotate

    with annotate("protocol.carry_frontier"):
        gated = jnp.where(cross, levels_prev[None, :] + 1, 0)
        return jnp.max(gated, axis=1, initial=0).astype(jnp.int32)


def wave_levels(conflicts: jax.Array, valid: jax.Array, *,
                base: jax.Array | None = None,
                backend: str | None = None) -> jax.Array:
    """DAG-level (wavefront) assignment.

        level[i] = max(base[i], 1 + max{ level[j] : j < i, C[i, j] })

    This is list scheduling with unbounded workers: tasks in the same level
    commute pairwise *within the window prefix semantics* — a task only
    enters level L if every earlier conflicting task sits at a level < L.
    ``base`` (optional, non-negative) is the cross-window carry frontier:
    a per-task release level below which the task may not be scheduled
    (default: no floor — the classic recurrence, level 0 for tasks with
    no earlier conflicts). Invalid (padded) slots get level -1.

    Sequential-equivalence argument: executing levels in ascending order is
    a topological order of the (strict) dependence DAG restricted to the
    window, and commuting tasks may be reordered freely (paper §3.2).

    Implementation lives in kernels/levels — the blocked Pallas kernel on
    TPU, the reference ``lax.scan`` elsewhere (chosen at trace time, like
    the conflict kernel).
    """
    from repro.kernels.levels.ops import wave_levels as _wave_levels

    return _wave_levels(conflicts, valid, base=base, backend=backend)


def wave_levels_capped(conflicts, valid, n_workers: int):
    """Finite-n list scheduling (NumPy, host-side): like wave_levels but each
    wave holds at most n_workers tasks; a task is placed in the earliest
    wave >= its dependence level that has spare capacity, scanning in chain
    order — this models n paper-workers with an ideal (zero-overhead)
    workflow and is used by the DES and the benchmarks."""
    import numpy as np

    conflicts = np.asarray(conflicts)
    valid = np.asarray(valid)
    w = conflicts.shape[0]
    levels = np.full(w, -1, dtype=np.int64)
    counts: dict[int, int] = {}
    for i in range(w):
        if not valid[i]:
            continue
        deps = np.nonzero(conflicts[i])[0]
        base = 0 if deps.size == 0 else int(levels[deps].max()) + 1
        lvl = base
        while counts.get(lvl, 0) >= n_workers:
            lvl += 1
        levels[i] = lvl
        counts[lvl] = counts.get(lvl, 0) + 1
    return levels


def critical_path_length(conflicts, valid) -> int:
    """Longest dependence chain in the window (= #waves with n=inf)."""
    lv = wave_levels(jnp.asarray(conflicts), jnp.asarray(valid))
    return int(jnp.max(lv) + 1)
