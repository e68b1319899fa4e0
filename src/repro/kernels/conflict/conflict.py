"""Prefix-conflict matrix Pallas kernel.

The protocol's record check is the O(W²) hot spot of scheduling: for every
pair (i, j<i) of tasks in the window, decide whether task i's id-footprint
intersects task j's write set. On TPU this is a perfectly regular integer
compare over a [W, W] tile grid — VPU work with no MXU involvement, tiled
128×128 so each block's operands live in VMEM:

  per (bi, bj) tile:
    rows: read_ids[bi·B : , :nr], write_ids[bi·B : , :nw]   (task i side)
    cols: read_ids[bj·B : , :nr], write_ids[bj·B : , :nw]   (task j side)
    out:  conflict int32 block [B, B]

The matrix is strictly lower-triangular, so tiles strictly above the block
diagonal (bj > bi) are identically zero. The grid is therefore a 1-D walk
over the n(n+1)/2 tiles with bj <= bi (n = W/B tile rows), with the
(bi, bj) coordinates of each step delivered through scalar-prefetch lookup
tables — instead of the dense n² grid, a 2× tile-count reduction at large
W (e.g. W=1024, B=128: 36 tiles instead of 64; W=4096: 528 instead of
1024). The never-visited upper tiles hold uninitialized memory and are
zeroed by one fused elementwise mask after the kernel (the in-kernel
global-index mask still handles the diagonal tiles' upper halves and the
padded tail, so visited tiles come out exactly as the dense grid produced
them — bit-identical by construction and by test).

Hazard semantics (shared repo-wide; see core/model.py):

  strict=False — the paper's record rule: the record accumulates the write
      sets of skipped tasks and tests them against the task at hand's READ
      set, i.e. flow (RAW) hazards:      W_j ∩ R_i ≠ ∅.
      (For models whose write ids also appear among their read ids — e.g.
      Axelrod, where the target's traits are read to compute the overlap —
      this equals the paper's flow+output statement exactly.)
  strict=True  — full dependence closure: adds output (WAW) W_j ∩ W_i and
      anti (WAR) W_i ∩ R_j hazards; the only rule that is bit-exact vs
      sequential execution.

Windows that are not a multiple of the tile size are padded up with -1 ids
and invalid slots (masked in-kernel via w_total), then sliced back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128


def _hazard_tile(nr_i: int, nw_i: int, nr_j: int, nw_j: int, strict: bool,
                 reads_i, writes_i, reads_j, writes_j, bi, bj):
    """Shared hazard algebra for one [Bi, Bj] tile (rows = later task i,
    cols = earlier task j): flow W_j ∩ R_i, plus output W_j ∩ W_i and
    anti W_i ∩ R_j under the strict closure. Pure VPU integer compares;
    used by both the triangular prefix kernel and the rectangular
    cross-window block kernel."""
    conf = jnp.zeros((bi, bj), dtype=jnp.bool_)

    # flow (RAW): write_j ∈ reads_i
    for a in range(nw_j):
        wj = writes_j[:, a][None, :]          # [1, Bj] earlier-task writes
        uj = wj >= 0
        for c in range(nr_i):
            ri = reads_i[:, c][:, None]       # [Bi, 1]
            conf |= (ri == wj) & uj & (ri >= 0)
        if strict:
            # output (WAW): write_j ∈ writes_i
            for c in range(nw_i):
                wi = writes_i[:, c][:, None]
                conf |= (wi == wj) & uj & (wi >= 0)

    if strict:
        # anti (WAR): write_i ∈ reads_j
        for a in range(nw_i):
            wi = writes_i[:, a][:, None]      # [Bi, 1]
            ui = wi >= 0
            for c in range(nr_j):
                rj = reads_j[:, c][None, :]   # [1, Bj]
                conf |= (wi == rj) & ui & (rj >= 0)
    return conf


def _kernel(nr: int, nw: int, strict: bool, w_total: int,
            bi_ref, bj_ref,
            reads_i, writes_i, reads_j, writes_j, valid_i, valid_j, out_ref):
    t = pl.program_id(0)
    bi = bi_ref[t]
    bj = bj_ref[t]
    b = out_ref.shape[0]

    gi = bi * b + jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)  # global i
    gj = bj * b + jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)  # global j

    conf = _hazard_tile(nr, nw, nr, nw, strict,
                        reads_i, writes_i, reads_j, writes_j, b, b)

    mask = (gj < gi) & (gi < w_total) & (gj < w_total)
    mask &= (valid_i[:, :1] != 0) & (valid_j[:, :1].T != 0)
    out_ref[...] = (conf & mask).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("strict", "interpret", "block"))
def conflict_matrix_pallas(read_ids, write_ids, valid, *, strict: bool = True,
                           interpret: bool | None = None, block: int = BLOCK):
    """read_ids [W, nr] int32, write_ids [W, nw] int32 (−1 = unused slot),
    valid [W] bool. Returns [W, W] int32 prefix-conflict matrix.

    interpret=None picks by the platform the call is traced for: compiled
    on TPU, the Pallas interpreter elsewhere. Any window size is accepted;
    non-multiples of the tile size are padded to the next tile boundary
    internally.
    """
    if interpret is None:
        from repro.kernels import interpret_default

        interpret = interpret_default()
    w, nr = read_ids.shape
    nw = write_ids.shape[1]
    b = min(block, w)
    w_pad = -(-w // b) * b  # next multiple of the tile size
    if w_pad != w:
        pad = ((0, w_pad - w), (0, 0))
        read_ids = jnp.pad(read_ids, pad, constant_values=-1)
        write_ids = jnp.pad(write_ids, pad, constant_values=-1)
        valid = jnp.pad(valid, (0, w_pad - w), constant_values=False)
    n = w_pad // b
    # 1-D triangular tile walk (bj <= bi), coordinates via scalar prefetch
    bi_map, bj_map = (np.asarray(x, np.int32) for x in zip(
        *[(bi, bj) for bi in range(n) for bj in range(bi + 1)]))
    valid_i32 = valid.astype(jnp.int32)[:, None]  # [W, 1] for clean tiling

    row_spec = pl.BlockSpec((b, nr), lambda t, bi, bj: (bi[t], 0))
    col_spec = pl.BlockSpec((b, nr), lambda t, bi, bj: (bj[t], 0))
    roww_spec = pl.BlockSpec((b, nw), lambda t, bi, bj: (bi[t], 0))
    colw_spec = pl.BlockSpec((b, nw), lambda t, bi, bj: (bj[t], 0))
    vrow_spec = pl.BlockSpec((b, 1), lambda t, bi, bj: (bi[t], 0))
    vcol_spec = pl.BlockSpec((b, 1), lambda t, bi, bj: (bj[t], 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(len(bi_map),),
        in_specs=[row_spec, roww_spec, col_spec, colw_spec,
                  vrow_spec, vcol_spec],
        out_specs=pl.BlockSpec((b, b), lambda t, bi, bj: (bi[t], bj[t])),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, nr, nw, strict, w),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((w_pad, w_pad), jnp.int32),
        interpret=interpret,
    )(jnp.asarray(bi_map), jnp.asarray(bj_map),
      read_ids, write_ids, read_ids, write_ids, valid_i32, valid_i32)
    # zero the never-visited tiles strictly above the block diagonal
    lower = jnp.tril(jnp.ones((w_pad, w_pad), dtype=bool), k=-1)
    return jnp.where(lower, out, 0)[:w, :w]


def _block_kernel(nr_i: int, nw_i: int, nr_j: int, nw_j: int, strict: bool,
                  wi_total: int, wj_total: int,
                  reads_i, writes_i, reads_j, writes_j,
                  valid_i, valid_j, out_ref):
    bi, bj = out_ref.shape

    gi = (pl.program_id(0) * bi
          + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 0))
    gj = (pl.program_id(1) * bj
          + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1))

    conf = _hazard_tile(nr_i, nw_i, nr_j, nw_j, strict,
                        reads_i, writes_i, reads_j, writes_j, bi, bj)

    # full rectangle: every j-side task precedes every i-side task, so
    # there is no triangular/prefix mask — only padding and validity
    mask = (gi < wi_total) & (gj < wj_total)
    mask &= (valid_i[:, :1] != 0) & (valid_j[:, :1].T != 0)
    out_ref[...] = (conf & mask).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("strict", "interpret", "block"))
def conflict_block_pallas(reads_i, writes_i, reads_j, writes_j,
                          valid_i, valid_j, *, strict: bool = True,
                          interpret: bool | None = None, block: int = BLOCK):
    """Rectangular cross-window conflict block [Wi, Wj] int32.

    Rows are the *later* window's tasks (reads_i [Wi, nr_i] / writes_i
    [Wi, nw_i]), columns the *earlier* window's (reads_j [Wj, nr_j] /
    writes_j [Wj, nw_j]); -1 ids are unused slots, valid_* mask padded
    window entries. Because the two sides come from different windows,
    every column task precedes every row task in chain order, so — unlike
    the triangular prefix kernel — the tile grid is the full [Wi/B, Wj/B]
    rectangle and no prefix mask applies. This is the overlapped engines'
    carry-over record check (core/records.cross_window_conflicts).
    """
    if interpret is None:
        from repro.kernels import interpret_default

        interpret = interpret_default()
    wi, nr_i = reads_i.shape
    wj, nr_j = reads_j.shape
    nw_i, nw_j = writes_i.shape[1], writes_j.shape[1]
    b_i, b_j = min(block, wi), min(block, wj)
    wi_pad, wj_pad = -(-wi // b_i) * b_i, -(-wj // b_j) * b_j

    def _pad(x, w_pad):
        w = x.shape[0]
        return (x if w_pad == w else
                jnp.pad(x, ((0, w_pad - w), (0, 0)), constant_values=-1))

    reads_i, writes_i = _pad(reads_i, wi_pad), _pad(writes_i, wi_pad)
    reads_j, writes_j = _pad(reads_j, wj_pad), _pad(writes_j, wj_pad)
    vi = jnp.pad(valid_i.astype(jnp.int32), (0, wi_pad - wi))[:, None]
    vj = jnp.pad(valid_j.astype(jnp.int32), (0, wj_pad - wj))[:, None]

    out = pl.pallas_call(
        functools.partial(_block_kernel, nr_i, nw_i, nr_j, nw_j,
                          strict, wi, wj),
        grid=(wi_pad // b_i, wj_pad // b_j),
        in_specs=[pl.BlockSpec((b_i, nr_i), lambda i, j: (i, 0)),
                  pl.BlockSpec((b_i, nw_i), lambda i, j: (i, 0)),
                  pl.BlockSpec((b_j, nr_j), lambda i, j: (j, 0)),
                  pl.BlockSpec((b_j, nw_j), lambda i, j: (j, 0)),
                  pl.BlockSpec((b_i, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((b_j, 1), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((b_i, b_j), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((wi_pad, wj_pad), jnp.int32),
        interpret=interpret,
    )(reads_i, writes_i, reads_j, writes_j, vi, vj)
    return out[:wi, :wj]
