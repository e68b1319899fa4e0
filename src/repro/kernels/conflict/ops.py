"""Public wrapper for the prefix-conflict computation.

Routes between the Pallas kernel (compiled on TPU; interpreter elsewhere)
and a vectorized pure-jnp implementation, chosen when the call is traced
(``repro.kernels.resolve``). On CPU the jnp path is the default: Pallas
interpret mode re-traces the tile loop in Python and is orders of
magnitude slower than one fused XLA elementwise kernel, while on TPU the
tiled Pallas kernel keeps each [B, B] block in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.model import footprint_conflicts
from repro.kernels import resolve
from repro.kernels.conflict.conflict import (
    conflict_block_pallas,
    conflict_matrix_pallas,
)
from repro.obs.profiler import annotate


@functools.partial(jax.jit, static_argnames=("strict",))
def conflict_matrix_jnp(read_ids, write_ids, valid, *, strict: bool = True):
    """Vectorized fallback: the shared hazard algebra (footprint_conflicts)
    broadcast to all pairs, plus the prefix/validity mask."""
    w = read_ids.shape[0]
    conf = footprint_conflicts(
        (read_ids[:, None], write_ids[:, None]),
        (read_ids[None, :], write_ids[None, :]),
        strict=strict,
    )
    lower = jnp.tril(jnp.ones((w, w), dtype=bool), k=-1)
    return conf & lower & valid[:, None] & valid[None, :]


def conflict_matrix(read_ids, write_ids, valid, *, strict: bool = True,
                    backend: str | None = None,
                    interpret: bool | None = None):
    """Prefix-conflict matrix [W, W] (bool) from id footprints.

    read_ids [W, nr] int32, write_ids [W, nw] int32; negative ids are unused
    slots; valid [W] bool masks padded window entries.

    backend: None  — auto: Pallas (compiled) on TPU, jnp elsewhere;
             "pallas" — force the kernel (interpret per ``interpret`` arg:
                        None = compiled on TPU, interpreter elsewhere);
             "jnp"    — force the vectorized fallback.
    """
    read_ids = jnp.asarray(read_ids, jnp.int32)
    write_ids = jnp.asarray(write_ids, jnp.int32)
    valid = jnp.asarray(valid, bool)
    backend, interpret = resolve("conflict_matrix", backend, interpret)
    with annotate("protocol.conflict_matrix"):
        if backend == "jnp":
            return conflict_matrix_jnp(read_ids, write_ids, valid,
                                       strict=strict)
        out = conflict_matrix_pallas(read_ids, write_ids, valid,
                                     strict=strict, interpret=interpret)
        return out.astype(bool)


@functools.partial(jax.jit, static_argnames=("strict",))
def conflict_block_jnp(reads_i, writes_i, reads_j, writes_j,
                       valid_i, valid_j, *, strict: bool = True):
    """Vectorized fallback for the rectangular cross block: the shared
    hazard algebra broadcast over all (later i, earlier j) pairs, masked
    by validity only — no triangular mask, every j precedes every i."""
    conf = footprint_conflicts(
        (reads_i[:, None], writes_i[:, None]),
        (reads_j[None, :], writes_j[None, :]),
        strict=strict,
    )
    return conf & valid_i[:, None] & valid_j[None, :]


def conflict_block(reads_i, writes_i, reads_j, writes_j, valid_i, valid_j,
                   *, strict: bool = True, backend: str | None = None,
                   interpret: bool | None = None):
    """Cross-window conflict block [Wi, Wj] (bool) from id footprints.

    Rows are the *later* window's tasks, columns the *earlier* window's;
    negative ids are unused slots; valid_i/valid_j mask padded entries.
    This is the overlapped engines' carry-over record check — the
    [W_next, W_tail] block between window k+1's head tasks and window
    k's not-yet-drained tail (core/records.cross_window_conflicts).

    backend: None  — auto: Pallas (compiled) on TPU, jnp elsewhere;
             "pallas" — force the rectangular-tile kernel (interpret as
                        in ``conflict_matrix``);
             "jnp"    — force the vectorized fallback.
    """
    reads_i = jnp.asarray(reads_i, jnp.int32)
    writes_i = jnp.asarray(writes_i, jnp.int32)
    reads_j = jnp.asarray(reads_j, jnp.int32)
    writes_j = jnp.asarray(writes_j, jnp.int32)
    valid_i = jnp.asarray(valid_i, bool)
    valid_j = jnp.asarray(valid_j, bool)
    backend, interpret = resolve("conflict_block", backend, interpret)
    with annotate("protocol.conflict_block"):
        if backend == "jnp":
            return conflict_block_jnp(reads_i, writes_i, reads_j, writes_j,
                                      valid_i, valid_j, strict=strict)
        out = conflict_block_pallas(reads_i, writes_i, reads_j, writes_j,
                                    valid_i, valid_j, strict=strict,
                                    interpret=interpret)
        return out.astype(bool)
