"""Blocked wave-level Pallas kernel.

``wave_levels`` is inherently a prefix recurrence — level[i] needs the
levels of every earlier conflicting task — which the reference implements
as a W-step ``lax.scan``: W dependent HBM round-trips, the last serial
stage on the scheduling path. The blocked formulation reduces the serial
structure to the B diagonal blocks of the (tiled) conflict matrix:

  grid step bi (sequential over W/B diagonal blocks):
    panel  = C[bi·B : bi·B+B, :]                      # [B, W] row panel
    dep0   = rowwise max of levels[j] over j < bi·B   # one vectorized
             where C[row, j]                          #   [B, W] pass
    in-block: a B-step loop resolves the [B, B] diagonal block, each step
             one vectorized masked max over the block
    levels[bi·B : bi·B+B] written; the full level vector stays resident
             in VMEM across grid steps (constant-index output block)

So the cross-block dependence work — all but a [B, B] sliver of the
matrix — is a single [B, W] vectorized pass per block instead of B scan
steps touching HBM, and the remaining serial loop runs on VMEM-resident
operands. Grid iteration on TPU is sequential by construction, which is
exactly the ordering the recurrence needs.

Semantics match the scan reference for *arbitrary* inputs: entries at or
above the diagonal and entries pointing at invalid tasks contribute the
initial level -1, i.e. nothing.

Mosaic constraints. The diagonal block is a dynamic lane slice
``conf_ref[:, pl.ds(base, B)]``, which compiles only when ``base`` is a
provable multiple of 128; the window is therefore always padded with
invalid slots to a multiple of B = 128 (a W = 64 window runs as one
128-block). The double-buffered (B, W) int32 conflict panel counts
against Mosaic's scoped VMEM limit, so the kernel's scoped VMEM grows
linearly in W; windows past ``MAX_WINDOW`` are refused up front with an
error naming the limit, instead of failing inside Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 128

#: default scoped VMEM of one TPU v5e TensorCore (Mosaic's limit when a
#: kernel sets none)
VMEM_LIMIT = 16 * 2**20


def vmem_bytes(wp: int, block: int = BLOCK) -> int:
    """Scoped VMEM the kernel needs for a window padded to ``wp`` slots:
    the double-buffered (block, wp) int32 panel plus ~48 B per slot of
    Mosaic's own scratch (fitted to its scoped-allocation report on v5e:
    16.08 MiB at wp = 15744, 16.75 MiB at wp = 16384)."""
    return 2 * block * wp * 4 + 48 * wp


#: largest window (a multiple of BLOCK) whose panel fits VMEM_LIMIT
MAX_WINDOW = VMEM_LIMIT // vmem_bytes(1) // BLOCK * BLOCK


def _kernel(conf_ref, valid_ref, base_ref, out_ref):
    bi = pl.program_id(0)
    b = conf_ref.shape[0]      # block rows
    wp = conf_ref.shape[1]     # padded window
    base = bi * b

    @pl.when(bi == 0)
    def _():
        out_ref[...] = jnp.full_like(out_ref, -1)

    panel = conf_ref[...] != 0                                   # [B, W]
    lv = out_ref[...].reshape(1, wp)                             # [1, W]
    col = jax.lax.broadcasted_iota(jnp.int32, (b, wp), 1)
    prior = jnp.where(panel & (col < base), lv, -1)
    dep0 = jnp.max(prior, axis=1, keepdims=True)                 # [B, 1]

    blk = conf_ref[:, pl.ds(base, b)] != 0                       # [B, B]
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    ri = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)          # [B, 1]
    ci = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)          # [1, B]
    vrow = valid_ref[...] != 0                                   # [B, 1]
    floor = base_ref[...]                                        # [B, 1]

    def body(r, cur):
        # cur [1, B]: levels of the block's tasks resolved so far (-1 unset)
        m_in = jnp.max(jnp.where((rows == r) & blk, cur, -1))
        m_pre = jnp.max(jnp.where(ri == r, dep0, -1))
        base_r = jnp.max(jnp.where(ri == r, floor, 0))
        lvl = jnp.maximum(jnp.maximum(m_in, m_pre) + 1, base_r)
        valid_r = jnp.max(jnp.where((ri == r) & vrow, 1, 0)) > 0
        lvl = jnp.where(valid_r, lvl, -1)
        return jnp.where(ci == r, lvl, cur)

    cur = jax.lax.fori_loop(0, b, body,
                            jnp.full((1, b), -1, dtype=jnp.int32))
    out_ref[pl.ds(base, b), :] = cur.reshape(b, 1)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def wave_levels_pallas(conflicts, valid, base=None, *,
                       interpret: bool | None = None, block: int = BLOCK):
    """conflicts [W, W] bool/int, valid [W] bool -> [W] int32 levels.

    ``base`` (optional [W] int32, non-negative) is the per-task level
    floor — the overlapped engines' cross-window carry frontier; None
    means no floor (all-zero), the classic recurrence.

    interpret=None picks by the platform the call is traced for: compiled
    on TPU, the Pallas interpreter elsewhere. Windows up to MAX_WINDOW are
    accepted; they are padded with invalid slots to a multiple of the
    128-lane block internally.
    """
    if interpret is None:
        from repro.kernels import interpret_default

        interpret = interpret_default()
    assert block % 128 == 0, "the diagonal-block lane slice needs 128-multiples"
    w = conflicts.shape[0]
    b = block
    wp = -(-w // b) * b  # next multiple of the block
    if vmem_bytes(wp, b) > VMEM_LIMIT:
        raise ValueError(
            f"wave_levels_pallas: window {w} needs "
            f"{vmem_bytes(wp, b) / 2**20:.2f} MiB of scoped VMEM, over the "
            f"{VMEM_LIMIT >> 20} MiB limit; the largest window this kernel "
            f"takes is {MAX_WINDOW}")
    conf = conflicts.astype(jnp.int32)
    if base is None:
        base = jnp.zeros((w,), dtype=jnp.int32)
    base = base.astype(jnp.int32)
    if wp != w:
        conf = jnp.pad(conf, ((0, wp - w), (0, wp - w)))
        valid = jnp.pad(valid.astype(bool), (0, wp - w),
                        constant_values=False)
        base = jnp.pad(base, (0, wp - w))
    valid_i32 = valid.astype(jnp.int32)[:, None]  # [W, 1] for clean tiling
    base_i32 = base[:, None]                      # [W, 1] for clean tiling

    out = pl.pallas_call(
        _kernel,
        grid=(wp // b,),
        in_specs=[pl.BlockSpec((b, wp), lambda i: (i, 0)),
                  pl.BlockSpec((b, 1), lambda i: (i, 0)),
                  pl.BlockSpec((b, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((wp, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((wp, 1), jnp.int32),
        interpret=interpret,
    )(conf, valid_i32, base_i32)
    return out[:w, 0]
