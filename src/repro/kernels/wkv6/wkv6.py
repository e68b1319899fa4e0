"""RWKV6 time-mix Pallas kernel — chunked data-dependent-decay recurrence.

This is the classic "recurrence that deserves a kernel" (the RWKV project
ships a CUDA kernel for it). TPU-native rethink: instead of a per-timestep
CUDA thread loop, we use the chunked-scan formulation —

  grid = (B·H, T/L); the chunk axis is sequential ("arbitrary"), carrying
  the inter-chunk state S [D, D] in f32 VMEM scratch.

Per chunk of length L, with per-channel log-decays lw_t = log w_t < 0 and
inclusive cumsums s_t = Σ_{j<=t} lw_j (monotone decreasing):

  state term   o  += (r_t ⊙ e^{s_{t-1}}) @ S0                (MXU matmul;
               exponents <= 0, numerically safe)
  intra term   A[t,i<t] = Σ_d r[t,d] k[i,d] e^{s_{t-1,d} - s_{i,d}}
               A[t,t]   = Σ_d r[t,d] u[d]  k[t,d]
               o  += A @ V                                    (MXU matmul)
  state update S <- diag(e^{s_L}) S0 + (k ⊙ e^{s_L - s})ᵀ @ V (MXU matmul)

All exponents are differences s_a - s_b with a >= b along time, hence <= 0:
the chunked form is stable without log-space max-subtraction games. The
intra-chunk A is computed blockwise: off-diagonal sub-blocks factor through
a boundary reference (two stable matmuls); diagonal sub-blocks are computed
directly as an [l, l, D] masked contraction (VPU).

VMEM budget per grid step (L=128, D=64, f32): r/k/v/w blocks 4·32 KiB,
S scratch 16 KiB, A 64 KiB, sub-block temporaries < 128 KiB — well under
the ~16 MiB budget, leaving room for double-buffered pipelines.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


DEFAULT_CHUNK = 128
SUB = 32  # diagonal sub-block length


def _kernel(n_heads: int, chunk: int,
            r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sfin_ref, s_ref):
    c = pl.program_id(1)
    L = chunk
    d = r_ref.shape[-1]

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)          # [L, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # [1, D] block -> [D]

    lw = jnp.log(w)                           # < 0
    s_incl = jnp.cumsum(lw, axis=0)           # [L, D] decreasing
    s_excl = s_incl - lw

    S0 = s_ref[...]                           # [D, D]

    # ---- state term: (r ⊙ e^{s_excl}) @ S0 ----
    q = r * jnp.exp(s_excl)
    o = jax.lax.dot_general(q, S0, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [L, D]

    # ---- intra-chunk A ----
    a = jnp.zeros((L, L), jnp.float32)
    n_sub = L // SUB
    for bi in range(n_sub):          # row (later) sub-block
        t0 = bi * SUB
        # boundary reference: s at the *start* of row block (exclusive)
        s_ref_row = s_excl[t0]                          # [D]
        q_b = (r[t0:t0 + SUB] * jnp.exp(s_excl[t0:t0 + SUB] - s_ref_row))
        for bj in range(bi):         # strictly-earlier column sub-blocks
            i0 = bj * SUB
            k_b = (k[i0:i0 + SUB] * jnp.exp(s_ref_row - s_incl[i0:i0 + SUB]))
            blk = jax.lax.dot_general(
                q_b, k_b, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # [SUB, SUB]
            a = jax.lax.dynamic_update_slice(a, blk, (t0, i0))
        # diagonal sub-block: direct masked contraction + u-bonus diag
        rd = r[t0:t0 + SUB]                              # [l, D]
        kd = k[t0:t0 + SUB]
        se = s_excl[t0:t0 + SUB]
        si = s_incl[t0:t0 + SUB]
        expdiff = jnp.exp(se[:, None, :] - si[None, :, :])  # [l, l, D]
        blk = jnp.sum(rd[:, None, :] * kd[None, :, :] * expdiff, axis=-1)
        tri = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0) > \
            jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
        diag_val = jnp.sum(rd * u * kd, axis=-1)         # [l]
        eye = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0) == \
            jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
        blk = jnp.where(tri, blk, 0.0) + jnp.where(eye, diag_val[:, None], 0.0)
        a = jax.lax.dynamic_update_slice(a, blk, (t0, t0))

    o = o + jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # ---- state update ----
    total = s_incl[L - 1]                                # [D]
    k_dec = k * jnp.exp(total[None, :] - s_incl)         # [L, D]
    s_new = (jnp.exp(total)[:, None] * S0
             + jax.lax.dot_general(k_dec, v, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))
    s_ref[...] = s_new
    o_ref[0] = o.astype(o_ref.dtype)
    # constant block index along the sequential axis: the last write wins,
    # so emitting every step is safe on TPU and in interpret mode alike
    sfin_ref[0] = s_new


@functools.partial(
    jax.jit, static_argnames=("n_heads", "interpret", "chunk"))
def wkv6_pallas(r, k, v, w, u, *, n_heads: int, interpret: bool = True,
                chunk: int = DEFAULT_CHUNK):
    """r/k/v/w: [B·H, T, D]; u: [H, D]. Returns (o [B·H, T, D] f32,
    s_final [B·H, D, D] f32)."""
    bh, t, d = r.shape
    L = min(chunk, t)
    assert t % L == 0, (t, L)
    assert L % SUB == 0, (L, SUB)
    grid = (bh, t // L)

    tmap = lambda b, c: (b, c, 0)
    umap = lambda b, c: (b % n_heads, 0)

    return pl.pallas_call(
        functools.partial(_kernel, n_heads, L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L, d), tmap),
            pl.BlockSpec((1, L, d), tmap),
            pl.BlockSpec((1, L, d), tmap),
            pl.BlockSpec((1, L, d), tmap),
            pl.BlockSpec((1, d), umap),
        ],
        out_specs=[
            pl.BlockSpec((1, L, d), tmap),
            pl.BlockSpec((1, d, d), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)
