"""Fused attention Pallas kernel (flash-attention, TPU layout).

Design for the TPU memory hierarchy:

  * grid = (B·H, T/bq, S/bk); the kv axis is the innermost, *sequential*
    dimension (dimension_semantics "arbitrary") carrying the online-softmax
    running state (m, l, acc) in VMEM scratch across kv blocks.
  * blocks: q [bq, D], k/v [bk, D] with bq = bk = 128 — MXU-aligned matmul
    dims (128×D×128); the two matmuls per tile hit the MXU, masking and the
    online-softmax rescale run on the VPU in f32.
  * GQA is resolved in the k/v BlockSpec index maps (query head h reads kv
    head h // group) — no repeat/materialization of kv in HBM.
  * causal + sliding-window masks are computed from global indices; tiles
    that the mask would zero entirely are skipped with pl.when (the grid
    still visits them, but neither matmul executes — the hillclimb log
    discusses replacing this with a shortened kv grid per q block).

The q block is aligned to the *end* of the key axis when S > T, which gives
chunked-prefill/decode semantics for free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(causal: bool, window: int | None, scale: float, seq_off: int,
            n_kv_blocks: int,
            q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # global positions (q offset by seq_off = S - T: ends aligned)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + seq_off
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # tile-level skip test (static per (qi, kj) given causal/window)
    q_first = qi * bq + seq_off
    q_last = q_first + bq - 1
    k_first = kj * bk
    k_last = k_first + bk - 1
    live = True
    if causal:
        live = jnp.logical_and(live, k_first <= q_last)
    if window is not None:
        live = jnp.logical_and(live, k_last > q_first - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]

        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                    # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                 # [bq, bk]
        correction = jnp.exp(m_prev - m_new)                   # [bq, 1]
        l_ref[...] = l_ref[...] * correction + jnp.sum(
            p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bq, D]
        acc_ref[...] = acc_ref[...] * correction + pv
        m_ref[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "n_q_heads", "n_kv_heads",
                     "interpret", "block_q", "block_k"))
def flash_attention_pallas(q, k, v, *, causal: bool, window: int | None,
                           scale: float, n_q_heads: int, n_kv_heads: int,
                           interpret: bool = True,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K):
    """q [B·H, T, D]; k, v [B·Hkv, S, D]. Returns o [B·H, T, D]."""
    bh, t, d = q.shape
    s = k.shape[1]
    bq = min(block_q, t)
    bk = min(block_k, s)
    assert t % bq == 0 and s % bk == 0, (t, s, bq, bk)
    group = n_q_heads // n_kv_heads
    grid = (bh, t // bq, s // bk)
    seq_off = s - t

    def kv_map(b, i, j):
        batch = b // n_q_heads
        head = b % n_q_heads
        return (batch * n_kv_heads + head // group, j, 0)

    kernel = functools.partial(_kernel, causal, window, scale, seq_off,
                               s // bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
