"""shard_map Megatron-SP transformer block (§Perf iteration 10).

GSPMD-driven sequence parallelism regressed 1.9× (EXPERIMENTS.md iteration
7): the chunked-attention inner map re-gathers a T-sharded operand per
chunk. This block pins the schedule by hand, the same way moe_sharded.py
does for EP:

  residual stream x: [B, T/msz, D]   (T sharded over model between blocks)
  1. all_gather(model, T)   -> x_full [B, T, D]          (0.47 GiB·15/16)
  2. norm1; qkv with column-sharded weights -> local q-head subset
     (kv replicated when Hkv doesn't divide; expanded+sliced locally)
  3. chunked attention — entirely local (head-subset)
  4. out-projection row-sharded -> partial [B, T, D]
  5. reduce_scatter(model, T)  + residual add             (0.47 GiB·15/16)
  6. same AG/RS pair around the SwiGLU MLP

vs the pjit baseline's 2 all-reduces (= 2×bytes each): the napkin says
~2× less wire per layer plus T-sharded activations between blocks.

Weight layouts match distributed/sharding.py's TP rules, so the same
checkpoint serves both paths. Used when cfg.tp_shard_map is set and heads
divide the model axis (dense/vlm families).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.attention import attention_inner
from repro.models.layers import rmsnorm, rope


def attn_mlp_block_sharded(lp, x, cfg, *, positions, window, mesh):
    """One pre-norm attention+SwiGLU layer under manual SP.

    x [B, T, D] logically T-sharded over model (in_spec pins it). Returns
    the same layout. lp: the standard layer params (norm1/attn/norm2/mlp).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    msz = sizes.get("model", 1)
    dax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = dax if len(dax) > 1 else (dax[0] if dax else None)
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    assert hq % msz == 0, "tp_shard_map needs q-heads % model == 0"
    h_loc = hq // msz
    kv_sharded = hkv % msz == 0

    def fn(xs, n1, wq, wk, wv, wo, n2, wg, wu, wdn):
        b = xs.shape[0]
        # ---- SP: gather the full sequence ----
        xf = jax.lax.all_gather(xs, "model", axis=1, tiled=True)  # [B,T,D]
        t = xf.shape[1]
        h = rmsnorm({"scale": n1}, xf, cfg.norm_eps)

        q = (h @ wq).reshape(b, t, h_loc, hd)
        k = (h @ wk).reshape(b, t, -1, hd)
        v = (h @ wv).reshape(b, t, -1, hd)
        q = rope(q, positions[:t], cfg.rope_theta)
        k = rope(k, positions[:t], cfg.rope_theta)
        q = q.transpose(0, 2, 1, 3)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
        if not kv_sharded:
            # kv replicated: expand to all q heads, slice this rank's span
            group = hq // hkv
            midx = jax.lax.axis_index("model")
            k = jnp.repeat(k, group, axis=1)
            v = jnp.repeat(v, group, axis=1)
            k = jax.lax.dynamic_slice_in_dim(k, midx * h_loc, h_loc, 1)
            v = jax.lax.dynamic_slice_in_dim(v, midx * h_loc, h_loc, 1)
        o = attention_inner(q, k, v, causal=True, window=window,
                            impl="chunked", chunk=cfg.attn_chunk)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, h_loc * hd)
        part = o @ wo                                   # partial over heads
        # ---- SP: reduce_scatter back to T-shards + residual ----
        att = jax.lax.psum_scatter(part, "model", scatter_dimension=1,
                                   tiled=True)
        xs = xs + att.astype(xs.dtype)

        # ---- MLP with the same AG/RS pair ----
        xf2 = jax.lax.all_gather(xs, "model", axis=1, tiled=True)
        h2 = rmsnorm({"scale": n2}, xf2, cfg.norm_eps)
        act = jax.nn.silu(h2 @ wg) * (h2 @ wu)
        part2 = act @ wdn
        mlp = jax.lax.psum_scatter(part2, "model", scatter_dimension=1,
                                   tiled=True)
        return xs + mlp.astype(xs.dtype)

    kv_spec = P(None, "model") if kv_sharded else P(None, None)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(bspec, "model", None),   # x: T-sharded
                  P(None),                   # norm1 scale
                  P(None, "model"),          # wq col-sharded (heads)
                  kv_spec, kv_spec,          # wk, wv
                  P("model", None),          # wo row-sharded
                  P(None),                   # norm2 scale
                  P(None, "model"),          # w_gate
                  P(None, "model"),          # w_up
                  P("model", None)),         # w_down
        out_specs=P(bspec, "model", None),
        check_vma=False,
    )(x, lp["norm1"]["scale"], lp["attn"]["wq"]["w"], lp["attn"]["wk"]["w"],
      lp["attn"]["wv"]["w"], lp["attn"]["wo"]["w"], lp["norm2"]["scale"],
      lp["mlp"]["w_gate"]["w"], lp["mlp"]["w_up"]["w"],
      lp["mlp"]["w_out"]["w"])
