"""shard_map expert-parallel MoE — the §Perf rewrite of the dense dispatch.

Why (measured, EXPERIMENTS.md §Perf): the pjit dense dispatch lets GSPMD
choose the collectives for the [E, C, D] scatter, and it chooses
catastrophically — 51 TB wire/step on qwen3-moe train_4k (every layer
re-gathers the expert buffer). This version pins the textbook EP schedule
explicitly:

  tokens: sharded over the data axes; replicated over model
  w_gate/w_up: [E→data, D→model, Fe]   w_out: [E→data, Fe, D→model]

  1. local top-k / sort / capacity  -> buf [E, C_loc, D_loc]
     (each model rank dispatches only its D-slice: the a2a ships D/msz)
  2. all_to_all over data           -> buf' [E_loc, dsz·C_loc, D_loc]
  3. h = buf' ·_D w_gate  (partial over D) --psum(model, bf16)--> [rows, Fe]
     silu gating local
  4. y = act · w_out      -> [rows, D_loc]  (no comms; D stays sharded)
  5. reverse all_to_all over data   -> [E, C_loc, D_loc]
  6. local gate-weighted combine -> out [N_loc, D_loc]
     --all_gather(model)--> [N_loc, D]  (residual stream is
     model-replicated elsewhere)

Napkin (qwen3 train_4k, per device per layer, fwd): 2×0.34 GiB a2a +
~3.2 GiB h/u psum + 0.5 GiB gather ≈ 4.4 GiB — vs ~540 GiB/layer measured
for the dense dispatch (≈40× predicted; dry-run confirms, EXPERIMENTS.md).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import swiglu


def _local_dispatch(xd, probs, k, e, cap):
    """xd [N, Dl]; probs [N, E] -> (buf [E, cap, Dl], se, st, sg, keep,
    rank) — sorted (expert, token, gate) arrays reused by the combine."""
    n = xd.shape[0]
    gates, choice = jax.lax.top_k(probs, k)                  # [N, k]
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    flat_e = choice.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(n), k)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.bincount(se, length=e)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(n * k) - starts[se]
    keep = rank < cap
    slot = jnp.where(keep, rank, cap)
    buf = jnp.zeros((e, cap + 1, xd.shape[1]), xd.dtype)
    buf = buf.at[se, slot].add(xd[st])
    return buf[:, :cap], se, st, sg, keep, rank


def moe_layer_sharded(params, x, cfg, mesh):
    """Drop-in replacement for moe_layer under `mesh`. x [B, S, D] sharded
    P(data-axes, None, None), model-replicated. Requires the shard_map
    param layout (sharding.py selects it when cfg.moe_impl=='shard_map')."""
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.n_experts, m.top_k
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsz = int(np.prod([sizes[a] for a in dax])) if dax else 1
    msz = sizes.get("model", 1)
    n_loc = (b * s) // dsz
    cap = int(n_loc * k / e * m.capacity_factor) + 1
    if cfg.moe_impl == "shard_map_wg" and msz > 1:
        # rows regrouped over model: dsz·cap must split msz ways
        cap = -(-cap // msz) * msz
    dl = d // msz
    weight_gathered = cfg.moe_impl == "shard_map_wg"
    bspec = dax if len(dax) > 1 else (dax[0] if dax else None)

    def fn(x_loc, rw, wg_l, wu_l, wo_l):
        nl = x_loc.shape[0] * x_loc.shape[1]
        xf = x_loc.reshape(nl, d)
        logits = xf.astype(jnp.float32) @ rw["w"]            # [Nl, E]
        probs = jax.nn.softmax(logits, axis=-1)

        if msz > 1:
            midx = jax.lax.axis_index("model")
            xd = jax.lax.dynamic_slice_in_dim(xf, midx * dl, dl, axis=1)
        else:
            xd = xf
        buf, se, st, sg, keep, rank = _local_dispatch(xd, probs, k, e, cap)

        # ---- EP all-to-all over the data axes ----
        if dsz > 1:
            buf = jax.lax.all_to_all(buf, dax, split_axis=0, concat_axis=1,
                                     tiled=True)     # [E/dsz, dsz·cap, Dl]
        if weight_gathered and msz > 1:
            # §Perf iteration 6: row-parallel expert GEMMs. Gather this
            # layer's expert weights over model (transient, ~2×300 MiB for
            # qwen3) and regroup the dispatch rows over model via a second
            # a2a, so each model rank runs full-D GEMMs on 1/msz of the
            # rows — replacing the 2×~3.2 GiB/layer h/u psums with
            # ~0.3 GiB a2as (measured in EXPERIMENTS.md §Perf).
            wg_f = jax.lax.all_gather(wg_l, "model", axis=1, tiled=True)
            wu_f = jax.lax.all_gather(wu_l, "model", axis=1, tiled=True)
            wo_f = jax.lax.all_gather(wo_l, "model", axis=2, tiled=True)
            rows = jax.lax.all_to_all(buf, "model", split_axis=1,
                                      concat_axis=2, tiled=True)
            h = jnp.einsum("ecd,edf->ecf", rows.astype(jnp.float32),
                           wg_f.astype(jnp.float32))
            u = jnp.einsum("ecd,edf->ecf", rows.astype(jnp.float32),
                           wu_f.astype(jnp.float32))
            act = jax.nn.silu(h) * u
            y = jnp.einsum("ecf,efd->ecd", act.astype(x.dtype), wo_f)
            y = jax.lax.all_to_all(y, "model", split_axis=2, concat_axis=1,
                                   tiled=True)       # back to [.., C', Dl]
        else:
            # ---- expert GEMMs (contraction over model-sharded D) ----
            h = jnp.einsum("ecd,edf->ecf", buf.astype(jnp.float32),
                           wg_l.astype(jnp.float32))
            u = jnp.einsum("ecd,edf->ecf", buf.astype(jnp.float32),
                           wu_l.astype(jnp.float32))
            if msz > 1:
                h = jax.lax.psum(h.astype(jnp.bfloat16), "model")
                u = jax.lax.psum(u.astype(jnp.bfloat16), "model")
            act = (jax.nn.silu(h.astype(jnp.float32))
                   * u.astype(jnp.float32))
            y = jnp.einsum("ecf,efd->ecd", act.astype(x.dtype), wo_l)

        # ---- reverse a2a + local combine ----
        if dsz > 1:
            y = jax.lax.all_to_all(y, dax, split_axis=1, concat_axis=0,
                                   tiled=True)               # [E, cap, Dl]
        contrib = y[se, jnp.where(keep, rank, 0)]
        contrib = jnp.where(keep[:, None], contrib, 0.0)
        out = jnp.zeros((nl, dl), y.dtype).at[st].add(
            contrib * sg[:, None].astype(y.dtype))
        if msz > 1:
            out = jax.lax.all_gather(out, "model", axis=1, tiled=True)

        # ---- aux metrics (consistent with models/moe.py) ----
        me = jnp.mean(probs, axis=0)
        ce = jnp.bincount(se, length=e).astype(jnp.float32) / nl
        lb = e * jnp.sum(me * ce) / k
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        ov = 1.0 - jnp.mean(keep.astype(jnp.float32))
        if dax:
            lb = jax.lax.pmean(lb, dax)
            z = jax.lax.pmean(z, dax)
            ov = jax.lax.pmean(ov, dax)
        aux = jnp.stack([lb, z, ov])
        return out.reshape(x_loc.shape[0], x_loc.shape[1], d), aux

    out, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(bspec, None, None),
                  P(),                               # router replicated
                  P(bspec, "model", None),           # w_gate [E, D, Fe]
                  P(bspec, "model", None),           # w_up
                  P(bspec, None, "model")),          # w_out [E, Fe, D]
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(x, params["router"], params["experts"]["w_gate"],
      params["experts"]["w_up"], params["experts"]["w_out"])

    aux_d = {"load_balance_loss": aux[0], "router_z_loss": aux[1],
             "overflow_fraction": aux[2]}
    if m.dense_parallel:
        out = out + swiglu(params["dense_mlp"], x)
    return out, aux_d
