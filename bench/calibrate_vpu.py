"""Measure the chip's int32 compare rate on its vector unit (VPU), once.

No published figure gives a TPU's VPU integer rate, and the schedule's
roofline (``bench/workcount.py``) needs one. This Pallas kernel is the
fastest plain compare stream written for it: an int32 block resident in
VMEM (and in vector registers), compared against a value that changes
every step of an in-kernel loop, each result OR-ed into an accumulator,
nothing else. Every variant of block height and unroll is timed and the
best rate is the one to commit to ``bench/peaks.json`` as
``vpu_int32_compare_per_s``, with its date, commit and this kernel's
name. The benchmark never runs this; the rate stays fixed.

    python bench/calibrate_vpu.py          # on the chip; prints one JSON line
"""
from __future__ import annotations

import functools
import json
import sys
import time


def _kernel(n_iter: int, unroll: int, x_ref, o_ref):
    import jax
    import jax.numpy as jnp

    # the block as (8, 128) tiles, one vector register each, loaded once
    xs = [x_ref[r * 8:(r + 1) * 8, :] for r in range(x_ref.shape[0] // 8)]

    def body(i, accs):
        # the accumulators cross the loop as int32 (Mosaic keeps no mask
        # registers across iterations); inside, each step is one compare
        # and one OR a tile, an accumulator a tile
        m = [a != 0 for a in accs]
        for k in range(unroll):
            s = i * unroll + k
            m = [a | (x == s) for a, x in zip(m, xs)]
        return tuple(a.astype(jnp.int32) for a in m)

    accs = jax.lax.fori_loop(
        0, n_iter, body, tuple(jnp.zeros((8, 128), jnp.int32) for _ in xs))
    acc = accs[0]
    for a in accs[1:]:
        acc = acc | a
    o_ref[...] = acc


def rate(rows: int, unroll: int, n_iter: int, repeats: int = 3) -> float:
    """Best compares per second over ``repeats`` fenced calls."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    x = jax.random.randint(jax.random.key(0), (rows, 128), 0,
                           n_iter * unroll, dtype=jnp.int32)
    call = jax.jit(pl.pallas_call(
        functools.partial(_kernel, n_iter, unroll),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int32)))
    call(x).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t = time.perf_counter()
        call(x).block_until_ready()
        best = min(best, time.perf_counter() - t)
    return rows * 128 * unroll * n_iter / best


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate_vpu: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    rows_list, unrolls = (8, 32, 64, 128), (8, 16, 32)
    results = []
    for rows in rows_list:
        for unroll in unrolls:
            # about 2^36 compares a call: some tens of milliseconds or more
            n_iter = max(1, 2**36 // (rows * 128 * unroll))
            r = rate(rows, unroll, n_iter)
            results.append({"rows": rows, "unroll": unroll, "n_iter": n_iter,
                            "compares_per_s": r})
            print(f"rows={rows} unroll={unroll}: {r:.4e} compares/s",
                  file=sys.stderr, flush=True)
    best = max(results, key=lambda r: r["compares_per_s"])
    print(json.dumps({"device_kind": dev.device_kind,
                      "vpu_int32_compare_per_s": best["compares_per_s"],
                      "best": best, "all": results}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
