"""Kernel micro-benchmarks (interpret-mode wall time is NOT TPU time — the
useful numbers are the pure-jnp oracle timings, which XLA compiles for CPU;
reported for completeness and trend tracking)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.timing import median_time

rng = np.random.RandomState(0)


def bench_conflict():
    from repro.kernels.conflict.ref import conflict_matrix_ref

    w = 512
    reads = jnp.asarray(rng.randint(0, 10_000, (w, 2)), jnp.int32)
    writes = reads[:, 1:]
    valid = jnp.ones((w,), bool)
    f = jax.jit(lambda r, wr, v: conflict_matrix_ref(r, wr, v, strict=True))
    t = median_time(lambda: f(reads, writes, valid))
    return [("conflict_ref_512", t * 1e6, f"{w*w/t/1e6:.0f} Mpairs/s")]


def bench_axelrod_wave():
    from repro.mabs.axelrod import AxelrodConfig, AxelrodModel
    from repro.engine import make_engine

    rows = []
    for f_ in (3, 150, 500):
        m = AxelrodModel(AxelrodConfig(n_agents=10_000, n_features=f_))
        st = m.init_state(jax.random.key(0))
        eng = make_engine("wavefront", m, window=256)
        t = median_time(lambda: eng.run(st, 256, seed=1)[0], repeats=3)
        rows.append((f"axelrod_window256_F{f_}", t * 1e6,
                     f"{256/t:.0f} tasks/s"))
    return rows


def bench_sir_wave():
    from repro.mabs.sir import SIRConfig, SIRModel
    from repro.engine import make_engine

    rows = []
    for s in (10, 100, 1000):
        m = SIRModel(SIRConfig(n_agents=4_000, k=14, subset_size=s))
        st = m.init_state(jax.random.key(0))
        w = min(64, 2 * m.cfg.n_subsets)
        eng = make_engine("wavefront", m, window=w)
        t = median_time(lambda: eng.run(st, w, seed=1)[0], repeats=3)
        rows.append((f"sir_window{w}_s{s}", t * 1e6,
                     f"{w*s/t:.0f} agent-updates/s"))
    return rows


def bench_wkv6():
    from repro.models.rwkv6 import wkv6_chunked_jnp

    b, h, t, d = 2, 8, 512, 64
    f = lambda *sh: jnp.asarray(rng.randn(*sh).astype(np.float32) * 0.3)
    r, k, v = f(b, h, t, d), f(b, h, t, d), f(b, h, t, d)
    w = jnp.exp(-jnp.exp(f(b, h, t, d)))
    u = f(h, d)
    fn = jax.jit(lambda *a: wkv6_chunked_jnp(*a, chunk=64)[0])
    tt = median_time(lambda: fn(r, k, v, w, u), repeats=3)
    return [("wkv6_chunked_jnp_2x8x512x64", tt * 1e6,
             f"{b*t/tt:.0f} tok/s")]


def bench_attention():
    from repro.models.attention import attention_inner

    b, h, hkv, t, d = 1, 8, 2, 1024, 64
    f = lambda *sh: jnp.asarray(rng.randn(*sh).astype(np.float32) * 0.3)
    q, k, v = f(b, h, t, d), f(b, hkv, t, d), f(b, hkv, t, d)
    fn = jax.jit(lambda q, k, v: attention_inner(q, k, v, impl="chunked",
                                                 chunk=256))
    tt = median_time(lambda: fn(q, k, v), repeats=3)
    return [("attn_chunked_1x8x1024x64", tt * 1e6, f"{b*t/tt:.0f} tok/s")]


def run_all():
    rows = []
    for fn in (bench_conflict, bench_axelrod_wave, bench_sir_wave,
               bench_wkv6, bench_attention):
        rows.extend(fn())
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows


if __name__ == "__main__":
    run_all()
