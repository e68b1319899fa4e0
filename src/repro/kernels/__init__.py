"""Pallas TPU kernels for the compute hot-spots.

Each subpackage ships three files:
  <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (interpret=True fallback on CPU)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels:
  conflict — W×W prefix-conflict bitmask over task id-footprints (the
             protocol's O(W²) record check, paper §3.5); triangular
             1-D tile walk via scalar prefetch
  levels   — blocked wave-level assignment over the conflict matrix
             (replaces the per-task scan on the scheduling path)
  axelrod  — one wave of pairwise cultural interactions (paper §4.1)
  sir      — one wave of ring-graph SIRS subset updates (paper §4.2)
  wkv6     — RWKV6 data-dependent-decay time-mix (chunked recurrence)
  flash    — fused attention (causal / sliding-window), online softmax

Backend choice happens when a call is traced, from the platform JAX runs
on at that moment — never at import, so importing the package starts no
backend. On a TPU the scheduling kernels are the compiled Pallas kernels;
elsewhere the fused jnp / scan fallbacks, or the Pallas interpreter when
``backend="pallas"`` is forced.
"""
from __future__ import annotations

import jax

#: how each scheduling kernel was last traced: ``{"conflict_matrix" |
#: "conflict_block" | "wave_levels": "pallas" | "interpret" | "jnp"}``
#: ("pallas" = compiled Mosaic kernel). Written by ``resolve``; the chip
#: smoke test reads it to refuse a run that fell back off the kernels.
RESOLVED: dict[str, str] = {}


def on_tpu() -> bool:
    """Whether calls traced now run on a TPU (the default backend)."""
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """pallas interpret mode: Python interpreter off TPU, compiled on TPU."""
    return not on_tpu()


def resolve(kernel: str, backend: str | None,
            interpret: bool | None) -> tuple[str, bool]:
    """Pick (backend, interpret) for one scheduling-kernel call at trace
    time: ``backend=None`` means the compiled Pallas kernel on TPU and the
    jnp fallback elsewhere; ``interpret=None`` means compiled on TPU and
    the interpreter elsewhere. Records the choice in ``RESOLVED``."""
    tpu = on_tpu()
    if backend is None:
        backend = "pallas" if tpu else "jnp"
    if interpret is None:
        interpret = not tpu
    if backend not in ("pallas", "jnp"):
        raise ValueError(f"unknown {kernel} backend {backend!r}")
    RESOLVED[kernel] = ("interpret" if backend == "pallas" and interpret
                        else backend)
    return backend, bool(interpret)
