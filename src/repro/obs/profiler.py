"""Device-profile integration for the protocol phases.

Two timing sources meet in one profile (docs/observability.md):

  * the span tracer (obs/trace.py) — unfenced host spans of the run
    loops (``repro.schedule``, ``repro.execute``, ...), each entered as
    a ``jax.profiler.TraceAnnotation``, so under a profiler session they
    land on the host plane, on the device ops' clock;
  * the XLA device profiler (``jax.profiler.trace``) — op-accurate
    device timelines, where the protocol phases show up by name because
    the scheduling kernels, halo gathers and window executors are
    wrapped in ``protocol.*`` named scopes (``annotate`` below).

``profile_session`` is the context helper the benchmarks wire in
(``benchmarks/engine_sweep.py --profile DIR``): a no-op when ``logdir``
is falsy, a ``jax.profiler.trace`` session otherwise — the resulting
TensorBoard/Perfetto profile groups device ops under the protocol
phase scopes.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax

#: named-scope alias used at every protocol phase boundary; a trace-time
#: label only — zero runtime cost, safe inside jit/shard_map/pallas
#: wrappers (the scope names the traced ops, it does not execute).
annotate = jax.named_scope


@contextmanager
def profile_session(logdir: str | None = None):
    """Device-profiler context: no-op when ``logdir`` is falsy, else a
    ``jax.profiler.trace`` session writing a TensorBoard-loadable
    profile (with the ``protocol.*`` scopes labeling the phases)."""
    if not logdir:
        yield None
        return
    with jax.profiler.trace(logdir):
        yield logdir
