"""Public wrapper for wave-level assignment.

Routes between the blocked Pallas kernel (compiled on TPU; interpreter
elsewhere) and the reference ``lax.scan``, chosen when the call is traced
(``repro.kernels.resolve``). On CPU the scan is the default:
Pallas interpret mode re-traces the block loop in Python, while XLA
compiles the scan into one tight loop. On TPU the blocked kernel replaces
W dependent scan steps with W/B sequential grid steps whose operands stay
in VMEM.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import resolve
from repro.kernels.levels.levels import wave_levels_pallas
from repro.kernels.levels.ref import wave_levels_ref
from repro.obs.profiler import annotate


def wave_levels(conflicts, valid, *, base=None, backend: str | None = None,
                interpret: bool | None = None):
    """Wavefront levels [W] int32 from a prefix-conflict matrix.

        level[i] = max(base[i], 1 + max{ level[j] : j < i, C[i, j] })

    ``base`` (optional [W] int32, non-negative) is a per-task level
    floor — the overlapped engines pass the cross-window carry frontier
    (core/records.carry_frontier) so window k+1's tasks cannot start
    before the window-k tail waves they conflict with have drained; None
    (the default) means no floor, the classic recurrence (level 0 for
    tasks with no earlier conflicts). Invalid (padded) slots get level
    -1. Executing levels in ascending order is a topological order of
    the strict dependence DAG restricted to the window (paper §3.2).

    backend: None  — auto: Pallas (compiled) on TPU, the scan elsewhere;
             "pallas" — force the blocked kernel (interpret per
                        ``interpret`` arg: None = compiled on TPU,
                        interpreter elsewhere);
             "jnp"    — force the scan reference.
    """
    conflicts = jnp.asarray(conflicts)
    valid = jnp.asarray(valid, bool)
    if base is not None:
        base = jnp.asarray(base, jnp.int32)
    backend, interpret = resolve("wave_levels", backend, interpret)
    with annotate("protocol.wave_levels"):
        if backend == "jnp":
            return wave_levels_ref(conflicts, valid, base)
        return wave_levels_pallas(conflicts, valid, base,
                                  interpret=interpret)
