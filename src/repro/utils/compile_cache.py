"""Persistent XLA compile cache for the entry-point scripts.

Every model closes over its ``Topology``, so each engine executable embeds
the [N, max_degree] neighbor table as a constant: at 10⁶ agents a single
window executable is tens of MB and its cold compile is the largest fixed
cost of a run. JAX's persistent compilation cache lets a second process
in the same checkout load those executables instead of compiling them.

The rule is one place, ``enable_compile_cache``, called by the scripts
(``chip_smoke.py``, ``benchmarks/engine_sweep.py``, the examples) and
never when the library is imported:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself and
  nothing else is configured here;
* unset — the cache lives at ``<checkout>/.jax_cache``. The path is
  fixed (no temporary name, pid or time in it): a directory that moves
  between runs never hits.

``compile_stats`` counts the cache's hits and misses in this process and
sums the seconds spent in XLA compilation, cache loads included (all
from JAX's monitoring events), so a script can show what its executables
cost and whether they came from the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default: src/repro/utils/ -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_counts = {"hits": 0, "misses": 0, "compile_s": 0.0}
_listening = False


def _on_event(event: str, **_):
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def _on_duration(event: str, duration: float, **_):
    if event == _COMPILE_EVENT:
        _counts["compile_s"] += duration


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on for this process and return
    its directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (left to JAX),
    else the fixed ``DEFAULT_DIR``. Call before the first compile."""
    global _listening
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return path


def compile_stats() -> dict:
    """``{"hits", "misses", "compile_s"}`` so far in this process, counted
    once ``enable_compile_cache`` has run: persistent-cache lookups, and
    seconds in XLA compilation (loads from the cache included)."""
    return dict(_counts)
