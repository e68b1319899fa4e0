"""Span tracer of the engines' run loops.

Records what the host does in each ``Engine.run``: one span for the run,
for each window's schedule dispatch, each overlap boundary, each window's
execute dispatch and the read-back of the run's statistics, plus
counters (``host_syncs``: the blocking device-to-host reads of a run;
``device_loop_windows``: the windows run inside a device loop).
Exports Chrome trace-event JSON (``{"traceEvents": [...]}``, which
Perfetto and ``chrome://tracing`` load).

Design (docs/observability.md):

* **Off by default, no cost when off.** No tracer is installed unless
  the caller enters ``tracing()``; the engines check
  ``current_tracer() is None`` once per run and take the untraced path,
  which has no trace call, allocation or branch inside its loops.
* **No fences.** A span never waits for the device: a traced run issues
  the same jitted calls in the same order as an untraced one, with no
  extra host sync. A span's time is the host's time in its block (the
  dispatch of a jitted call, its argument puts, or a read-back that
  blocks on the device queue).
* **One clock.** Every span is also entered as
  ``jax.profiler.TraceAnnotation(name)``, so under a profiler session it
  lands on the XSpace host plane beside JAX's own events, on the clock of
  the device ops. The in-memory copy stamps ``time.time_ns()``, the clock
  the profiler's host events use (tests/test_obs.py pins the agreement).
* **Deferred facts.** What only the device knows (a window's wave count,
  levels, shipped rows) is kept by reference on its span
  (``Span.defer``) and read when the trace is rendered (``events``),
  after the run.

Spans (pid 1, process "repro.protocol", one lane; all nest under
``repro.run``):

  repro.run       one ``Engine.run``
  repro.schedule  one window's schedule dispatch (scalar puts included)
  repro.boundary  one overlap boundary step
  repro.execute   one window's execute dispatch; after the run it holds
                  ``n_waves`` and ``widths`` (tasks per wave), and on the
                  sharded engines ``rung``, ``rows``, ``bytes`` and
                  ``owned`` (tasks per device) per wave. The barrier
                  wavefront engine schedules and executes every window in
                  one device program: one ``repro.execute`` a run
                  (``device_loop=True``, ``windows``; ``n_waves`` after
                  the run, no ``widths``) and no ``repro.schedule``
  repro.readback  from the last dispatch to the stats dict

Usage:

    from repro.obs import tracing

    with tracing() as tr:
        state, stats = engine.run(state, total)
    tr.export("trace.json")           # -> load in ui.perfetto.dev
    tr.counters["host_syncs"]
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager, nullcontext
from typing import Any

import jax

#: Chrome trace-event phases the validator accepts.
PHASES = frozenset({"B", "E", "X", "i", "I", "C", "M"})

PID = 1
TID = 0


class Span:
    """One span: a context manager that stamps its host times (ns on
    ``time.time_ns``'s clock) and enters a profiler annotation of the same
    name. ``args`` may be extended until export."""

    __slots__ = ("name", "args", "t0", "t1", "_ann", "_later")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args
        self.t0: int = 0
        self.t1: int | None = None
        self._ann = None
        self._later: list = []

    def __enter__(self):
        self.t0 = time.time_ns()
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self.t1 = time.time_ns()
        self._ann = None
        return False

    def defer(self, fn, *refs) -> None:
        """Add ``fn(*refs)`` (a dict) to ``args`` when the trace is
        rendered: ``refs`` are device values the run must not wait for."""
        self._later.append((fn, refs))

    def resolve(self) -> None:
        while self._later:
            fn, refs = self._later.pop(0)
            self.args.update(fn(*refs))


class SpanTracer:
    """Spans and counters of the runs made while it is installed.

    Not thread-safe by design: the engines' run loops are single-
    threaded hosts, and the tracer is installed per ``tracing()`` block.
    """

    def __init__(self, *, process_name: str = "repro.protocol"):
        self.process_name = process_name
        self.spans: list[Span] = []
        self.counters: dict[str, int] = {}

    def span(self, name: str, **args: Any) -> Span:
        """A span around a block (``with tr.span(name, **args) as sp``)."""
        sp = Span(name, args)
        self.spans.append(sp)
        return sp

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    # ----------------------------------------------------------- export
    def events(self) -> list[dict]:
        """Every span as a Chrome B/E event pair (``ts`` in µs on the
        profiler's clock), its deferred facts read first."""
        out: list[dict] = [
            {"name": "process_name", "ph": "M", "pid": PID, "tid": TID,
             "args": {"name": self.process_name}},
        ]
        for sp in self.spans:
            sp.resolve()
            t1 = sp.t1 if sp.t1 is not None else time.time_ns()
            out.append({"name": sp.name, "ph": "B", "cat": "protocol",
                        "ts": sp.t0 / 1e3, "pid": PID, "tid": TID,
                        "args": dict(sp.args)})
            out.append({"name": sp.name, "ph": "E", "cat": "protocol",
                        "ts": t1 / 1e3, "pid": PID, "tid": TID})
        # stable ts order (ties keep emission order, so an E at the same
        # timestamp as the next B stays correctly nested)
        out.sort(key=lambda e: e.get("ts", 0.0))
        return out

    def to_chrome_trace(self) -> dict:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms",
                "otherData": {"tracer": "repro.obs", "version": 2,
                              "counters": dict(self.counters)}}

    def export(self, path: str | None = None) -> dict:
        """Chrome trace-event payload; also written to ``path`` if given."""
        payload = self.to_chrome_trace()
        if path is not None:
            with open(path, "w") as f:
                json.dump(payload, f)
        return payload


# --------------------------------------------------------------------------
# the installed tracer (module global; None = tracing off, the default)

_CURRENT: SpanTracer | None = None


def current_tracer() -> SpanTracer | None:
    """The installed tracer, or None (the default: tracing off). Engines
    check this once per run and take the untraced path when it is
    None."""
    return _CURRENT


def optional_span(tr: SpanTracer | None, name: str, **args: Any):
    """``tr.span(name, **args)``, or a no-op context when ``tr`` is None
    (for once-a-run spans; the loops branch on ``tr`` themselves)."""
    return nullcontext() if tr is None else tr.span(name, **args)


@contextmanager
def tracing(tracer: SpanTracer | None = None):
    """Install a tracer for the duration of the block (and restore the
    previous one after — blocks nest)."""
    global _CURRENT
    prev = _CURRENT
    tr = tracer if tracer is not None else SpanTracer()
    _CURRENT = tr
    try:
        yield tr
    finally:
        _CURRENT = prev


# --------------------------------------------------------------------------
# schema validation (tests + the CI trace-export smoke)

def validate_chrome_trace(payload: Any) -> int:
    """Validate a Chrome trace-event payload; returns the event count.

    Checks the invariants the tests and the CI smoke step pin:
      * top level is ``{"traceEvents": [...]}`` (or a bare event list);
      * every event carries name/ph/pid/tid, a known phase, and a
        non-negative ``ts`` (metadata ``M`` events are exempt from ts);
      * ``X`` events carry a non-negative ``dur``;
      * per (pid, tid), in timestamp order, ``B``/``E`` events form a
        properly nested stack with matching names and non-decreasing
        timestamps (every span closed, no cross-nesting).

    Raises ``ValueError`` on the first violation.
    """
    if isinstance(payload, dict):
        events = payload.get("traceEvents")
        if not isinstance(events, list):
            raise ValueError("payload has no traceEvents list")
    elif isinstance(payload, list):
        events = payload
    else:
        raise ValueError(f"not a trace payload: {type(payload).__name__}")

    lanes: dict[tuple, list[dict]] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i} is not an object")
        for k in ("name", "ph", "pid", "tid"):
            if k not in ev:
                raise ValueError(f"event {i} ({ev.get('name')!r}) "
                                 f"missing {k!r}")
        ph = ev["ph"]
        if ph not in PHASES:
            raise ValueError(f"event {i} ({ev['name']!r}) has unknown "
                             f"phase {ph!r}")
        if ph == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            raise ValueError(f"event {i} ({ev['name']!r}) has bad ts {ts!r}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                raise ValueError(f"X event {i} ({ev['name']!r}) has bad "
                                 f"dur {dur!r}")
        lanes.setdefault((ev["pid"], ev["tid"]), []).append(ev)

    for (pid, tid), lane in lanes.items():
        lane = sorted(lane, key=lambda e: e["ts"])  # stable: ties keep order
        stack: list[dict] = []
        last_ts = 0.0
        for ev in lane:
            if ev["ts"] < last_ts:
                raise ValueError(
                    f"tid {tid}: timestamps regress at {ev['name']!r}")
            last_ts = ev["ts"]
            if ev["ph"] == "B":
                stack.append(ev)
            elif ev["ph"] == "E":
                if not stack:
                    raise ValueError(
                        f"tid {tid}: E {ev['name']!r} without open B")
                top = stack.pop()
                if top["name"] != ev["name"]:
                    raise ValueError(
                        f"tid {tid}: E {ev['name']!r} closes B "
                        f"{top['name']!r} (cross-nested spans)")
        if stack:
            raise ValueError(
                f"tid {tid}: {len(stack)} unclosed span(s), first open: "
                f"{stack[0]['name']!r}")
    return len(events)
