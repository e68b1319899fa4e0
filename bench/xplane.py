"""From a ``jax.profiler`` trace to the numbers the metrics read.

The profiler writes an XSpace (``<dir>/plugins/profile/<run>/*.xplane.pb``);
``jax.profiler.ProfileData`` reads it with nothing but JAX. Three kinds
of event matter here:

  device ops  each plane ``/device:TPU:<n>`` has an ``XLA Ops`` line: one
              event per HLO op run on that chip. On a TPU v5e with JAX
              0.9 the event's name is the op's HLO text
              (``%wave_levels_pallas.1 = s32[256,1]... custom-call(...)``)
              and no stat carries its JAX name-scope path, so a layer is
              found by the op's name (a Pallas kernel is named after its
              wrapper, e.g. ``wave_levels_pallas``; the wave loop is the
              ``while`` op, and where the windows run in a device loop of
              their own, a ``while`` op inside another one) or, where a
              stat does carry it, by a ``protocol.*`` scope
  host spans  the benchmark's own ``jax.profiler.TraceAnnotation`` spans
              (``bench.interval`` around each ``Engine.run`` call,
              ``bench.observable`` around the read-back), on the host plane
  dispatch    JAX's own host events for each jitted call, on the same
              thread: ``PjitFunction(<name>)``

The traced window runs from the first ``bench.interval`` start to the
last one's end. Every time here is the union of intervals, so nested or
overlapping events are never counted twice.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass

INTERVAL = "bench.interval"
OBSERVABLE = "bench.observable"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
DISPATCH_PREFIX = "PjitFunction("
#: stats that may carry an op's scope path, in the order tried
SCOPE_STATS = ("tf_op", "name_scope", "long_name")
#: the op of a device loop (``lax.while_loop``)
LOOP_OP = "while"


@dataclass
class Event:
    name: str
    start: float          # ns, on the trace's common clock
    end: float
    scope: str = ""       # name-scope path (device ops), where a stat has it
    line: str = ""

    @property
    def op(self) -> str:
        """The HLO op's name without its ``.<n>`` suffix: ``while`` for
        ``%while.2 = (...) while(...)``."""
        return op_name(self.name)


_OP = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s|$|=)")


def op_name(name: str) -> str:
    m = _OP.match(name)
    return m.group(1) if m else name


def union(spans, lo=float("-inf"), hi=float("inf")):
    """Merged (start, end) intervals of ``spans`` clipped to [lo, hi]."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in spans):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def length(merged) -> float:
    return sum(b - a for a, b in merged)


def intersect(a, b):
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def nested_loops(ops) -> list:
    """(start, end) of the ``while`` ops in ``ops`` that run inside
    another ``while`` op: a wave loop inside a device loop over windows."""
    loops = sorted(((o.start, o.end) for o in ops if o.op == LOOP_OP),
                   key=lambda x: (x[0], -x[1]))
    out, reach = [], float("-inf")
    for a, b in loops:
        if reach >= b:
            out.append((a, b))
        reach = max(reach, b)
    return out


def matches(op: "Event", names) -> bool:
    """Whether ``op`` is one of ``names``: by its op name, or by a
    component of its scope path."""
    parts = op.scope.split("/") if op.scope else []
    return any(n == op.op or n in parts for n in names)


class Trace:
    """Device ops per chip and host events of one traced window."""

    def __init__(self, chips: list[list[Event]], host: list[Event]):
        self.chips = chips
        self.host = host
        spans = [e for e in host if e.name == INTERVAL]
        self.intervals = sorted((e.start, e.end) for e in spans)
        # the benchmark's own thread: where its spans and JAX's dispatch
        # of its calls are
        self.main = [e for e in host if spans and e.line == spans[0].line]
        self.observables = sorted((e.start, e.end) for e in host
                                  if e.name == OBSERVABLE)
        if self.intervals:
            self.t0 = self.intervals[0][0]
            self.t1 = max(b for _, b in self.intervals)
        else:
            self.t0 = self.t1 = 0.0

    # ------------------------------------------------------------ window
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_s(self, names=None, inner_loops=False) -> float | None:
        """Seconds in the window in which an op ran on the device (only
        ops under one of the scopes ``names``, when given), averaged over
        the chips. With ``inner_loops``, on a chip where a ``while`` op
        runs inside another, only the time inside such nested ones counts
        (the wave loops, not the device loop over windows around them).
        None when the trace holds no device."""
        if not self.chips:
            return None
        tot = 0.0
        for ops in self.chips:
            sel = ops if names is None else [
                o for o in ops if matches(o, names)]
            busy = union(((o.start, o.end) for o in sel), self.t0, self.t1)
            inner = nested_loops(ops) if inner_loops else []
            if inner:
                busy = intersect(busy, union(inner))
            tot += length(busy)
        return tot / len(self.chips) * 1e-9

    def dispatch_s(self) -> float:
        """Host seconds in JAX's dispatch of jitted calls made inside the
        intervals (not the benchmark's own read-back)."""
        ev = [(e.start, e.end) for e in self.main
              if e.name.startswith(DISPATCH_PREFIX)
              and _inside(e, self.intervals)
              and not _inside(e, self.observables)]
        return length(union(ev)) * 1e-9

    # --------------------------------------------------------- breakdown
    def device_ops(self, layers: dict, top: int = 10):
        """[[label, seconds], ...]: device time by ``<layer>:<name>`` for
        ops of a layer (``layers`` maps a layer name to its op names and
        scopes; an op that runs inside one of a layer's ops, as a wave
        loop's body does, counts to it), else by ``other:<op name>``,
        most first, averaged over the chips."""
        groups: dict[str, list] = {}
        for ops in self.chips:
            labelled = sorted((o.start, o.end, _label(o, layers))
                              for o in ops)
            outer = [x for x in labelled if not x[2].startswith("other:")]
            starts = [x[0] for x in outer]
            reach = []  # (end, label) of the farthest-reaching outer op so far
            for x in outer:
                reach.append(max(reach[-1], (x[1], x[2])) if reach
                             else (x[1], x[2]))
            for a, b, label in labelled:
                if label.startswith("other:"):
                    i = bisect.bisect_right(starts, a) - 1
                    if i >= 0 and reach[i][0] >= b:
                        label = reach[i][1]
                groups.setdefault(label, []).append((a, b))
        n = max(len(self.chips), 1)
        rows = [[k, length(union(v, self.t0, self.t1)) / n * 1e-9]
                for k, v in groups.items()]
        rows = [r for r in rows if r[1] > 0]
        return sorted(rows, key=lambda r: -r[1])[:top]

    def idle_gaps(self, top: int = 10):
        """[[host activity, seconds], ...]: the device's idle time in the
        window, each gap put down to the innermost host event under way at
        its middle on the benchmark's thread (chip 0), summed by that
        event's name, most first."""
        if not self.chips:
            return []
        busy = union(((o.start, o.end) for o in self.chips[0]),
                     self.t0, self.t1)
        gaps, t = [], self.t0
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = b
        if self.t1 > t:
            gaps.append((t, self.t1))
        # one sweep: events of one thread nest, so after popping those
        # that ended, the top of the stack is the innermost one open
        evs = sorted(self.main, key=lambda e: (e.start, -e.end))
        stack, j = [], 0
        sums: dict[str, float] = {}
        for a, b in gaps:
            mid = (a + b) / 2
            while j < len(evs) and evs[j].start <= mid:
                while stack and stack[-1].end <= evs[j].start:
                    stack.pop()
                stack.append(evs[j])
                j += 1
            while stack and stack[-1].end <= mid:
                stack.pop()
            name = stack[-1].name if stack else "(no host event)"
            sums[name] = sums.get(name, 0.0) + (b - a) * 1e-9
        return sorted(([k, v] for k, v in sums.items()),
                      key=lambda r: -r[1])[:top]


def _inside(e: Event, spans) -> bool:
    return any(a <= e.start and e.end <= b for a, b in spans)


def _label(o: Event, layers: dict) -> str:
    for layer, names in layers.items():
        for n in names:
            if matches(o, [n]):
                return f"{layer}:{n}"
    return f"other:{o.op}"


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        out[k] = v
    return out


def from_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to device ops and host
    events."""
    chips, host = [], []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX) and name[len(DEVICE_PREFIX):].isdigit():
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    st = _stats(e)
                    scope = next((str(st[k]) for k in SCOPE_STATS if k in st),
                                 "")
                    ops.append(Event(e.name, e.start_ns, e.end_ns, scope,
                                     line.name))
            chips.append((int(name[len(DEVICE_PREFIX):]), ops))
        elif name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    host.append(Event(e.name, e.start_ns, e.end_ns,
                                      line=line.name))
    return Trace([ops for _, ops in sorted(chips)], host)


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))
