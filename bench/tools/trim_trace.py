"""Cut a profiler trace down to a few intervals, for a test fixture.

Keeps the device ops and host events that start within the first
``n`` ``bench.interval`` spans of the trace (and those spans), with the
stats the reduction reads, and writes them as a new, small XSpace.

    python bench/tools/trim_trace.py <trace dir or .xplane.pb> <out.xplane.pb> [n]
"""
from __future__ import annotations

import os
import sys

#: device lines kept (the rest only make the fixture larger)
KEEP_DEVICE_LINES = ("XLA Ops", "XLA Modules")
#: stats kept on each event
KEEP_STATS = ("tf_op", "name_scope", "long_name", "hlo_category")


def _q(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def text_proto(planes) -> str:
    """XSpace text proto from ``[(plane name, [(line name, [(event name,
    start ns, duration ns, {stat: str}), ...]), ...]), ...]``."""
    out = []
    for pid, (pname, lines) in enumerate(planes, 1):
        ev_meta, st_meta, body = {}, {}, []
        for lid, (lname, events) in enumerate(lines, 1):
            t0 = min((e[1] for e in events), default=0)
            evs = []
            for name, start, dur, stats in events:
                mid = ev_meta.setdefault(name, len(ev_meta) + 1)
                st = "".join(
                    f" stats {{ metadata_id: "
                    f"{st_meta.setdefault(k, len(st_meta) + 1)} "
                    f"str_value: {_q(str(v))} }}"
                    for k, v in stats.items())
                evs.append(f"  events {{ metadata_id: {mid} offset_ps: "
                           f"{int(round((start - t0) * 1000))} duration_ps: "
                           f"{int(round(dur * 1000))}{st} }}")
            body.append(f" lines {{ id: {lid} name: {_q(lname)} "
                        f"timestamp_ns: {int(t0)}\n" + "\n".join(evs) + " }")
        meta = [f" event_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} }} }}"
                for n, i in ev_meta.items()]
        meta += [f" stat_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} }} }}"
                 for n, i in st_meta.items()]
        out.append(f"planes {{ id: {pid} name: {_q(pname)}\n"
                   + "\n".join(body + meta) + "\n}")
    return "\n".join(out) + "\n"


def serialize(planes) -> bytes:
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(text_proto(planes))


def trim(path: str, n: int):
    """Planes of the trace at ``path`` cut to its first ``n`` intervals."""
    from jax.profiler import ProfileData

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from bench import xplane

    pd = ProfileData.from_file(path)
    spans = sorted((e.start_ns, e.end_ns) for p in pd.planes
                   if p.name == xplane.HOST_PLANE for line in p.lines
                   for e in line.events if e.name == xplane.INTERVAL)[:n]
    lo, hi = spans[0][0], spans[-1][1]
    planes = []
    for p in pd.planes:
        device = p.name.startswith(xplane.DEVICE_PREFIX)
        if not device and p.name != xplane.HOST_PLANE:
            continue
        lines = []
        for line in p.lines:
            if device and line.name not in KEEP_DEVICE_LINES:
                continue
            if not device and not any(e.name == xplane.INTERVAL
                                      for e in line.events):
                continue  # host: the benchmark's own thread only
            evs = [(e.name, e.start_ns, e.duration_ns,
                    {k: v for k, v in e.stats if k in KEEP_STATS})
                   for e in line.events if lo <= e.start_ns <= hi]
            if evs:
                lines.append((line.name, evs))
        if lines:
            planes.append((p.name, lines))
    return planes


def main(src: str, dst: str, n: int = 2) -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from bench import xplane

    if os.path.isdir(src):
        src = xplane.find_xplane(src)
    data = serialize(trim(src, n))
    with open(dst, "wb") as f:
        f.write(data)
    print(f"{dst}: {len(data)} bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]) if len(sys.argv) > 3 else 2)
