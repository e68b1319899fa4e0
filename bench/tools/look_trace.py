"""Print what a profiler trace holds: planes, lines, event counts, the
first events of each line with their stats, and the device ops and
scopes that took most time. For reading a trace by hand.

    python bench/tools/look_trace.py <trace dir or .xplane.pb> [events per line]
"""
import collections
import os
import sys


def main(path: str, n: int = 8) -> None:
    from jax.profiler import ProfileData

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from bench import xplane

    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:n]:
                st = {k: (str(v)[:160]) for k, v in e.stats}
                print(f"    {e.name[:100]!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} {st}")
            if plane.name.startswith("/device") and evs:
                by = collections.Counter()
                for e in evs:
                    by[e.name[:80]] += e.duration_ns
                print("    top by time:", by.most_common(12))
    t = xplane.from_profile(pd)
    print("window", t.t0, t.t1, t.window_s, "intervals", len(t.intervals))
    print("busy_s", t.busy_s())
    scopes = collections.Counter()
    for ops in t.chips:
        for o in ops:
            parts = [p for p in o.scope.split("/") if p.startswith("protocol.")]
            scopes[parts[-1] if parts else "(none)"] += o.end - o.start
    print("device ns by innermost protocol scope:", scopes.most_common())
    disp = collections.Counter(e.name for e in t.host
                               if e.name.startswith(xplane.DISPATCH_PREFIX))
    print("dispatch events:", disp.most_common(20))
    print("dispatch_s", t.dispatch_s())


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 8)
