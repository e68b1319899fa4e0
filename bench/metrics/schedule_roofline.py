"""The schedule's share of its roofline: the least time the chip needs
for a window's record (``bench/workcount.py``: compares at the calibrated
VPU rate, or bytes at HBM bandwidth, whichever is longer, summed over
the traced windows, each counted at the tasks it holds) over the device
time the schedule took."""
from bench import workcount


def read(trace, facts):
    s = trace.busy_s(facts["layers"]["schedule"])
    if not s or not facts["windows"]:
        return None
    nr, nw = facts["footprint"]
    work = {"compares": 0, "relaxations": 0, "bytes": 0}
    for size, windows in facts["window_sizes"]:
        one = workcount.schedule_work(size, nr, nw, facts["overlap"])
        for k in work:
            work[k] += one[k] * windows
    least = workcount.least_seconds(work, facts["peaks"])
    if least is None:
        return None
    return 100.0 * least[0] / s
