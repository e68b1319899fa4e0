"""Share of the traced window in which no op ran on the device."""


def read(trace, facts):
    busy = trace.busy_s()
    if busy is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / trace.window_s)
