"""Device microseconds of the schedule per window: the union of the ops
of the schedule layer (``bench/layers.json``: the conflict, levels and
cross-window block Pallas kernels, found by name, and any op whose scope
path the trace gives as one of the ``protocol.*`` schedule scopes)."""


def read(trace, facts):
    s = trace.busy_s(facts["layers"]["schedule"])
    if not s or not facts["windows"]:
        return None
    return s / facts["windows"] * 1e6
