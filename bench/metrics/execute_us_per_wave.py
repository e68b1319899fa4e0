"""Device microseconds of wave execution per wave: the union of the ops
of the wave-execution layer (``bench/layers.json``: the executors' wave
loop, the ``while`` op, with everything that runs inside it), over the
waves the engine reports for the traced intervals."""


def read(trace, facts):
    s = trace.busy_s(facts["layers"]["wave execution"])
    if not s or not facts["waves"]:
        return None
    return s / facts["waves"] * 1e6
