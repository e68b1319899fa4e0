"""Device microseconds of wave execution per wave: the union of the ops
of the wave-execution layer (``bench/layers.json``: the executors' wave
loop, the ``while`` op, with everything that runs inside it), over the
waves the engine reports for the traced intervals. Where the windows run
in a device loop of their own (a ``while`` holding the wave loops), only
the ``while`` ops nested in another count, so the schedule and the rest
of the window loop stay out."""


def read(trace, facts):
    s = trace.busy_s(facts["layers"]["wave execution"], inner_loops=True)
    if not s or not facts["waves"]:
        return None
    return s / facts["waves"] * 1e6
