"""Host microseconds in JAX's dispatch of the engine's jitted calls
(``PjitFunction(...)`` events inside the intervals), per window."""


def read(trace, facts):
    s = trace.dispatch_s()
    if not s or not facts["windows"]:
        return None
    return s / facts["windows"] * 1e6
