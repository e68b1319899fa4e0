"""Seconds of XLA compilation in set-up, loads from the persistent cache
included (JAX's ``backend_compile_duration`` events)."""


def read(trace, facts):
    return facts["compile_s"]
