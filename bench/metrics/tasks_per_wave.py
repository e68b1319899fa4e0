"""Tasks per executed wave over the traced intervals, from the engine's
own exact counts (its ``mean_parallelism``)."""


def read(trace, facts):
    if not facts["waves"]:
        return None
    return facts["tasks"] / facts["waves"]
