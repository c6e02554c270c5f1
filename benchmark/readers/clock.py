"""A time the harness took itself, on the host's clock.

arg: ``clock`` (a key of ``obs.clock``: seconds), ``scale`` (1000 for
ms), ``per`` ("query").
"""


def read(arg, obs, begun):
    value = obs.clock.get(arg["clock"])
    if value is None:
        return None
    value *= arg.get("scale", 1)
    if arg.get("per") == "query":
        return value / obs.n_queries if obs.n_queries else None
    return value
