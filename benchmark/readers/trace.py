"""A number of the device trace as ``trace/reduce.py`` reduced it.

arg: ``field`` of the reduction; ``per`` "traced_query" divides by the
queries the trace covered; ``scale``.
"""


def read(arg, obs, begun):
    t = obs.trace
    if not t or not obs.traced:
        return None
    value = t.get(arg["field"])
    if value is None:
        return None
    value *= arg.get("scale", 1)
    if arg.get("per") == "traced_query":
        value /= len(obs.traced)
    return value
