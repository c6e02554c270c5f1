"""A span point of the program's own tracing (``utils/tracing`` rollup,
armed by ``spark.rapids.tpu.trace.enabled`` in the traced run).

arg: ``point`` (the span's name), ``field`` (``exclusiveMs``, ``ms`` or
``count``), ``per`` ("query").  Summed over the rollups the session left
after each query of the window.
"""


def read(arg, obs, begun):
    total, seen = 0.0, False
    for d in obs.done:
        points = (d.spans or {}).get("points") or {}
        if arg["point"] in points:
            total += float(points[arg["point"]][arg["field"]])
            seen = True
    if not seen:
        return None
    if arg.get("per") == "query":
        return total / obs.n_queries
    return total
