"""What the device itself reports after the window.

arg: ``stat`` (a key of ``memory_stats()``); the fullest of the chips
the cell uses.
"""


def read(arg, obs, begun):
    values = [s.get(arg["stat"]) for s in obs.memory_stats if s]
    values = [v for v in values if v is not None]
    return float(max(values)) if values else None
