"""Events JAX's own monitoring reports inside the window.

arg: ``event`` (a ``jax.monitoring`` duration event).  The backend
compile event fires for every program JAX had to compile or load from
its persistent cache: inside a window that was warmed up there is none.
"""

import jax.monitoring


def begin(arg, obs):
    seen = []

    def listener(event, duration, **kw):
        if event == arg["event"]:
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def read(arg, obs, begun):
    if begun is None:
        return None
    # the listener outlives the window; count what it saw up to now
    obs.notes["compile_seconds_in_window"] = float(sum(begun))
    return float(len(begun))
