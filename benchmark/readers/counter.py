"""A counter of the program, read before and after the window.

arg: ``object`` "module:name" (a function to call or an object),
``with_session`` (call the function with the session), ``method`` (then
call this method), ``key`` (then take this key), ``per`` ("query" divides
the delta by the window's queries).
"""

import importlib


def _value(arg, obs):
    module, _, name = arg["object"].partition(":")
    x = getattr(importlib.import_module(module), name)
    if arg.get("with_session"):
        x = x(obs.session)
    elif callable(x) and not arg.get("method"):
        x = x()
    if arg.get("method"):
        x = getattr(x, arg["method"])()
    if arg.get("key") is not None:
        x = x[arg["key"]]
    return float(x)


def begin(arg, obs):
    return _value(arg, obs)


def read(arg, obs, begun):
    if begun is None:
        return None
    delta = _value(arg, obs) - begun
    if arg.get("per") == "query":
        return delta / obs.n_queries if obs.n_queries else None
    return delta
