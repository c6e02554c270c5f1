"""A counter of the program that a program before it may not have:
``counter``'s arg and reading; where the object, method or key is not
there, nothing is read (None) and the metric is left out of the line.
"""

from benchmark.readers import counter


def begin(arg, obs):
    try:
        return counter.begin(arg, obs)
    except (ImportError, AttributeError, KeyError, TypeError):
        return None


def read(arg, obs, begun):
    return counter.read(arg, obs, begun)
