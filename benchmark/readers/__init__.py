"""Readers: one small module per kind of per-layer metric.

A reader has ``read(arg, obs, begun)`` and may have ``begin(arg, obs)``,
which the harness calls just before the window and whose result comes
back as ``begun``.  ``arg`` is the metric's own file's ``arg``; ``obs``
is the run's ``harness.observe.Observations``.  A reader that finds
nothing to read returns None and the metric is left out of the line.
"""
