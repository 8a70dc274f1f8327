"""Serve engine (models/serve.py): decode steps issued and not yet fetched,
observed at every issue with the program just issued counted in
(``steps_in_flight``, a count carried beside the intervals: its
``total_s`` is a sum of steps) — the mean over the window's issues. It is
the queue an admission stands behind (``first_token_queued``) and what a
freed row idles through; the engine derives it from its own host time a
turn against the device's time a step, and ``device_starved_pct.serve``
says whether it is too small. None where the program observes no such
count (the parent of PR 42)."""

from benchmark.lib import phases


def read(ctx):
    w = phases.window(ctx, "steps_in_flight")
    return None if w is None else w[0] / w[1]
