"""Serve engine (models/serve.py): the share of the loaded window in which
the device had nothing to run and the host was why — ``starved``, observed
at every enqueue that finds nothing enqueued before it still unfetched
(the time since the last fetch returned), over the time of the loaded
turns (``turn_loaded``). A program with turns and no starved enqueue in
the window reads 0."""

from benchmark.lib import phases


def read(ctx):
    loaded = phases.window(ctx, "turn_loaded")
    if loaded is None:
        return None
    starved = phases.window(ctx, "starved")
    return 100.0 * (starved[0] if starved else 0.0) / loaded[0]
