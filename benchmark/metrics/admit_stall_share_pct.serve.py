"""Serve engine (models/serve.py): the share of the loaded window that
admissions took — the time of the turns that hold an admission dispatch
(``turn_admit``) over one clean turn each, over the time of the turns
that held an admission or closed with a request waiting
(``turn_loaded``). Both sums stop where the offered load stops, so the
share does not move with how long the drain runs; the mean clean turn is
taken over every clean turn of the window."""

from benchmark.lib import phases


def read(ctx):
    admit = phases.window(ctx, "turn_admit")
    clean = phases.window(ctx, "turn_clean")
    loaded = phases.window(ctx, "turn_loaded")
    if admit is None or clean is None or loaded is None:
        return None
    return 100.0 * (admit[0] - admit[1] * clean[0] / clean[1]) / loaded[0]
