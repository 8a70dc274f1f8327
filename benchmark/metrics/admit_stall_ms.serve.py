"""Serve engine (models/serve.py): what admissions add to the gap between
two tokens of every live stream — the mean turn that holds at least one
admission dispatch (``turn_admit``) less the mean clean turn
(``turn_clean``): the admission programs' device time and whatever of the
host's half of them the device waited for."""

from benchmark.lib import phases


def read(ctx):
    admit = phases.mean_ms(ctx, "turn_admit")
    clean = phases.mean_ms(ctx, "turn_clean")
    if admit is None or clean is None:
        return None
    return admit - clean
