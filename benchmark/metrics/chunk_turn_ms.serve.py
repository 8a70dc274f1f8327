"""Serve engine (models/serve.py): mean length of a CLEAN turn — from one
fetch's return to the next where the chunk was enqueued before the first
returned and no admission dispatch lies between the two chunks
(``turn_clean``). While the device bounds the loop that is one decode
chunk's own time on the device, measured on the host's clock over the
whole window with no capture running."""

from benchmark.lib import phases


def read(ctx):
    return phases.mean_ms(ctx, "turn_clean")
