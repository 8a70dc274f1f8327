"""Serve engine (models/serve.py): of admission -> first token, the part
after the chunk in flight returned — the admission on the device, the
chunk the first token rides and its consumption (``first_token_ride``).
With ``first_token_queued_ms.serve`` it sums to
``admit_to_first_token_ms.serve``."""

from benchmark.lib import phases


def read(ctx):
    return phases.mean_ms(ctx, "first_token_ride")
