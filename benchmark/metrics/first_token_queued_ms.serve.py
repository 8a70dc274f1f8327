"""Serve engine (models/serve.py): of admission -> first token, the part
the admission stood behind the decode chunk already in flight — from the
admission to that chunk's fetch returning (``first_token_queued``; 0 for
an admission that found the device queue empty), per request whose first
delta fell in the window."""

from benchmark.lib import phases


def read(ctx):
    return phases.mean_ms(ctx, "first_token_queued")
