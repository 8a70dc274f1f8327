"""Serve engine (models/serve.py): how long a free slot and a runnable
request both stood waiting for the loop — at each admission, the
admission's time less the later of the return of the chunk whose
consumption freed the slot and the request's entering the wait queue
(``slot_vacant``): the step utilization's loss, in time, where it is
lost."""

from benchmark.lib import phases


def read(ctx):
    return phases.mean_ms(ctx, "slot_vacant")
