"""Serve engine (models/serve.py): mean wait for a slot of the requests
ADMITTED in the window — the engine's ``queue_wait`` (entering the wait
queue -> slot admission, observed at each admission). A wait, not a loop
phase: waits of different requests overlap."""


def read(ctx):
    w = (ctx["counters"].get("phases") or {}).get("queue_wait")
    if not w or not w.get("count"):
        return None
    return 1e3 * w["total_s"] / w["count"]
