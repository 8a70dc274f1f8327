"""Serving wire (serving/server.py): engine-thread time per decode chunk
spent in the ``on_delta`` / ``on_retired`` callbacks — packing frames and
sending them on the sockets — the engine's ``emit`` phase over the chunks
dispatched in the window."""


def read(ctx):
    ph = ctx["counters"].get("phases") or {}
    if "emit" not in ph or not ph.get("dispatch", {}).get("count"):
        return None
    return 1e3 * ph["emit"]["total_s"] / ph["dispatch"]["count"]
