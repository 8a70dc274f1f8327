"""Serve engine (models/serve.py): mean time from slot admission to the
first consumed delta of the requests whose first delta fell in the window
— the engine's ``first_token``: the padded prefill, the first decode
chunk and its fetch. With ``queue_wait_ms.serve`` it is the engine's
share of the time to first token; the client's adds the wire."""


def read(ctx):
    w = (ctx["counters"].get("phases") or {}).get("first_token")
    if not w or not w.get("count"):
        return None
    return 1e3 * w["total_s"] / w["count"]
