"""Serve engine (models/serve.py): host time per decode chunk — the
``PhaseTimes`` totals of dispatch + admit + retire over the window, per
chunk dispatched in it."""


def read(ctx):
    ph = ctx["counters"].get("phases")
    if not ph or not ph.get("dispatch", {}).get("count"):
        return None
    total = sum(ph.get(p, {}).get("total_s", 0.0)
                for p in ("dispatch", "admit", "retire"))
    return 1e3 * total / ph["dispatch"]["count"]
