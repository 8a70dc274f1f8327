"""What a window's ``phases`` hold of one name: the serve driver
differences every key of the program's ``PhaseTimes.summary()`` across the
window into ``ctx["counters"]["phases"]`` (``total_s``, ``count``), the
loop's phases and the intervals it observes alike. JAX-free."""

from __future__ import annotations


def window(ctx: dict, name: str):
    """(seconds, count) of ``name`` over the window; None where the
    program has no such phase or it was never entered in the window."""
    w = (ctx["counters"].get("phases") or {}).get(name)
    if not w or not w.get("count"):
        return None
    return w["total_s"], w["count"]


def mean_ms(ctx: dict, name: str):
    """Mean length of one ``name`` in the window, in ms; None as
    :func:`window`."""
    w = window(ctx, name)
    return None if w is None else 1e3 * w[0] / w[1]
