"""Train loop (models/loop.py): mean of ``tony_data_wait_seconds`` over the
window's steps — how long a step waited for its batch."""


def read(ctx):
    k = ctx["counters"]
    if not k.get("data_wait_n"):
        return None
    return 1e3 * k["data_wait_s"] / k["data_wait_n"]
