"""Serve engine: output tokens the clients kept over decode steps executed
x slots, in the window."""


def read(ctx):
    k = ctx["counters"]
    if not k.get("steps_executed"):
        return None
    return k["tokens_kept"] / (k["steps_executed"] * ctx["mix"]["slots"])
