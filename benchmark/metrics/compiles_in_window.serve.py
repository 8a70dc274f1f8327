"""Task runtime: compile requests made between the two edges of the
measured window (the same counter's change). Should be 0."""


def read(ctx):
    return ctx["counters"].get("compile_requests_in_window")
