"""Task runtime (runtime/compile_cache.py): compile requests that the
persistent cache did not serve, over the whole life of the process that
held the chip (``compile_cache.stats()``: requests - hits)."""


def read(ctx):
    k = ctx["counters"]
    if "compile_requests" not in k:
        return None
    return k["compile_requests"] - k["compile_hits"]
