"""Load generator (the benchmark's own): 95th percentile of send time
minus due time — a starved generator must not read as a fast server."""

from benchmark.lib.stats import percentile


def read(ctx):
    late = ctx["counters"].get("gen_late_s")
    if not late:
        return None
    return 1e3 * percentile(late, 95)
