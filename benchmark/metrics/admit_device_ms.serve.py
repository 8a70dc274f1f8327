"""Serve engine (models/serve.py): mean device time of ONE admission
dispatch in the traced window — the ``jit_admit_rows`` executions (a
prefill of the dispatch's rows x the bucket, landed in the freed slots),
their summed device time over their count. Beside
``admit_device_share_pct.serve`` (the same executions over the window) it
tells a dispatch that got cheaper from one that got rarer. None where the
trace holds no admission."""

from benchmark.lib import xplane


def read(ctx):
    admits = xplane.module_events(ctx["trace"], "jit_admit_rows")
    if not admits:
        return None
    return sum(e[2] for e in admits) / 1e6 / len(admits)
