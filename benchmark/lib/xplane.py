"""The reduction from a profiler trace (``*.xplane.pb``) to what the
per-layer readers use. Two halves:

- :func:`write_reduced` runs in the process that took the trace (it needs
  ``jax.profiler.ProfileData``): it keeps, per device plane, the op events
  of the "XLA Ops" line and the module events (by short name), and from
  the host planes only the benchmark's own ``bench.*`` annotations, as
  plain JSON;
- everything else is arithmetic on that JSON and imports no JAX, so the
  parent process and the CPU tests share it.

Times are nanoseconds on the profiler's clock, the same for host and
device planes of one trace.
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_KEEP = {OPS_LINE: "ops", MODULES_LINE: "modules"}
HOST_MARK = "bench."


def start(trace_dir: str) -> None:
    """Start a trace with the Python call tracer off: the benchmark's
    annotations are TraceMe events, and the call tracer would swamp the
    file and slow the host it measures."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    out = {"devices": [], "host": [],
           "lines_seen": {p.name: [ln.name for ln in p.lines]
                          for p in planes if p.name != "/host:CPU"}}
    for plane in planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if is_dev and line.name in _KEEP:
                # the event names are whole HLO instructions: keep the
                # short form, or a serving trace runs to tens of MB
                ev = [[short_name(e.name), int(e.start_ns),
                       int(e.duration_ns)] for e in line.events]
                dev = next((d for d in out["devices"]
                            if d["plane"] == plane.name), None)
                if dev is None:
                    dev = {"plane": plane.name, "ops": [], "modules": []}
                    out["devices"].append(dev)
                dev[_KEEP[line.name]] = ev
            elif not is_dev:
                out["host"] += [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events
                                if e.name.startswith(HOST_MARK)]
    out["host"].sort(key=lambda e: e[1])
    return out


def write_reduced(trace_dir: str, out_path: str) -> None:
    with open(out_path, "w") as f:
        json.dump(reduce_file(newest_xplane(trace_dir)), f)


# ------------------------------------------------------------- arithmetic
def union_ns(events) -> int:
    """Total time covered by at least one of ``[name, start, dur]``."""
    total, end = 0, None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if d <= 0:
            continue
        if end is None or s >= end:
            total, end = total + d, s + d
        elif s + d > end:
            total, end = total + s + d - end, s + d
    return total


def span_ns(events) -> tuple[int, int]:
    return (min(e[1] for e in events), max(e[1] + e[2] for e in events))


def busy_and_window_s(trace: dict) -> tuple[float, float]:
    """(seconds an op ran on the device, averaged over the device planes;
    length of the traced window: first op start to last op end over all
    devices)."""
    devs = [d for d in trace["devices"] if d["ops"]]
    if not devs:
        raise ValueError("the trace holds no device operation")
    lo = min(span_ns(d["ops"])[0] for d in devs)
    hi = max(span_ns(d["ops"])[1] for d in devs)
    busy = sum(union_ns(d["ops"]) for d in devs) / len(devs)
    return busy / 1e9, (hi - lo) / 1e9


MOSAIC = "tpu_custom_call"


def is_mosaic(name: str) -> bool:
    """A Pallas/Mosaic kernel call, as today's trace shows it: an HLO
    custom call whose target is ``tpu_custom_call``. The program names no
    kernel, so the calls differ only by the instruction name XLA gave
    them."""
    return MOSAIC in name


def short_name(name: str) -> str:
    """``%fusion.392 fusion`` or ``%closed_call.9 tpu_custom_call``: the
    event names on the "XLA Ops" line are whole HLO instructions."""
    head = name.split(" = ", 1)[0]
    if f'custom_call_target="{MOSAIC}"' in name:
        return f"{head} {MOSAIC}"
    m = re.search(r"[}\])] ?([a-z][\w\-]*)\(", name)
    return f"{head} {m.group(1)}" if m and " = " in name else head


def leaf_ops(ops) -> list:
    """The ops that enclose no other op: a ``while`` or a call region
    spans its body's ops on the same line and would count them twice."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    parent = [False] * len(ops)
    stack: list[int] = []
    for i in order:
        _, s, d = ops[i]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and d > 0 and s + d <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [op for op, p in zip(ops, parent) if not p]


def top_ops(trace: dict, n: int = 10) -> list:
    """[[name, seconds], ...] of device 0's leaf ops, summed by name."""
    total: dict[str, int] = {}
    for key, _, d in leaf_ops(trace["devices"][0]["ops"]):
        total[key] = total.get(key, 0) + d
    return [[k, v / 1e9] for k, v in sorted(
        total.items(), key=lambda kv: -kv[1])[:n]]


def module_events(trace: dict, prefix: str, device: int = 0) -> list:
    """The executions of the jitted program ``prefix`` (``jit_step``,
    ``jit_step_rows``): the "XLA Modules" line names them
    ``<name>(<fingerprint>)``."""
    return [e for e in trace["devices"][device]["modules"]
            if e[0].split("(")[0] == prefix]


def op_seconds(trace: dict, match, device: int = 0) -> float:
    """Summed duration of device ``device``'s ops whose name ``match``es
    (a predicate on the name)."""
    return sum(d for name, _, d in trace["devices"][device]["ops"]
               if match(name)) / 1e9


def idle_gaps(trace: dict, n: int = 10) -> list:
    """[[what the host was doing, seconds], ...]: device 0's idle time
    between ops, summed by the benchmark's host annotation that covers the
    middle of each gap ("unannotated" where none does)."""
    ops = sorted(trace["devices"][0]["ops"], key=lambda e: e[1])
    host = trace["host"]
    total: dict[str, int] = {}
    end = None
    for _, s, d in ops:
        if end is not None and s > end:
            mid = (s + end) // 2
            # the innermost (latest-started) annotation covering the gap
            label = "unannotated"
            for name, hs, hd in host:
                if hs > mid:
                    break
                if hs + hd >= mid:
                    label = name
            total[label] = total.get(label, 0) + s - end
        end = s + d if end is None else max(end, s + d)
    return [[k, v / 1e9] for k, v in sorted(
        total.items(), key=lambda kv: -kv[1])[:n]]
