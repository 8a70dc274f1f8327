"""Cluster-wide metrics plane: the in-process registry and its codecs.

The reference ships per-task resource metrics from every executor to the AM
over a dedicated RPC (reference: TaskMonitor.java + MetricsRpc, surfaced in
the history server). This module is the TPU build's substrate for the same
capability, shared by every layer:

- producers (``models/train.py``, ``models/serve.py``,
  ``cluster/executor.py``, ``cluster/liveness.py``) observe into the
  process-wide default :class:`MetricsRegistry`;
- the executor's heartbeater serializes the registry with :func:`to_wire`
  and piggybacks it on each heartbeat (``rpc/client.py`` →
  ``rpc/server.py``);
- the coordinator keeps the last snapshot per task in a
  :class:`SnapshotTable` and folds the table into periodic
  ``METRICS_SNAPSHOT`` events in the jhist stream (``events/events.py``);
- the history server replays those events into Prometheus text exposition
  (:func:`render_prometheus`) and JSON (``history/server.py``).

Design constraints (this sits on the serve hot loop):

- **dependency-free** — stdlib only, importable from the executor, the
  coordinator, and user training processes alike;
- **O(1) per observation, no locks on read-mostly paths** — instrument
  lookup is a plain dict read; ``inc``/``observe`` take a per-instrument
  lock (a read-modify-write like ``+=`` is NOT GIL-atomic, so lock-free
  writers would silently lose concurrent increments; an uncontended
  acquire is ~100 ns). Snapshot/render READS stay lock-free — a reader
  may see a histogram's ``sum`` and ``count`` momentarily torn, which
  monitoring tolerates by design (telemetry, not accounting). The
  registry lock is taken only when an instrument is first created.
"""

from __future__ import annotations

import bisect
import json
import logging
import math
import os
import re
import threading
import time

log = logging.getLogger(__name__)

#: default histogram bucket bounds for wall-clock seconds (le-style,
#: +Inf implicit) — spans µs-scale registry costs to minute-scale steps
TIME_BUCKETS_S: tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

def parse_latency_buckets(spec: str) -> tuple[float, ...]:
    """Parse a ``tony.metrics.latency-buckets`` value — comma-separated
    upper bounds in seconds — into a histogram bucket ladder. Empty/
    blank means the built-in :data:`TIME_BUCKETS_S` (the pre-QoS
    bounds, so unconfigured deployments render identical series).
    Raises ``ValueError`` on anything malformed: non-numeric or
    non-finite bounds, non-positive bounds, or a non-strictly-increasing
    ladder — refused at CONFIG LOAD, because a bad ladder discovered at
    the first ``observe`` would take the serve loop down instead of the
    operator's deploy."""
    if not isinstance(spec, str):
        raise ValueError(f"latency buckets must be a string, got "
                         f"{type(spec).__name__}")
    if not spec.strip():
        return TIME_BUCKETS_S
    bounds = []
    for part in spec.split(","):
        try:
            b = float(part.strip())
        except ValueError:
            raise ValueError(
                f"bad latency bucket bound {part.strip()!r} "
                f"(want a number of seconds)") from None
        if not math.isfinite(b) or b <= 0.0:
            raise ValueError(
                f"latency bucket bounds must be finite and positive, "
                f"got {part.strip()!r}")
        bounds.append(b)
    for lo, hi in zip(bounds, bounds[1:]):
        if hi <= lo:
            raise ValueError(
                f"latency bucket bounds must be strictly increasing, "
                f"got {lo} before {hi}")
    return tuple(bounds)


_KIND_COUNTER = "counter"
_KIND_GAUGE = "gauge"
_KIND_HISTOGRAM = "histogram"


def _labels_key(labels: dict[str, str]) -> tuple:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class Counter:
    """Monotonically increasing value. ``inc`` locks per instrument —
    ``+=`` is a preemptible read-modify-write, and a lost increment is a
    permanent undercount on a counter; ``value`` reads lock-free."""

    __slots__ = ("name", "labels", "_lock", "_value")

    def __init__(self, name: str, labels: dict[str, str]) -> None:
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    """Last-set value (may go up or down). ``set`` is a single atomic
    store (no lock needed); ``inc`` read-modify-writes under a lock."""

    __slots__ = ("name", "labels", "_lock", "_value")

    def __init__(self, name: str, labels: dict[str, str]) -> None:
        self.name = name
        self.labels = labels
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, value: float) -> None:
        self._value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Fixed-bucket histogram (cumulative rendering happens at export).

    ``observe`` is one ``bisect`` + three increments under the
    per-instrument lock — O(log #buckets) with a handful of buckets,
    effectively O(1). Reads don't lock (sum/count may be torn)."""

    __slots__ = ("name", "labels", "buckets", "_lock", "_counts", "_sum",
                 "_count")

    def __init__(self, name: str, labels: dict[str, str],
                 buckets: tuple[float, ...] = TIME_BUCKETS_S) -> None:
        self.name = name
        self.labels = labels
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError("histogram needs at least one bucket bound")
        self._lock = threading.Lock()
        # one slot per finite bound plus the +Inf overflow slot
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._count = 0

    def observe(self, value: float) -> None:
        i = bisect.bisect_left(self.buckets, value)
        with self._lock:
            self._counts[i] += 1
            self._sum += value
            self._count += 1

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def cumulative(self) -> list[int]:
        """Per-bound cumulative counts (Prometheus ``le`` semantics),
        +Inf last."""
        out, running = [], 0
        for c in self._counts:
            running += c
            out.append(running)
        return out


def histogram_quantile(snapshot, q: float) -> float:
    """Bucket-interpolated quantile, Prometheus ``histogram_quantile``
    semantics.

    ``snapshot`` is either a live :class:`Histogram` or its wire dict
    (``{"b": bounds, "n": per-bucket counts (+Inf last), ...}``).  The
    target rank ``q * count`` is located in the cumulative bucket
    counts, then linearly interpolated between the bucket's bounds (the
    first bucket's lower bound is 0).  A rank landing in the +Inf bucket
    returns the highest finite bound (the classic prometheus caveat: an
    unbounded bucket has no interior to interpolate).  Empty histogram
    -> NaN.
    """
    if not 0.0 <= q <= 1.0:
        raise ValueError("quantile must be in [0, 1]")
    if isinstance(snapshot, dict):
        bounds = [float(b) for b in snapshot.get("b", [])]
        counts = [int(c) for c in snapshot.get("n", [])]
    else:
        bounds = list(snapshot.buckets)
        counts = list(snapshot._counts)
    if not bounds or len(counts) != len(bounds) + 1:
        return float("nan")
    total = sum(counts)
    if total == 0:
        return float("nan")
    rank = q * total
    running = 0.0
    for i, c in enumerate(counts):
        running += c
        if running >= rank and c > 0:
            if i >= len(bounds):          # +Inf bucket
                return bounds[-1]
            lower = bounds[i - 1] if i > 0 else 0.0
            upper = bounds[i]
            frac = (rank - (running - c)) / c
            return lower + (upper - lower) * frac
    return bounds[-1]


class MetricsRegistry:
    """Thread-safe instrument registry with get-or-create semantics.

    One metric NAME has one kind (and one help string and, for
    histograms, one bucket ladder); label sets distinguish series under
    it. Lookup of an existing instrument is a single dict read.
    """

    def __init__(self) -> None:
        self._instruments: dict[tuple, object] = {}
        self._meta: dict[str, tuple[str, str]] = {}   # name -> (kind, help)
        self._lock = threading.Lock()

    # -- get-or-create ------------------------------------------------------
    def _get(self, kind: str, name: str, help: str, labels: dict,
             factory, cls: type):
        key = (name, _labels_key(labels))
        inst = self._instruments.get(key)      # lock-free fast path
        if inst is not None:
            if type(inst) is not cls:
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{type(inst).__name__.lower()}, cannot use as {kind}")
            return inst
        with self._lock:
            inst = self._instruments.get(key)
            if inst is not None:
                return inst
            meta = self._meta.get(name)
            if meta is not None and meta[0] != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {meta[0]}, "
                    f"cannot re-register as {kind}")
            if meta is None or (help and not meta[1]):
                self._meta[name] = (kind, help)
            inst = factory()
            self._instruments[key] = inst
            return inst

    def counter(self, name: str, help: str = "", **labels) -> Counter:
        return self._get(_KIND_COUNTER, name, help, labels,
                         lambda: Counter(name, dict(labels)), Counter)

    def gauge(self, name: str, help: str = "", **labels) -> Gauge:
        return self._get(_KIND_GAUGE, name, help, labels,
                         lambda: Gauge(name, dict(labels)), Gauge)

    def histogram(self, name: str, help: str = "",
                  buckets: tuple[float, ...] = TIME_BUCKETS_S,
                  **labels) -> Histogram:
        return self._get(_KIND_HISTOGRAM, name, help, labels,
                         lambda: Histogram(name, dict(labels), buckets),
                         Histogram)

    def clear(self) -> None:
        with self._lock:
            self._instruments.clear()
            self._meta.clear()

    # -- snapshots ----------------------------------------------------------
    def to_wire(self) -> dict:
        """Compact, JSON-safe snapshot of every series (the heartbeat
        payload). Keys: ``c``/``g``/``h`` hold ``[name, {labels},
        value]`` triples (histogram value = ``{"b": bounds, "n":
        per-bucket counts, "s": sum, "c": count}``); ``m`` maps metric
        name to ``[kind, help]``."""
        c, g, h = [], [], []
        for (name, _), inst in list(self._instruments.items()):
            if isinstance(inst, Counter):
                c.append([name, inst.labels, inst.value])
            elif isinstance(inst, Gauge):
                g.append([name, inst.labels, inst.value])
            elif isinstance(inst, Histogram):
                h.append([name, inst.labels,
                          {"b": list(inst.buckets), "n": list(inst._counts),
                           "s": inst.sum, "c": inst.count}])
        return {"c": c, "g": g, "h": h,
                "m": {n: list(km) for n, km in self._meta.items()}}

    def to_wire_json(self) -> str:
        return json.dumps(self.to_wire(), separators=(",", ":"))


class NullRegistry(MetricsRegistry):
    """A registry whose instruments swallow every observation — the
    uninstrumented side of an overhead comparison."""

    class _Null:
        name = "null"
        labels: dict = {}
        value = 0.0
        count = 0
        sum = 0.0
        buckets: tuple = (1.0,)

        def inc(self, amount: float = 1.0) -> None: ...
        def set(self, value: float) -> None: ...
        def observe(self, value: float) -> None: ...
        def cumulative(self) -> list: return [0, 0]

    _NULL = _Null()

    def counter(self, name, help="", **labels): return self._NULL
    def gauge(self, name, help="", **labels): return self._NULL
    def histogram(self, name, help="", buckets=TIME_BUCKETS_S, **labels):
        return self._NULL
    def to_wire(self) -> dict:
        return {"c": [], "g": [], "h": [], "m": {}}


_default = MetricsRegistry()
_default_lock = threading.Lock()


def get_default() -> MetricsRegistry:
    """The process-wide registry every producer observes into."""
    return _default


def set_default(registry: MetricsRegistry) -> MetricsRegistry:
    """Swap the default registry (tests, overhead comparisons). Returns
    the previous one."""
    global _default
    with _default_lock:
        prev = _default
        _default = registry
    return prev


# ---------------------------------------------------------------------------
# Wire validation / decoding (coordinator + history-server side)
# ---------------------------------------------------------------------------
#: Prometheus-legal metric names / label keys. Enforced at ingest so one
#: task's bad name can never corrupt the exposition for the whole fleet
#: (a space or newline in a series name is a scrape-wide parse error).
_METRIC_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_KEY_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def _check_number(v, what: str) -> None:
    # bool is an int subclass; NaN/Infinity parse as valid JSON numbers
    # under json.loads' defaults — both would poison the exposition
    if isinstance(v, bool) or not isinstance(v, (int, float)) \
            or not math.isfinite(v):
        raise ValueError(f"non-finite or non-numeric {what}: {v!r}")


def validate_wire(wire: dict) -> dict:
    """Structurally validate a snapshot produced by :meth:`to_wire` —
    shape, element types, finiteness, and Prometheus-legal names/label
    keys, so anything that passes here renders cleanly. Raises
    ``ValueError`` on anything malformed; returns the dict."""
    if not isinstance(wire, dict):
        raise ValueError("snapshot is not an object")
    for kind in ("c", "g", "h"):
        entries = wire.get(kind, [])
        if not isinstance(entries, list):
            raise ValueError(f"snapshot[{kind!r}] is not a list")
        for e in entries:
            if (not isinstance(e, (list, tuple)) or len(e) != 3
                    or not isinstance(e[0], str)
                    or not isinstance(e[1], dict)):
                raise ValueError(f"malformed series entry: {e!r}")
            if not _METRIC_NAME_RE.match(e[0]):
                raise ValueError(f"illegal metric name: {e[0]!r}")
            for k, v in e[1].items():
                if not isinstance(k, str) or not _LABEL_KEY_RE.match(k):
                    raise ValueError(f"illegal label key: {k!r}")
                if not isinstance(v, (str, int, float, bool)):
                    raise ValueError(f"illegal label value: {v!r}")
            if kind == "h":
                v = e[2]
                if (not isinstance(v, dict)
                        or not isinstance(v.get("b"), list)
                        or not isinstance(v.get("n"), list)
                        or len(v["n"]) != len(v["b"]) + 1
                        or not all(isinstance(n, int)
                                   and not isinstance(n, bool) and n >= 0
                                   for n in v["n"])
                        or not isinstance(v.get("c"), int)
                        or isinstance(v.get("c"), bool)
                        or v["c"] < 0):
                    # element types matter: a non-numeric bound or count
                    # that slipped through here would crash the Prometheus
                    # renderer and 500 the whole /metrics scrape
                    raise ValueError(f"malformed histogram value: {v!r}")
                for b in v["b"]:
                    _check_number(b, "histogram bound")
                if v["b"] != sorted(v["b"]):
                    # Prometheus requires le-ordered buckets
                    raise ValueError(f"unsorted histogram bounds: {v['b']!r}")
                # .get: a MISSING "s" must be a ValueError here, not a
                # KeyError that escapes ingest's catch and fails the beat
                _check_number(v.get("s"), "histogram sum")
            else:
                _check_number(e[2], "series value")
    meta = wire.get("m", {})
    if not isinstance(meta, dict):
        raise ValueError("snapshot['m'] is not an object")
    for name, km in meta.items():
        # series_from_wire indexes km[1] — a non-sequence or non-string
        # meta value passing here would TypeError at render time and
        # 500 the whole scrape
        if (not isinstance(name, str)
                or not isinstance(km, (list, tuple)) or not km
                or not all(isinstance(x, str) for x in km)):
            raise ValueError(f"malformed meta entry: {name!r}: {km!r}")
    return wire


def from_wire_json(payload: str) -> dict:
    """Parse + validate a JSON heartbeat snapshot. Raises ValueError."""
    try:
        wire = json.loads(payload)
    except json.JSONDecodeError as e:
        raise ValueError(f"snapshot is not JSON: {e}") from e
    return validate_wire(wire)


class SnapshotTable:
    """Coordinator-side table of each task's LAST metrics snapshot.

    ``ingest`` never raises — a malformed snapshot from one executor must
    not kill the coordinator's heartbeat handler (it is logged and
    dropped; the previous good snapshot, if any, is kept)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._by_task: dict[str, dict] = {}
        self._rejects = 0

    def ingest(self, task_id: str, payload: str | dict) -> bool:
        try:
            wire = (validate_wire(payload) if isinstance(payload, dict)
                    else from_wire_json(payload))
        except (ValueError, TypeError):
            with self._lock:        # gRPC handler threads race here
                self._rejects += 1
            log.warning("dropping malformed metrics snapshot from %s",
                        task_id, exc_info=True)
            return False
        with self._lock:
            self._by_task[task_id] = wire
        return True

    def clear(self) -> None:
        with self._lock:
            self._by_task.clear()

    @property
    def rejected(self) -> int:
        return self._rejects

    def tasks(self) -> list[str]:
        with self._lock:
            return sorted(self._by_task)

    def get(self, task_id: str) -> dict | None:
        with self._lock:
            return self._by_task.get(task_id)

    def as_payload(self) -> dict[str, dict]:
        """{task_id: wire snapshot} — the METRICS_SNAPSHOT event body."""
        with self._lock:
            return dict(self._by_task)


# ---------------------------------------------------------------------------
# Bridges from existing instrumentation
# ---------------------------------------------------------------------------
def observe_phase_times(phase_times, registry: MetricsRegistry | None = None,
                        prefix: str = "tony_serve_phase") -> None:
    """Fold a :class:`tony_tpu.runtime.profiler.PhaseTimes` summary into
    the registry: per phase, ``<prefix>_seconds_total`` (host wall spent)
    and ``<prefix>_ops_total`` (times entered) counters, labeled
    ``phase=<name>``. Called once per ``serve()`` — each call ADDS that
    call's accumulation, so the counters stay monotonic across calls."""
    reg = registry or get_default()
    for phase, row in phase_times.summary().items():
        reg.counter(f"{prefix}_seconds_total",
                    help="host wall seconds per serve-loop phase",
                    phase=phase).inc(row["total_s"])
        reg.counter(f"{prefix}_ops_total",
                    help="serve-loop phase entries", phase=phase).inc(
                        row["count"])


_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096
_CLK_TCK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100
_PROCESS_START = time.monotonic()


def sample_host_stats(registry: MetricsRegistry | None = None) -> None:
    """Sample this process's /proc stats into gauges: RSS bytes, CPU
    seconds (user+sys, cumulative), and process uptime. No-op (uptime
    only) where /proc is unavailable."""
    reg = registry or get_default()
    reg.gauge("tony_process_uptime_seconds",
              help="seconds since this process imported the metrics "
                   "module").set(time.monotonic() - _PROCESS_START)
    try:
        with open("/proc/self/stat", "rb") as f:
            stat = f.read().decode("ascii", "replace")
        # fields after the parenthesized comm (which may contain spaces)
        rest = stat.rsplit(")", 1)[1].split()
        utime, stime = int(rest[11]), int(rest[12])   # fields 14/15
        rss_pages = int(rest[21])                      # field 24
        reg.gauge("tony_process_cpu_seconds",
                  help="cumulative user+system CPU seconds").set(
                      (utime + stime) / float(_CLK_TCK))
        reg.gauge("tony_process_rss_bytes",
                  help="resident set size in bytes").set(
                      rss_pages * _PAGE_SIZE)
    except (OSError, IndexError, ValueError):
        pass                         # non-Linux / constrained container
    try:
        # Open-fd count next to RSS/CPU: the cheap early-warning for the
        # launch-path fd-leak class (a leaked pipe per task launch grows
        # this linearly with restarts).
        reg.gauge("tony_task_open_fds",
                  help="open file descriptors in this process").set(
                      len(os.listdir("/proc/self/fd")))
    except OSError:
        pass                         # non-Linux / constrained container


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------
def _escape_label(value: str) -> str:
    return (str(value).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape_label(v)}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _fmt_value(v: float) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


def series_from_wire(wire: dict, extra_labels: dict[str, str] | None = None,
                     ) -> list[tuple]:
    """Flatten a wire snapshot into ``(kind, name, labels, value, help)``
    entries, merging ``extra_labels`` (e.g. ``{"job": app_id, "task":
    task_id}``) into each series — the exporter-side join that keeps
    per-task series distinct in a fleet-wide scrape."""
    extra = dict(extra_labels or {})
    meta = wire.get("m", {})
    out = []
    for kind_key, kind in (("c", _KIND_COUNTER), ("g", _KIND_GAUGE),
                           ("h", _KIND_HISTOGRAM)):
        for name, labels, value in wire.get(kind_key, []):
            m = meta.get(name, [kind, ""])
            out.append((kind, name, {**labels, **extra}, value,
                        m[1] if len(m) > 1 else ""))
    return out


def render_prometheus(entries: list[tuple]) -> str:
    """Render ``(kind, name, labels, value, help)`` entries as Prometheus
    text exposition (format 0.0.4): one ``# HELP``/``# TYPE`` pair per
    metric name, histogram expansion to ``_bucket``/``_sum``/``_count``,
    duplicate series dropped (last write wins)."""
    by_name: dict[str, list] = {}
    kinds: dict[str, str] = {}
    helps: dict[str, str] = {}
    for kind, name, labels, value, help_ in entries:
        if kinds.setdefault(name, kind) != kind:
            log.warning("metric %s seen as both %s and %s — keeping %s",
                        name, kinds[name], kind, kinds[name])
            continue
        if help_ and not helps.get(name):
            helps[name] = help_
        # duplicate-series guard: same (name, labels) keeps the LAST value
        bucket = by_name.setdefault(name, [])
        key = _labels_key(labels)
        bucket[:] = [(k, l, v) for (k, l, v) in bucket
                     if _labels_key(l) != key]
        bucket.append((kind, labels, value))
    lines = []
    for name in sorted(by_name):
        kind = kinds[name]
        help_txt = (helps.get(name) or name).replace(
            "\\", "\\\\").replace("\n", "\\n")
        lines.append(f"# HELP {name} {help_txt}")
        lines.append(f"# TYPE {name} {kind}")
        for _, labels, value in by_name[name]:
            if kind == _KIND_HISTOGRAM:
                bounds = value["b"]
                running = 0
                for bound, n in zip(bounds + [float("inf")], value["n"]):
                    running += n
                    le = "+Inf" if bound == float("inf") else _fmt_value(bound)
                    lines.append(
                        f"{name}_bucket"
                        f"{_fmt_labels({**labels, 'le': le})}"
                        f" {running}")
                lines.append(f"{name}_sum{_fmt_labels(labels)}"
                             f" {_fmt_value(value['s'])}")
                lines.append(f"{name}_count{_fmt_labels(labels)}"
                             f" {value['c']}")
            else:
                lines.append(f"{name}{_fmt_labels(labels)}"
                             f" {_fmt_value(value)}")
    return "\n".join(lines) + ("\n" if lines else "")


def render_registry(registry: MetricsRegistry | None = None,
                    extra_labels: dict[str, str] | None = None) -> str:
    """Prometheus text for a live in-process registry."""
    reg = registry or get_default()
    return render_prometheus(series_from_wire(reg.to_wire(), extra_labels))
