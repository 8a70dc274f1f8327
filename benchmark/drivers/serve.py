"""Driver of the serving mixes: a replica child (``jobs/serve_replica.py``)
holds the chip; this JAX-free process is its client over the program's
``StreamingClient`` and the load generator.

``loop: open`` offers the mix's fixed rate on a seeded schedule and times
every request from when it was DUE; ``loop: closed`` keeps ``clients``
requests in flight. All latencies are taken here, at the client, from the
arrival of each TOKENS frame's event.
"""

from __future__ import annotations

import json
import os
import queue
import random
import subprocess
import sys
import threading
import time

from benchmark.lib import traffic
from benchmark.lib.procs import Children, fail
from benchmark.lib.stats import percentile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPLICA = os.path.join(BENCH, "jobs", "serve_replica.py")


class Replica:
    """The child and its line protocol."""

    def __init__(self, children, *, cell, seed, trace, out, env, platform,
                 fault, script=REPLICA) -> None:
        self.log = os.path.join(out, "replica.log")
        argv = [sys.executable, script, "--config", cell["config"], "--traffic", cell["traffic"],
                "--seed", str(seed), "--trace", str(trace),
                "--platform", platform, "--out", out]
        if fault:
            argv += ["--fault", fault]
        self.proc = children.spawn(argv, env=env, cwd=out,
                                   log_path=self.log, stdin=subprocess.PIPE,
                                   stdout=subprocess.PIPE, text=True)
        self._events: queue.Queue = queue.Queue()
        threading.Thread(target=self._read, name="bench-replica-out",
                         daemon=True).start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            if line.startswith('{"event"'):
                self._events.put(json.loads(line))
        self._events.put({"event": "eof"})

    def expect(self, event: str, timeout: float) -> dict:
        try:
            msg = self._events.get(timeout=timeout)
        except queue.Empty:
            fail(f"replica: no {event!r} within {timeout:.0f} s", self.log)
        if msg["event"] != event:
            fail(f"replica: wanted {event!r}, got {msg['event']!r}",
                 self.log)
        return msg

    def ask(self, cmd: str, event: str, timeout: float = 120.0, **kw):
        self.proc.stdin.write(json.dumps(dict(kw, cmd=cmd)) + "\n")
        self.proc.stdin.flush()
        return self.expect(event, timeout)

    def close(self) -> None:
        self.proc.stdin.write('{"cmd": "exit"}\n')
        self.proc.stdin.flush()
        self.proc.wait(timeout=120)


class Stream:
    """One request's life at the client."""

    __slots__ = ("req", "due", "sent", "arrivals", "tokens", "reason")

    def __init__(self, req: dict, due: float) -> None:
        self.req, self.due = req, due
        self.sent = None
        self.arrivals: list[tuple[float, int]] = []   # (time, n tokens)
        self.tokens: list[int] = []
        self.reason = None                            # terminal event

    def follow(self, client, rid: int, timeout: float) -> None:
        try:
            while True:
                ev = client.next_event(rid, timeout=timeout)
                now = time.perf_counter()
                if ev[0] == "tokens":
                    self.arrivals.append((now, len(ev[1])))
                    self.tokens.extend(ev[1])
                else:
                    self.reason = ev[1] if ev[0] == "retired" else ev[0]
                    return
        except queue.Empty:
            self.reason = "timeout"

    @property
    def ok(self) -> bool:
        return (self.reason == "budget"
                and len(self.tokens) == self.req["max_new_tokens"])


def _submit(client, stream: Stream, threads: list, timeout: float) -> None:
    stream.sent = time.perf_counter()
    rid = client.submit(stream.req["prompt"], stream.req["max_new_tokens"])
    th = threading.Thread(target=stream.follow, args=(client, rid, timeout),
                          name=f"bench-stream-{rid}", daemon=True)
    th.start()
    threads.append(th)


def warm_up(client, c, mix, seed) -> None:
    """One request per admission bucket the mix can reach (powers of two
    from the shortest to the longest prompt), and enough tokens for two
    decode chunks: every program the window uses, compiled or fetched
    from the cache, before it opens."""
    rs = random.Random(seed)
    n = mix["prompt_tokens"]["min"]
    lengths = []
    while n <= mix["prompt_tokens"]["max"]:
        lengths.append(n)
        n *= 2
    streams, threads = [], []
    for n in lengths:
        s = Stream({"prompt": [rs.randrange(c["vocab_size"])
                               for _ in range(n)],
                    "max_new_tokens": 20}, 0.0)
        _submit(client, s, threads, timeout=900.0)
        streams.append(s)
    for th in threads:
        th.join()
    bad = [s.reason for s in streams if not s.ok]
    if bad:
        raise RuntimeError(f"warm-up requests ended {bad}")


def open_loop(client, reqs, due, t0, drain_s) -> list[Stream]:
    streams, threads = [], []
    for req, d in zip(reqs, due):
        s = Stream(req, t0 + float(d))
        wait = s.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        _submit(client, s, threads, timeout=drain_s + 60.0)
        streams.append(s)
    deadline = time.perf_counter() + drain_s
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.perf_counter()))
    return streams


def closed_loop(client, reqs, clients, t0, seconds) -> list[Stream]:
    """``clients`` workers, each sending its next request when its last
    ends, until the window closes; a request in flight then is left to
    finish (its later tokens fall outside the window's count)."""
    pool = iter(reqs)
    lock = threading.Lock()
    done: list[Stream] = []
    t_end = t0 + seconds

    def worker() -> None:
        while time.perf_counter() < t_end:
            with lock:
                req = next(pool, None)
            if req is None:
                return
            s = Stream(req, time.perf_counter())
            s.sent = s.due
            rid = client.submit(req["prompt"], req["max_new_tokens"])
            s.follow(client, rid, timeout=max(1.0, t_end + 5.0
                                              - time.perf_counter()))
            with lock:
                done.append(s)

    workers = [threading.Thread(target=worker, name=f"bench-client-{i}",
                                daemon=True) for i in range(clients)]
    for th in workers:
        th.start()
    for th in workers:
        th.join(timeout=seconds + 30.0)
    return done


def latencies(streams, t_end) -> dict:
    ttft, gaps, in_window = [], [], 0
    gave_up = time.perf_counter()
    for s in streams:
        for t, n in s.arrivals:
            if t <= t_end:
                in_window += n
        # a request that failed or never finished misses any limit: it
        # counts with the time it had waited when the run gave it up
        ttft.append((s.arrivals[0][0] if s.ok else gave_up) - s.due)
        if not s.ok:
            continue
        for i, (t, n) in enumerate(s.arrivals):
            if i:
                gaps.append(t - s.arrivals[i - 1][0])
            gaps.extend([0.0] * (n - 1))
    return {"ttft": ttft, "gaps": gaps, "tokens_in_window": in_window}


def offer(rep, client, *, c, mix, seed, seconds, trace):
    """The measured window: the mix's load between two snapshots of the
    replica's counters. Returns (streams, before, after, start of the
    window on ``time.time`` and on ``perf_counter``)."""
    if mix["loop"] == "open":
        due = traffic.poisson_due_times(mix, seconds)
        reqs = traffic.requests(mix, seed, len(due), c["vocab_size"])
    else:
        reqs = traffic.requests(mix, seed, mix["pool_requests"],
                                c["vocab_size"])
    before = rep.ask("snapshot", "snapshot")
    t_wall, t_start = time.time(), time.perf_counter()
    tracer = None
    if trace:
        def traced() -> None:
            time.sleep(min(2.0, seconds / 4))
            rep.ask("trace_start", "trace_started")
            time.sleep(min(mix["trace_seconds"], seconds / 2))
            rep.ask("trace_stop", "trace_stopped")
        tracer = threading.Thread(target=traced, daemon=True,
                                  name="bench-tracer")
        tracer.start()
    if mix["loop"] == "open":
        streams = open_loop(client, reqs, due, t_start, mix["drain_seconds"])
    else:
        streams = closed_loop(client, reqs, mix["clients"], t_start, seconds)
    if tracer is not None:
        tracer.join()
    return streams, before, rep.ask("snapshot", "snapshot"), t_wall, t_start


def judge(rep, streams, mix, seed) -> dict:
    """The window is closed: a sample of what it finished, drawn from the
    seed with the longest in it, goes through the reference in the
    replica. Returns what ``correct`` compares and a note."""
    finished = [s for s in streams if s.ok]
    if not finished:
        fail("no request finished in the window", rep.log)
    longest = max(finished, key=lambda s: len(s.req["prompt"])
                  + len(s.tokens))
    rest = [s for s in finished if s is not longest]
    random.Random(seed).shuffle(rest)
    sample = [longest] + rest[:mix["check_requests"] - 1]
    checked = rep.ask("check", "checked", timeout=900.0,
                      samples=[[s.req["prompt"], s.tokens] for s in sample])
    gaps = [g for row in checked["gaps"] for g in row]
    return {
        "compared": {
            "served_token_mismatch_share": sum(g > 0 for g in gaps)
            / len(gaps),
            "served_token_mean_gap": sum(gaps) / len(gaps),
            "served_token_widest_gap": max(gaps),
            "streams_with_wrong_token_count": sum(
                s.reason == "budget" and not s.ok for s in streams)},
        "gaps": gaps,
        "note": f"reference over {len(sample)} of {len(finished)} finished "
                f"requests, {len(gaps)} served tokens, took "
                f"{checked['reference_s']:.1f} s"}


def run(*, cell, c, mix, seed, seconds, trace, out, env, platform, fault,
        t0) -> dict:
    had_jax = "jax" in sys.modules      # the CPU tests' own process has it
    from tony_tpu.serving.client import StreamingClient
    if "jax" in sys.modules and not had_jax:
        fail("the serving client imported jax into the parent")
    with Children() as children:
        rep = Replica(children, cell=cell, seed=seed, trace=trace, out=out,
                      env=env, platform=platform, fault=fault)
        hello = rep.expect("listening", timeout=1000.0)
        t_listening = time.time()
        with StreamingClient("127.0.0.1", hello["port"]) as client:
            warm_up(client, c, mix, seed)
            streams, before, after, t_wall, t_start = offer(
                rep, client, c=c, mix=mix, seed=seed, seconds=seconds,
                trace=trace)
        judged = judge(rep, streams, mix, seed)
        rep.close()

    lat = latencies(streams, t_start + seconds)
    failed = sum(not s.ok for s in streams)
    kept = sum(len(s.tokens) for s in streams)
    e2e = {"setup_s": t_wall - t0,
           "serve_tokens_per_s": lat["tokens_in_window"] / seconds,
           "itl_p95_ms": 1e3 * percentile(lat["gaps"], 95)}
    if mix["loop"] == "open":
        e2e["ttft_p90_ms"] = 1e3 * percentile(lat["ttft"], 90)
    phases = {
        p: {k: after["phases"].get(p, {}).get(k, 0)
            - before["phases"].get(p, {}).get(k, 0)
            for k in ("total_s", "count")}
        for p in after["phases"]}
    steps = after["steps_executed"] - before["steps_executed"]
    tr = None
    if trace:
        with open(os.path.join(out, "trace.json")) as f:
            tr = json.load(f)
    return {
        "device": dict(hello["device"],
                       memory_peak_bytes=after["memory_peak_bytes"]),
        "attempted": len(streams), "failed": failed,
        "e2e": e2e,
        "compared": judged["compared"],
        "notes": [
            f"set-up: replica listening after {t_listening - t0:.1f} s "
            f"({hello['took']}), warm-up requests "
            f"{t_wall - t_listening:.1f} s",
            f"requests {len(streams)} finished "
            f"{sum(s.ok for s in streams)} failed "
            f"{failed}; ttft samples {len(lat['ttft'])}, gap samples "
            f"{len(lat['gaps'])}, tokens in window "
            f"{lat['tokens_in_window']}",
            judged["note"]],
        "trace": tr,
        "ctx": {"counters": {
            "compile_requests": after["compile_requests"],
            "compile_hits": after["compile_hits"],
            "compile_requests_in_window": after["compile_requests"]
            - before["compile_requests"],
            "phases": phases, "steps_executed": steps,
            "chunk": after["chunk"], "tokens_kept": kept,
            "prefill_tokens": after["prefill_forward_tokens"]
            - before["prefill_forward_tokens"],
            "mean_live_rows": _mean_live_rows(streams, steps, mix),
            "gen_late_s": [s.sent - s.due for s in streams]
            if mix["loop"] == "open" else None}},
    }


def _mean_live_rows(streams, steps: int, mix: dict) -> float:
    """Cache rows live in an average decode step: each finished request
    holds prompt + i rows at its i-th step; summed over requests and
    steps, over the decode steps executed."""
    if not steps:
        return 0.0
    total = sum(len(s.tokens) * (len(s.req["prompt"])
                                 + len(s.tokens) / 2) for s in streams)
    return total / steps
