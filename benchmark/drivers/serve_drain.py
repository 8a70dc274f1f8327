"""Driver of a closed-loop serving mix whose queue outlasts
``drivers/serve.py``'s grace. Everything is that driver's — the replica
child, the streams, the warm-up, the window between two snapshots, the
judge, the result — but for ONE number: how long a client waits for its
request's next event once the window has closed.

``serve.closed_loop`` gives 5 s past the close. That holds where a queued
request reaches a slot in a second (12 clients on 6 slots, answers of
0.8 s). With twice as many clients as slots and answers of 6-18 s, the
half that is queued at the close waits a whole answer for its slot, and
whatever was sent in the window's last seconds times out and counts as
failed: 21 of 163 requests at 64 clients on 32 slots (chip run, PR 28).
Here the mix's ``drain_seconds`` — the key the open loop already has —
stands where the 5 stood, so a request sent inside the window is left to
finish: tokens after the close stay outside the window's count, as there.

A ``benchmark`` PR should give ``serve.closed_loop`` this key and delete
this file; a program PR may only add files, so the loop is repeated here
with its two numbers changed and swapped in for the length of one run.
"""

from __future__ import annotations

import functools
import threading
import time

from benchmark.drivers import serve


def closed_loop(client, reqs, clients, t0, seconds, *, drain_s):
    """``serve.closed_loop`` with ``drain_s`` for its 5 s (per event, from
    the moment a request is sent) and for the workers' join."""
    pool = iter(reqs)
    lock = threading.Lock()
    done: list[serve.Stream] = []
    t_end = t0 + seconds

    def worker() -> None:
        while time.perf_counter() < t_end:
            with lock:
                req = next(pool, None)
            if req is None:
                return
            s = serve.Stream(req, time.perf_counter())
            s.sent = s.due
            rid = client.submit(req["prompt"], req["max_new_tokens"])
            s.follow(client, rid, timeout=max(1.0, t_end + drain_s
                                              - time.perf_counter()))
            with lock:
                done.append(s)

    workers = [threading.Thread(target=worker, name=f"bench-client-{i}",
                                daemon=True) for i in range(clients)]
    for th in workers:
        th.start()
    for th in workers:
        th.join(timeout=max(0.0, t_end + drain_s + 5.0
                            - time.perf_counter()))
    return done


def run(*, mix, **kw) -> dict:
    if mix["loop"] != "closed":
        raise ValueError("serve_drain drives closed loops; an open loop "
                         "has drain_seconds in drivers/serve.py")
    kept = serve.closed_loop
    serve.closed_loop = functools.partial(closed_loop,
                                          drain_s=mix["drain_seconds"])
    try:
        return serve.run(mix=mix, **kw)
    finally:
        serve.closed_loop = kept
