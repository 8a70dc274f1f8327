"""The engine's device-queue timeline, held to a script: a real (tiny)
batcher under an injected clock that moves only where the script says —
host costs inside the loop's phases, a simulated device that runs what was
enqueued in order and blocks each fetch until its chunk is done.

Two ways of holding it. (1) ``_expect`` recomputes every new total and
count from the LOG of what was enqueued, fetched and admitted, by the
definitions in ``models/serve.py``'s docstring and nothing of its code;
the three loops (pipelined, sequential, speculative) must each match it
exactly (the costs are whole numbers, so sums are exact). (2) One
pipelined script is small enough to derive by hand, and its numbers are
pinned.

CPU only; no sleeps, no wall clock.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import serve as S
from tony_tpu.models import transformer as T
from tony_tpu.models.serve import (ContinuousBatcher, ServeEngine,
                                   SpeculativeContinuousBatcher)
from tony_tpu.runtime import metrics as M
from tony_tpu.runtime import profiler, tracing

CFG = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)

# what each thing costs, in the clock's units (whole numbers: exact sums)
ISSUE, ADMIT_CALL, PAD, RETIRE, EMIT, ACCOUNT = 1, 2, 3, 1, 1, 1   # host
DRAW_CALL = 1                      # the first-token draw's dispatch: host
CHUNK_DEV, ADMIT_DEV = 100, 30                                   # device
IDLE = 1000                                      # one block on the queue

TILING = ("dispatch", "fetch", "first_fetch", "consume", "admit_pick",
          "admit", "retire", "account")


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


class World:
    """The clock, the simulated device and the log."""

    def __init__(self):
        self.t = 5000.0
        self.dev_free = 0.0           # when the device finishes its queue
        self.dev_idle = 0.0           # device idle inside runs
        self.in_run = False
        self.log = []                 # (kind, time, data)

    def perf_counter(self):
        return self.t

    def tick(self, dt):
        self.t += dt

    def enqueue(self, kind, dev):
        """A program enters the device queue now."""
        if self.in_run and self.t > self.dev_free:
            self.dev_idle += self.t - self.dev_free
        self.in_run = True
        self.dev_free = max(self.t, self.dev_free) + dev
        self.log.append((kind, self.t, self.dev_free))
        return self.dev_free


class _Handle:
    """A chunk's tokens on the simulated device: converting them blocks
    until the device has run the chunk."""

    def __init__(self, world, toks, done):
        self.world, self.toks, self.done = world, toks, done

    def __array__(self, dtype=None, copy=None):
        w = self.world
        w.t = max(w.t, self.done)
        w.log.append(("fetch", w.t, self.done))
        return np.asarray(self.toks)


class _FirstHandle(_Handle):
    """A wave's first tokens on the simulated device: ready when the
    device has run everything enqueued before the draw (the draw itself
    takes it no time)."""

    def __array__(self, dtype=None, copy=None):
        w = self.world
        w.t = max(w.t, self.done)
        w.log.append(("first_fetch", w.t, self.done))
        return np.asarray(self.toks)


class _Cond(threading.Condition):
    """The engine's condition with ``wait`` scripted: the clock moves by
    ``IDLE`` and the next step of ``script`` runs (it submits, or
    drains) with the lock released, as a submitting thread would."""

    def __init__(self, lock, world, script):
        super().__init__(lock)
        self.world, self.script = world, script

    def wait(self, timeout=None):
        w = self.world
        w.log.append(("wait", w.t, w.t + IDLE))
        w.in_run = False
        w.tick(IDLE)
        self.release()
        try:
            next(self.script)
        finally:
            self.acquire()
        return True


def _scripted(cls, world):
    class Scripted(cls):
        def _admit_batch(self, pairs, prompts):
            world.log.append(("admit_batch", world.t, list(pairs)))
            return super()._admit_batch(pairs, prompts)

        def _pad_prompts_to(self, *a, **kw):
            world.tick(PAD)                 # in admit, not admit_dispatch
            return super()._pad_prompts_to(*a, **kw)

        def _admit_rows(self, *a, **kw):
            world.tick(ADMIT_CALL)
            world.enqueue("admit", ADMIT_DEV)
            return super()._admit_rows(*a, **kw)

        def _retire(self, mask):
            world.tick(RETIRE)
            return super()._retire(mask)

        def _fetch(self, handle):
            return super()._fetch(_Handle(world, *handle))
    return Scripted


#: the program's own, however many worlds a test has wrapped them in
_PROGRAMS = (S.step_rows, S.spec_step_rows, S.cache_rows_visited,
             S.first_tokens)


def _patch_world(monkeypatch, world):
    """The clock under both modules that read it, and the two jitted chunk
    programs and the row count wrapped so that the host's cost falls
    INSIDE the phase that calls them."""
    monkeypatch.setattr(S, "time", world)
    monkeypatch.setattr(profiler, "time", world)

    def chunk(fn):
        def call(*a, **kw):
            world.tick(ISSUE)
            done = world.enqueue("chunk", CHUNK_DEV)
            out = fn(*a, **kw)
            return ((out[0], done),) + tuple(out[1:])
        return call

    def counted(fn):
        def call(*a, **kw):
            world.tick(ACCOUNT)
            return fn(*a, **kw)
        return call
    def draw(*a, **kw):
        world.tick(DRAW_CALL)
        return _FirstHandle(world, _PROGRAMS[3](*a, **kw),
                            world.enqueue("draw", 0))
    monkeypatch.setattr(S, "first_tokens", draw)
    monkeypatch.setattr(S, "step_rows", chunk(_PROGRAMS[0]))
    monkeypatch.setattr(S, "spec_step_rows", chunk(_PROGRAMS[1]))
    monkeypatch.setattr(S, "cache_rows_visited", counted(_PROGRAMS[2]))


def _prompts(seed, sizes):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, CFG.vocab_size, size=n)]
            for n in sizes]


#: (prompt length, budget, when submitted: "start", the n-th wait, or
#: ("delta", rid): as that request's first delta is emitted). Lengths 5-12
#: share the 16 bucket, 20 takes the 32 bucket: a wave of two buckets is
#: two dispatches.
SCRIPT = (
    (5, 6, "start"), (6, 4, "start"), (7, 4, "start"),   # 3 on 2 slots
    (8, 4, 0), (20, 6, 0), (9, 2, 0),                    # after a wait
    (6, 6, 1),                                           # a lone restart
    (7, 2, ("delta", 6)),           # ... joined while a chunk is in flight
)


def _run(monkeypatch, params, *, kind="pipelined", script=SCRIPT):
    """Serve ``script`` through the scripted world; returns (world,
    batcher, outputs by rid, wall of ``engine.run()``, the clock at its
    start)."""
    world = World()
    _patch_world(monkeypatch, world)
    if kind == "speculative":
        b = _scripted(SpeculativeContinuousBatcher, world)(
            params, CFG, params, CFG, batch=2, max_len=64,
            num_speculative=1, chunk=1)
    else:
        b = _scripted(ContinuousBatcher, world)(
            params, CFG, batch=2, max_len=64, chunk=2,
            pipeline=(kind == "pipelined"))
    prompts = _prompts(7, [n for n, _, _ in script])
    got = {}

    def on_delta(rid, toks):
        world.tick(EMIT)
        if rid not in got:
            submit(("delta", rid))
        got.setdefault(rid, []).extend(toks)

    def on_retired(rid, reason, n, final):
        world.tick(EMIT)
        got.setdefault(rid, []).extend(final)
    engine = ServeEngine(b, on_delta=on_delta, on_retired=on_retired,
                         registry=M.MetricsRegistry())

    def submit(when):
        for rid, (_, budget, at) in enumerate(script):
            if at == when:
                world.log.append(("submit", world.t, rid))
                engine.submit(rid, prompts[rid], budget)

    def waits():
        n = 0
        while any(at == n for _, _, at in script):
            submit(n)
            n += 1
            yield
        engine.drain()
        yield
    engine._work = _Cond(engine._lock, world, waits())
    submit("start")
    t0 = world.t
    engine.run()
    assert [len(got[r]) for r in range(len(script))] == \
        [budget for _, budget, _ in script]
    world.script, world.tokens_a_chunk = script, b._chunk_tokens_max()
    world.steps_a_program = b.chunk
    world.engine = engine
    return world, b, got, world.t - t0, t0


def _expect(world, t0, t_end):
    """Every new total and count, from the log alone. The log's order is
    the order things happened in; times are the clock's."""
    totals = {}

    def observe(name, dt):
        total, count = totals.get(name, (0.0, 0))
        totals[name] = (total + dt, count + 1)

    programs = []       # (kind, enqueue time, log index), in seq order
    # a chunk is known by when the device finishes it; one that is never
    # fetched was dropped (all its rows were garbage): nothing stands
    # behind it and no turn closes on it
    seq_of, n = {}, 0
    for kind, _, data in world.log:
        if kind in ("chunk", "admit"):
            if kind == "chunk":
                seq_of[data] = n
            n += 1
    fetches = [seq_of[d] for k, _, d in world.log if k == "fetch"]
    unfetched = []      # chunks enqueued whose fetch is still to come
    prev = None         # the chunk fetched last
    run_first = None    # seq of the run's first program; None between runs
    turn_open = None
    ret_index = None    # log index of the last fetch's return in this run
    waiting = set()     # rids submitted and not admitted
    admitted = {}       # rid -> [t_admit, t_ride (None: still behind)]
    behind = []         # (rid, the newest chunk enqueued ahead of it)
    in_queue = []       # chunks enqueued, neither fetched nor dropped
    freed = {}          # row -> time its last occupant's slot was freed
    left = {}           # rid -> tokens still to come
    on_row = {}         # row -> rid
    slivers = 0.0
    run_started = t0
    budgets = {rid: b for rid, (_, b, _) in enumerate(world.script)}
    t_queued = {}
    snaps = {}          # chunk's seq -> rows' rids when it was enqueued
    wave = []           # the (row, rid) pairs admitted last
    draws = []          # waves whose first-token draw is still unfetched
    ahead = set()       # rids whose first token left ahead of its chunk

    def first_delta(rid, t):
        t_admit, t_ride = admitted[rid]
        observe("first_token", t - t_admit)
        observe("first_token_queued", t_ride - t_admit)
        observe("first_token_ride", t - t_ride)

    for i, (kind, t, data) in enumerate(world.log):
        if kind == "submit":
            waiting.add(data)
            t_queued[data] = t
        elif kind == "wait":
            if turn_open is not None:
                slivers += t - turn_open          # the run's closing edge
            elif run_first is None:
                slivers += t - run_started        # a run that fed nothing
            observe("wait", data - t)
            run_first = turn_open = ret_index = None
            run_started = data
            in_queue.clear()        # what a run leaves unfetched it drops
        elif kind == "admit_batch":
            for row, rid in data:
                waiting.discard(rid)
                in_flight = bool(unfetched)
                admitted[rid] = [t, None if in_flight else t]
                if in_flight:
                    # it stands behind every chunk in the queue: its
                    # wait behind them ends when the newest returns
                    behind.append((rid, unfetched[-1]))
                if left.get(on_row.get(row), 0) > 0:
                    # a FORESEEN handover: the row's last step is in the
                    # queue and the slot changes hands behind it, now
                    freed[row] = t
                observe("queue_wait", t - t_queued[rid])
                observe("slot_vacant",
                        t - max(freed.get(row, 0.0), t_queued[rid]))
                on_row[row] = rid
                left[rid] = budgets[rid]
            wave = list(data)
        elif kind == "draw":
            draws.append(wave)
        elif kind == "first_fetch":
            # fetched in the order drawn; every request of the wave gets
            # ONE token here, and that is its first delta
            for row, rid in draws.pop(0):
                first_delta(rid, t)
                observe("first_token_early", t - admitted[rid][0])
                ahead.add(rid)
                left[rid] -= 1
                if left[rid] <= 0 and on_row[row] == rid:
                    freed[row] = t
        elif kind in ("chunk", "admit"):
            seq = len(programs)
            if run_first is None:
                run_first, turn_open = seq, t
                slivers += t - run_started        # the run's opening edge
            programs.append((kind, t, i))
            if kind == "chunk":
                if seq in fetches:
                    unfetched.append(seq)
                snaps[seq] = dict(on_row)
                in_queue.append(seq)
                observe("steps_in_flight",
                        len(in_queue) * world.steps_a_program)
        elif kind == "fetch":
            seq = unfetched.pop(0)
            assert seq == seq_of[data]           # fetched in issue order
            in_queue.remove(seq)
            in_run_prev = prev is not None and prev >= run_first
            lo = prev + 1 if in_run_prev else run_first
            admits = sum(programs[s][0] == "admit" for s in range(lo, seq))
            clean = (in_run_prev and not admits
                     and programs[seq][2] < ret_index)
            took = t - turn_open
            observe("turn", took)
            if admits:
                observe("turn_admit", took)
            elif clean:
                observe("turn_clean", took)
            if admits or waiting:
                observe("turn_loaded", took)
            turn_open, ret_index, prev = t, i, seq
            for rid, last in behind:
                if last <= seq:
                    admitted[rid][1] = t
            behind = [(rid, last) for rid, last in behind if last > seq]
            for row, rid in snaps[seq].items():
                if left.get(rid, 0) <= 0:
                    continue
                if left[rid] == budgets[rid]:
                    # its first delta rides a chunk: the loop drew none
                    # ahead of it (the speculative one)
                    first_delta(rid, t)
                # column 0 of a row's first chunk is the token that left
                # ahead of it
                left[rid] -= world.tokens_a_chunk - (rid in ahead)
                ahead.discard(rid)
                if left[rid] <= 0 and on_row[row] == rid:
                    freed[row] = t
    slivers += t_end - (turn_open if turn_open is not None
                        else run_started)
    # starved: a second pass, by the definition alone — an enqueue with
    # nothing enqueued before it still unfetched, inside a run
    done = -1                     # seq of the newest fetched chunk
    seq = 0
    last_ret = None
    for kind, t, data in world.log:
        if kind == "wait":
            last_ret = None
        elif kind in ("chunk", "admit"):
            if last_ret is not None and seq - 1 == done:
                observe("starved", t - last_ret)
            seq += 1
        elif kind == "fetch":
            done, last_ret = seq_of[data], t
    return totals, slivers


def _held(b, totals):
    pt = b.phase_times
    for name, (total, count) in sorted(totals.items()):
        assert (pt.total(name), pt.count(name)) == (total, count), name
    for name in ("turn", "turn_clean", "turn_admit", "turn_loaded",
                 "starved", "first_token_queued", "first_token_ride",
                 "first_token_early", "slot_vacant", "steps_in_flight"):
        if name not in totals:
            assert pt.count(name) == 0, name


@pytest.mark.parametrize("kind", ["pipelined", "sequential", "speculative"])
def test_every_loop_matches_the_log(monkeypatch, params, kind):
    world, b, _, wall, t0 = _run(monkeypatch, params, kind=kind)
    totals, slivers = _expect(world, t0, t0 + wall)
    _held(b, totals)
    pt = b.phase_times
    # the split sums to the whole, request by request and so in total
    assert pt.total("first_token_queued") + pt.total("first_token_ride") \
        == pt.total("first_token")
    assert pt.count("first_token_queued") == pt.count("first_token_ride") \
        == pt.count("first_token") == len(SCRIPT)
    assert pt.count("slot_vacant") == pt.count("queue_wait") == len(SCRIPT)
    # starved is the device's idle time inside the runs, by construction:
    # the simulated device kept its own account
    assert pt.total("starved") == world.dev_idle
    # tiling: wait and the turns are the thread's wall but for the runs'
    # edges, and the phases are the wall but for what no phase holds —
    # nothing here, since the script's clock only moves inside phases
    assert pt.total("wait") + pt.total("turn") + slivers == wall
    assert pt.total("wait") + sum(pt.total(p) for p in TILING) == wall
    assert pt.total("admit_dispatch") <= pt.total("admit")
    assert pt.total("emit") <= pt.total("consume")
    # one admit_dispatch a device dispatch, one seq a program
    n_admits = sum(k == "admit" for k, _, _ in world.log)
    n_chunks = sum(k == "chunk" for k, _, _ in world.log)
    assert pt.count("admit_dispatch") == n_admits
    n_fetches = sum(k == "fetch" for k, _, _ in world.log)
    assert pt.count("dispatch") == n_chunks
    assert pt.count("fetch") == pt.count("turn") == n_fetches
    # the speculative loop's bound on a final chunk counts rounds, not
    # tokens: each run ends on a chunk dropped unfetched, and no turn
    # closes on one
    assert n_chunks - n_fetches == (3 if kind == "speculative" else 0)
    assert b.seq == n_admits + n_chunks
    # the draw is a program in the queue and no turn knows it: it takes
    # no seq and is no admission dispatch. One a wave, fetched once (the
    # wait is a phase of its own, so the tiling above still holds) and
    # consumed once; every first token left by it, a chunk early
    n_draws = sum(k == "draw" for k, _, _ in world.log)
    early = world.engine.stats()["first_tokens_early"]
    if kind == "speculative":
        assert n_draws == pt.count("first_fetch") == early == 0
        # ... where it rides its chunk, as it did
        assert pt.total("first_token_ride") >= len(SCRIPT) * CHUNK_DEV
    else:
        assert n_draws == pt.count("admit") == pt.count("first_fetch") > 0
        assert early / pt.count("first_token") == 1.0
        assert pt.count("first_token_early") == early
        assert pt.total("first_token_ride") < len(SCRIPT) * CHUNK_DEV
    assert pt.count("consume") == n_fetches + pt.count("first_fetch")
    if kind == "sequential":
        assert pt.count("turn_clean") == 0      # never a chunk in flight
        # every turn but a run's first opens on an idle device
        assert pt.count("starved") == n_chunks - pt.count("wait")
        assert pt.total("first_token_queued") == 0.0


def test_the_pipelined_script_by_hand(monkeypatch, params):
    """Derived by hand from SCRIPT and the costs; docs/observability.md,
    "How to read a turn", walks the first run."""
    world, b, _, wall, _ = _run(monkeypatch, params)
    kinds = "".join({"chunk": "C", "admit": "A", "wait": "|"}[k]
                    for k, _, _ in world.log
                    if k in ("chunk", "admit", "wait"))
    # run 1: a wave of one dispatch; chunk 2 enqueued behind chunk 1. As
    # chunk 1 is consumed the host FORESEES that chunk 2 holds row 1's
    # last step while a request waits: the row changes hands behind it —
    # the admission is enqueued on a busy device (before PR 42 the issue
    # was deferred and the admission found the device idle) — and chunk 3
    # follows; chunk 4 is certainly final. Run 2: a wave of two buckets,
    # two chunks, the foreseen handover of row 0 behind chunk 2, a last
    # chunk. Run 3: one request, joined by another while chunks 1 and 2
    # are in flight: that admission stands behind chunk 2.
    assert kinds == "ACCACC|AACCAC|ACCAC|"
    pt = b.phase_times
    assert {n: (pt.total(n), pt.count(n)) for n in (
        "turn", "turn_clean", "turn_admit", "turn_loaded", "starved",
        "first_token", "first_token_queued", "first_token_ride",
        "slot_vacant", "queue_wait", "admit_dispatch", "wait",
        "steps_in_flight")} == {
        # run 1: 130 (admission 30 + chunk 100, from the first enqueue),
        # 100, 130 (the handover's admission 30 + 100), 100; run 2: 160
        # (two admissions), 100, 130; run 3: 130, 100, 130
        "turn": (460 + 390 + 360, 10),
        "turn_clean": (400, 4),
        "turn_admit": (130 + 130 + 160 + 130 + 130 + 130, 6),
        # the admission turns alone: at the close of each clean turn the
        # request that had waited was already admitted
        "turn_loaded": (810, 6),
        # no enqueue finds the queue empty inside a run: a foreseen
        # admission goes in behind the row's last step, not after it
        "starved": (0, 0),
        # 35 = pad 3 + call 2 + admission 30: the draw's own call (1)
        # runs while the device runs the admission, and NO chunk is in
        # it; run 2's wave 65 (both dispatches ahead); the two handovers
        # 128 = 98 behind the row's last chunk + 30; the joiner 129 = 99
        # behind the newer of the two chunks in flight + 30
        "first_token": (3 * 35 + 2 * 65 + 2 * 128 + 129, 8),
        "first_token_queued": (98 + 98 + 99, 8),
        "first_token_ride": (3 * 35 + 2 * 65 + 3 * 30, 8),
        # a handed-over slot stood vacant for nobody; the joiner is
        # submitted as request 6's first delta leaves and admitted when
        # chunk 1 has been consumed: its wait, and its never-used slot's
        "slot_vacant": (100, 8),
        # the handovers' requests waited for chunk 1 of their run (137:
        # the wave 5 + the draw 1 + two issues 4 ... to its return at
        # 135, and the two callbacks; 167 with the second bucket)
        "queue_wait": (137 + 167 + 100, 8),
        "admit_dispatch": (7 * ADMIT_CALL, 7),
        "wait": (3 * IDLE, 3),
        # a count, not an interval: the steps issued and unfetched at each
        # issue, this program's two included — a run's first 2, then 4
        # (depth 2, two steps a program)
        "steps_in_flight": (3 * 2 + 7 * 4, 10)}
    # every first token left ahead of its chunk
    assert (pt.total("first_token_early"), pt.count("first_token_early")) \
        == (pt.total("first_token"), 8)
    # the depth never left the floor: a host turn of 2-8 against 100
    assert world.engine.stats()["depth"] == 2
    assert world.engine.stats()["steps_in_flight"] == 3.4
    assert wall == 4233


def _drained(monkeypatch, params, tail):
    """Three requests on two slots, the last answering ``tail`` tokens
    after the queue has emptied: the drain's length."""
    script = ((5, 4, "start"), (6, 4, "start"), (7, tail, "start"))
    world, b, _, _, _ = _run(monkeypatch, params, script=script)
    return b.phase_times


def test_the_admission_share_does_not_move_with_the_drain(monkeypatch,
                                                         params):
    from benchmark import run as bench_run
    short = _drained(monkeypatch, params, 4)
    long = _drained(monkeypatch, params, 24)
    assert long.count("turn") > short.count("turn") + 8
    for name in ("turn_admit", "turn_loaded", "starved"):
        assert (long.total(name), long.count(name)) == \
            (short.total(name), short.count(name)), name

    def read(name, pt):
        phases = {n: {"total_s": row["total_s"], "count": row["count"]}
                  for n, row in pt.summary().items()}
        return bench_run.read_metric(name, {"counters": {"phases": phases}})
    for name in ("admit_stall_share_pct.serve", "device_starved_pct.serve",
                 "admit_stall_ms.serve", "chunk_turn_ms.serve"):
        assert read(name, long) == read(name, short) is not None, name


def test_tokens_and_admission_order_are_every_loops(monkeypatch, params):
    """The timeline watches; it does not steer. The three loops serve the
    script's tokens alike and admit in one order, and an uninstrumented
    closed batch agrees."""
    runs = {k: _run(monkeypatch, params, kind=k)
            for k in ("pipelined", "sequential", "speculative")}
    order = {k: [pair for kind, _, data in w.log if kind == "admit_batch"
                 for pair in data] for k, (w, *_) in runs.items()}
    assert order["pipelined"] == order["sequential"]
    assert [rid for _, rid in order["speculative"]] == \
        [rid for _, rid in order["pipelined"]]
    toks = {k: got for k, (_, _, got, _, _) in runs.items()}
    assert toks["pipelined"] == toks["sequential"] == toks["speculative"]
    monkeypatch.undo()
    plain = ContinuousBatcher(params, CFG, batch=2, max_len=64, chunk=2)
    outs = plain.serve(_prompts(7, [n for n, _, _ in SCRIPT]),
                       [b for _, b, _ in SCRIPT])
    assert outs == [toks["pipelined"][r] for r in range(len(SCRIPT))]


def test_request_spans_name_their_causes(monkeypatch, params):
    tr = tracing.Tracer(proc="t", sample_rate=1.0)
    prev = tracing.set_tracer(tr)
    try:
        world, b, _, _, _ = _run(monkeypatch, params)
    finally:
        tracing.set_tracer(prev)
    spans = tr.recent()
    by = {}
    for s in spans:
        by.setdefault(s["n"], []).append(s)
    assert {len(by[n]) for n in ("engine.request", "engine.queued",
                                 "engine.first_token")} == {len(SCRIPT)}
    roots = {s["sid"] for s in by["engine.request"]}
    kinds = [k for k, _, _ in world.log if k in ("chunk", "admit")]
    for s in by["engine.queued"]:
        assert s["pid"] in roots
        assert s["a"]["slot"] in (0, 1)
        # the chunk that freed the slot, or -1 for a slot never used
        freed = s["a"]["freed_seq"]
        assert freed == -1 or kinds[freed] == "chunk"
    assert sorted(s["a"]["freed_seq"] for s in by["engine.queued"])[:2] \
        == [-1, -1]
    for s in by["engine.first_token"]:
        assert s["pid"] in roots
        admit, chunk = s["a"]["admit_seq"], s["a"]["chunk_seq"]
        assert kinds[admit] == "admit" and kinds[chunk] == "chunk"
        # delivered by the first chunk enqueued after its admission
        assert chunk == admit + 1 + kinds[admit + 1:].index("chunk")
