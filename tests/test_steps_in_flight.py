"""A token no longer waits out a chunk of eight: the default decode
program is ``serve._DECODE_STEPS`` (two) steps, and the pipelined loop
keeps a QUEUE of issued programs whose depth it reads from its own turns
(``serve._Depth``).

Held here: at depths 2, 3 and 8 and at 1 and 4 steps a program every
request's tokens are the sequential loop's — greedy and sampled, through
an eos, a cancel, a foreseen budget end with a request waiting and an
admission that lands between queued steps — and programs are fetched in
the order they were issued; a delta is one program's tokens a live
request (one token at ``chunk=1``); the depth follows the host turn over
the step and keeps to its floor and its cap; the first token that left with its admission is
column 0 of the first program enqueued behind it at any depth; the
speculative batcher serves what it served, round for round.

CPU only; no sleeps, no clock.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import serve as S
from tony_tpu.models import transformer as T
from tony_tpu.models.serve import (ContinuousBatcher, ServeEngine,
                                   SpeculativeContinuousBatcher)
from tony_tpu.runtime.metrics import MetricsRegistry

CFG = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)
SAMPLED = dict(temperature=0.8, top_k=12, top_p=0.9, seed=5)
SIZES = (5, 9, 7, 12, 6, 20, 8)
BUDGETS = (9, 5, 14, 3, 11, 7, 1)


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def _prompts(seed=11, sizes=SIZES):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, CFG.vocab_size, size=n)]
            for n in sizes]


def _pin_depth(monkeypatch, depth):
    """Hold the pipelined loop's queue at ``depth`` programs, whatever
    its turns read (and whatever the cap: a test may stand further ahead
    than a deployment would)."""
    class Pinned(S._Depth):
        def __init__(self, steps_a_program):
            super().__init__(steps_a_program)
            self.depth = depth

        def turn(self, host, took, clean):
            pass
    monkeypatch.setattr(S, "_Depth", Pinned)


class _Run:
    """One engine run with what a client sees and what the queue did."""

    def __init__(self, batcher, *, cancel=None, join=None):
        self.b = batcher
        self.tokens = {}            # rid -> tokens, deltas then the final
        self.deltas = {}            # rid -> [len of each delta]
        self.reasons = {}
        self.fetched = []           # seq of each program fetched, in order
        self.queued_at_admit = []   # programs unfetched at each admission
        self.by_seq = {}            # seq -> the [B, n] tokens fetched
        self.firsts = []            # (row, rid, token, chunk_seq)
        self.cancel, self.join = cancel, join
        self.engine = ServeEngine(batcher, on_delta=self._delta,
                                  on_retired=self._retired,
                                  registry=MetricsRegistry(),
                                  max_queue_depth=0)
        fetch, admit = batcher._fetch, batcher._admit_batch
        consume_first = self.engine._consume_first

        def fetch_logged(handle):
            seq = batcher._unfetched[0][0]
            self.fetched.append(seq)
            got = fetch(handle)
            self.by_seq[seq] = got
            return got

        def admit_logged(pairs, prompts):
            self.queued_at_admit.append(len(batcher._unfetched))
            return admit(pairs, prompts)

        def first_logged(host, rows, chunk_seq):
            self.firsts += [(row, req.rid, int(host[row]), chunk_seq)
                            for row, req in rows if not req.done]
            return consume_first(host, rows, chunk_seq)
        batcher._fetch, batcher._admit_batch = fetch_logged, admit_logged
        self.engine._consume_first = first_logged

    def _delta(self, rid, toks):
        first = rid not in self.tokens
        self.tokens.setdefault(rid, []).extend(toks)
        self.deltas.setdefault(rid, []).append(len(toks))
        if self.join and first and rid == self.join[0]:
            self.join[1](self.engine)   # lands between queued steps
        if self.cancel and rid == self.cancel[0] \
                and len(self.tokens[rid]) >= self.cancel[1]:
            self.engine.cancel(rid)

    def _retired(self, rid, reason, n, final):
        self.tokens.setdefault(rid, []).extend(final)
        self.reasons[rid] = reason

    def run(self):
        self.engine.drain()
        self.engine.run()
        return self


def _serve(params, *, pipeline, chunk, sampling, eos=None, cancel=None,
           late=True):
    """The scenario: seven requests on two slots (so budget ends are
    foreseen with requests waiting), the last two submitted as request
    0's first delta leaves (an admission between queued steps), an eos
    and a cancel if given."""
    b = ContinuousBatcher(params, CFG, batch=2, max_len=64, chunk=chunk,
                          pipeline=pipeline, eos_id=eos, **sampling)
    prompts = _prompts()

    def join(engine):
        for rid in (5, 6):
            engine.submit(rid, prompts[rid], BUDGETS[rid])
    run = _Run(b, cancel=cancel, join=(0, join) if late else None)
    for rid in range(5 if late else 7):
        run.engine.submit(rid, prompts[rid], BUDGETS[rid])
    if late:
        # drain() would refuse the joiners: the run ends when they retire
        def retired(rid, reason, n, final, inner=run._retired):
            inner(rid, reason, n, final)
            if len(run.reasons) == 7:
                run.engine.drain()
        run.engine.on_retired = retired
        run.engine.run()
        return run
    return run.run()


@pytest.mark.parametrize("sampling", [{}, SAMPLED],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("chunk", [1, 4])
@pytest.mark.parametrize("depth", [2, 3, 8])
def test_every_depth_serves_the_sequential_tokens(monkeypatch, params,
                                                  depth, chunk, sampling):
    ref = _serve(params, pipeline=False, chunk=chunk, sampling=sampling)
    assert [len(ref.tokens[r]) for r in range(7)] == list(BUDGETS)
    # an eos that ends request 2 early, in the middle of the queue, and a
    # cancel of request 4 once the client holds three of its tokens
    eos = ref.tokens[2][6]
    want = _serve(params, pipeline=False, chunk=chunk, sampling=sampling,
                  eos=eos)
    assert want.reasons[2] == "eos" and len(want.tokens[2]) < BUDGETS[2]
    _pin_depth(monkeypatch, depth)
    got = _serve(params, pipeline=True, chunk=chunk, sampling=sampling,
                 eos=eos, cancel=(4, 3))
    for rid in range(7):
        if rid == 4:
            # what it got before the cancel landed is its stream's head
            assert got.reasons[4] == "cancelled"
            assert 3 <= len(got.tokens[4]) <= BUDGETS[4]
            assert got.tokens[4] == want.tokens[4][:len(got.tokens[4])]
        else:
            assert got.tokens[rid] == want.tokens[rid], rid
            assert got.reasons[rid] == want.reasons[rid], rid
    # fetched strictly in the order issued, whatever the depth
    assert got.fetched == sorted(got.fetched)
    assert len(set(got.fetched)) == len(got.fetched)
    # the queue was as deep as asked, and an admission stood behind it:
    # the foreseen handover behind a row's last step, or the joiners
    # behind the steps in flight
    assert got.b.phase_times.total("steps_in_flight") \
        <= depth * chunk * got.b.phase_times.count("steps_in_flight")
    assert max(got.queued_at_admit) >= min(depth, 2) - 1
    if depth > 2:
        assert max(got.queued_at_admit) >= 2
    assert not got.engine._redraw_warned


@pytest.mark.parametrize("sampling", [{}, SAMPLED],
                         ids=["greedy", "sampled"])
def test_budget_ends_are_foreseen_without_a_lost_step(monkeypatch, params,
                                                      sampling):
    """A closed batch, budgets only: the row changes hands behind its last
    step, so the pipelined loop runs the sequential loop's programs —
    count, tokens, admission order — at any depth."""
    seq = _serve(params, pipeline=False, chunk=1, sampling=sampling,
                 late=False)
    for depth in (2, 3, 8):
        _pin_depth(monkeypatch, depth)
        got = _serve(params, pipeline=True, chunk=1, sampling=sampling,
                     late=False)
        assert got.tokens == seq.tokens
        assert got.b.steps_executed == seq.b.steps_executed
        # no enqueue found the queue empty once the run was fed
        assert got.b.phase_times.count("starved") == 0
        assert seq.b.phase_times.count("starved") > 0


@pytest.mark.parametrize("chunk", [None, 1])
def test_a_delta_is_a_programs_tokens_a_request(params, chunk):
    """At the default a program is ``_DECODE_STEPS`` (2) steps: a delta
    holds that many tokens a live request, the first ONE (it left with
    its admission) and the next one fewer (column 0 of the first program
    is that same token); at ``chunk=1`` every delta is one token, once a
    live request a step."""
    kw = {} if chunk is None else {"chunk": chunk}
    b = ContinuousBatcher(params, CFG, batch=2, max_len=64, **kw)
    assert S._DECODE_STEPS == 2 and b.chunk == (chunk or S._DECODE_STEPS)
    run = _Run(b)
    prompts = _prompts()
    for rid in range(7):
        run.engine.submit(rid, prompts[rid], BUDGETS[rid])
    run.run()
    for rid in range(7):
        assert len(run.tokens[rid]) == BUDGETS[rid]
        deltas = run.deltas.get(rid, [])
        # the last tokens ride the retirement, not a delta
        assert sum(deltas) < BUDGETS[rid] or BUDGETS[rid] == 1
        assert deltas[:1] == [1][:len(deltas)]
        if b.chunk == 1:
            assert deltas == [1] * (BUDGETS[rid] - 1)
        else:
            assert deltas[1:2] == [1][:len(deltas) - 1]
            assert set(deltas[2:]) <= {2}
    pt = b.phase_times
    assert pt.count("dispatch") * b.chunk == b.steps_executed
    # every program is [B, chunk]
    assert {np.asarray(t).shape for t in run.by_seq.values()} \
        == {(2, b.chunk)}
    stats = run.engine.stats()
    assert stats["depth"] >= 2 and 1 <= stats["steps_in_flight"] <= 16


def test_the_first_token_is_column_0_of_the_program_behind_it(monkeypatch,
                                                             params):
    _pin_depth(monkeypatch, 3)
    got = _serve(params, pipeline=True, chunk=1, sampling=SAMPLED)
    want = _serve(params, pipeline=False, chunk=1, sampling=SAMPLED)
    assert got.tokens == want.tokens
    # sent once: every request's first token left by the draw ...
    assert sorted(rid for _, rid, _, _ in got.firsts) == list(range(7))
    assert got.engine.stats()["first_tokens_early"] == 7
    for row, rid, tok, chunk_seq in got.firsts:
        assert got.tokens[rid][0] == tok
        # ... and the first program enqueued behind its admission drew
        # the same token as its column 0 (where the request still held
        # the row: one that ended on its first token was not re-read)
        assert int(got.by_seq[chunk_seq][row][0]) == tok
    assert not got.engine._redraw_warned


# ------------------------------------------------------------ the depth
def test_the_depth_follows_the_host_turn_over_the_step():
    d = S._Depth(1)
    assert (d.depth, d.cap) == (2, 16)
    d.turn(0.050, 0.080, False)          # no clean turn yet: no step time
    assert d.depth == 2
    for _ in range(4):
        d.turn(0.002, 0.010, True)       # a fifth of a step: the floor
    assert d.depth == 2 and d.step == pytest.approx(0.010)
    d.turn(0.015, 0.030, False)          # an admission's host turn: 1.5
    assert d.depth == 3                  # ... at once
    d.turn(0.035, 0.050, False)
    assert d.depth == 5
    n = 0
    while d.depth > 2:                   # and back to the floor, slowly
        d.turn(0.002, 0.010, True)
        n += 1
    assert 10 < n < 40
    d.turn(5.0, 5.0, False)              # a stall counts as the cap's worth
    assert d.depth == d.cap == 16
    n = 0
    while d.depth > 2:
        d.turn(0.002, 0.010, True)
        n += 1
    assert n < 64
    # never more than sixteen STEPS ahead, never under two programs
    assert S._Depth(4).cap == 4 and S._Depth(8).cap == 2
    assert S._Depth(16).cap == 2
    wide = S._Depth(4)
    wide.turn(0.001, 0.010, True)
    wide.turn(1.0, 1.0, False)
    assert wide.depth == 4


def test_a_host_bound_loop_deepens_its_queue(monkeypatch, params):
    """The engine's own turns drive it: with a host that takes longer
    than a step to hand a step's tokens on, the loop stands deeper than
    the floor — and serves the same tokens."""
    b = ContinuousBatcher(params, CFG, batch=2, max_len=64)
    prompts = _prompts()
    seen = []
    fold = S._Depth.turn

    def slow_host(self, host, took, clean):
        # the turn as measured, with a host part of a step and a half
        step = self.step or took
        fold(self, 1.5 * step, max(took, 1.5 * step), clean)
        seen.append(self.depth)
    monkeypatch.setattr(S._Depth, "turn", slow_host)
    run = _Run(b)
    for rid in range(7):
        run.engine.submit(rid, prompts[rid], BUDGETS[rid])
    run.run()
    assert max(seen) == 3 and run.engine.stats()["depth"] == 3
    assert b.phase_times.total("steps_in_flight") \
        > 2 * b.phase_times.count("steps_in_flight") - 2 * 7
    monkeypatch.undo()
    plain = ContinuousBatcher(params, CFG, batch=2, max_len=64,
                              pipeline=False)
    assert plain.serve(prompts, list(BUDGETS)) == \
        [run.tokens[r] for r in range(7)]
    assert run.fetched == sorted(run.fetched)


# ------------------------------------------------- the speculative batcher
def test_the_speculative_batcher_serves_what_it_served(params):
    """Tokens, rounds and programs as at the parent of PR 42 (read there
    on this scenario): its ``chunk`` is still four ROUNDS a program, and
    the queue of programs is the loop it always ran."""
    draft = T.init_params(jax.random.PRNGKey(1), CFG)
    prompts, budgets = _prompts(), list(BUDGETS)
    greedy = ContinuousBatcher(params, CFG, batch=2, max_len=64, chunk=4)
    want = greedy.serve(prompts, budgets)
    for draft_params, rounds, programs in ((params, 16, 4),
                                           (draft, 32, 8)):
        for pipeline in (True, False):
            b = SpeculativeContinuousBatcher(
                params, CFG, draft_params, CFG, batch=2, max_len=64,
                pipeline=pipeline)
            assert b.chunk == 4
            assert b.serve(prompts, budgets) == want
            assert (b.rounds_executed, b.steps_executed,
                    b.phase_times.count("dispatch")) == \
                (rounds, rounds * 5, programs)
