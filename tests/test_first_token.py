"""The first token leaves with its admission: one draw a wave
(``serve.first_tokens``) from the logits the admission seeded, fetched in
device-queue order and sent ahead of the chunk enqueued behind it.

Held here: every stream is the parent's, token for token (``_Late`` is the
parent's delivery: no draw, the first token rides its chunk) and
``decode.generate``'s, through every admission program, greedy and
sampled; WHEN the first delta leaves, by an order log on the fetch seam
(no sleeps, no clock); the books at a first token that ends its request,
and across a cancel and a preemption that land between the token and its
chunk; the loops' equivalence, chunk counts included; the speculative
batcher, which keeps its own first token.

CPU only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import transformer as T
from tony_tpu.models.decode import extract_kv_rows, generate
from tony_tpu.models.serve import (ContinuousBatcher, KVPackage,
                                   ServeEngine,
                                   SpeculativeContinuousBatcher,
                                   prefill_ship_row, prefill_ship_rows)
from tony_tpu.runtime.metrics import MetricsRegistry

CFG = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)
RING = CFG.scaled(attn_window=8, kv_cache_capacity=8)
PREFIX = [7, 8, 9, 3, 5]
SAMPLED = dict(temperature=0.8, top_k=12, top_p=0.9, seed=5)


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


class _Late(ContinuousBatcher):
    """The parent's delivery: nothing is drawn ahead, so every first
    token rides the chunk enqueued behind its admission."""

    def _draw_first(self):
        return None


def _prompts(seed, sizes):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, CFG.vocab_size, size=n)]
            for n in sizes]


def _reference(params, prompt, max_new, cfg=CFG):
    out = generate(params, jnp.asarray(prompt, jnp.int32)[None], cfg,
                   max_new_tokens=max_new, rng=jax.random.PRNGKey(0),
                   temperature=0.0)
    return [int(t) for t in np.asarray(out.tokens[0, len(prompt):])]


def _package(params, cfg, prompt, key):
    """One prompt prefilled for shipment as the prefill gang does."""
    if cfg.kv_cache_capacity:
        lg, mini = prefill_ship_row(
            params, jnp.asarray(prompt, jnp.int32)[None], cfg)
        width = mini["k"].shape[2]
    else:
        toks = np.zeros((2, 16), np.int64)
        toks[0, :len(prompt)] = prompt
        lg, mini = prefill_ship_rows(
            params, jnp.asarray(toks, jnp.int32),
            jnp.asarray([len(prompt), 1], np.int32), cfg)
        width = len(prompt)
    bufs = extract_kv_rows(mini, [width], cfg)[0]
    return KVPackage(bufs, len(prompt), np.asarray(lg)[0],
                     np.asarray(key, np.uint32))


class _Served:
    """An engine's run with everything a client would see, in order."""

    def __init__(self, batcher, registry=None):
        self.b = batcher
        self.deltas = {}              # rid -> [each delta's tokens]
        self.retired = {}             # rid -> [(reason, n, final)]
        self.log = []                 # what happened, in order
        self.on_first = {}            # rid -> called as its first delta leaves
        self.engine = ServeEngine(
            batcher, on_delta=self._delta, on_retired=self._retired,
            registry=registry or MetricsRegistry())

    def _delta(self, rid, toks):
        self.log.append(("delta", rid, len(toks)))
        first = rid not in self.deltas
        self.deltas.setdefault(rid, []).append(list(toks))
        if first and rid in self.on_first:
            self.on_first.pop(rid)()

    def _retired(self, rid, reason, n, final):
        self.log.append(("retired", rid, reason))
        self.retired.setdefault(rid, []).append((reason, n, list(final)))

    def stream(self, rid):
        """The tokens the client holds for ``rid``: its deltas, then the
        final one its retirement carried."""
        out = [t for d in self.deltas.get(rid, []) for t in d]
        for _, _, final in self.retired.get(rid, []):
            out.extend(final)
        return out

    def run(self):
        self.engine.drain()
        self.engine.run()
        return self


# ------------------------------------------- the streams are the parent's
def _admission(kind, cls, params, sampling):
    """(batcher, submit(engine, rid, prompt, budget), the whole prompts)
    for one admission program."""
    prompts = _prompts(11, (5, 3, 7, 4, 6))
    kw = dict(batch=2, max_len=40, chunk=3, **sampling)
    if kind == "ring":
        b = cls(params, RING, **dict(kw, max_len=16))
    elif kind == "shared-prefix":
        b = cls(params, CFG, shared_prefix=PREFIX, **kw)
    else:
        b = cls(params, CFG, **kw)
    if kind == "resident-prefix":
        b.install_prefix("sys", PREFIX)
        prompts = [PREFIX + p for p in prompts]

    def submit(engine, rid, prompt, budget):
        if kind == "shipped-kv":
            engine.submit_prefilled(
                rid, _package(params, b.cfg, prompt, b._req_key(rid)),
                budget)
        else:
            engine.submit(rid, prompt, budget)
    return b, submit, prompts


@pytest.mark.parametrize("sampling", [{}, SAMPLED],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", ["prompt", "resident-prefix",
                                  "shared-prefix", "ring", "shipped-kv"])
def test_every_stream_is_the_parents(params, kind, sampling):
    """Through each admission program — the draw reads the engine's
    ``logits``, whichever of them seeded it — every request's stream is
    what the parent's delivery serves, greedy and sampled (the early draw
    and step 0 of the chunk use the same key), and greedy is
    ``decode.generate``'s; only the first delta differs: ONE token."""
    budgets = [6, 1, 7, 4, 5]
    runs = {}
    for cls in (ContinuousBatcher, _Late):
        b, submit, prompts = _admission(kind, cls, params, sampling)
        served = _Served(b)
        for rid, (p, n) in enumerate(zip(prompts, budgets)):
            submit(served.engine, rid, p, n)
        runs[cls] = served.run()
    early, late = runs[ContinuousBatcher], runs[_Late]
    for rid, n in enumerate(budgets):
        assert early.stream(rid) == late.stream(rid), rid
        assert len(early.stream(rid)) == n
        assert [r for r, _, _ in early.retired[rid]] == ["budget"]
        if n > 1:
            assert len(early.deltas[rid][0]) == 1          # the early one
        if n > 3:
            assert len(late.deltas[rid][0]) == 3           # a whole chunk
    assert early.b.steps_executed == late.b.steps_executed
    assert early.engine.stats()["first_tokens_early"] == len(budgets)
    assert late.engine.stats()["first_tokens_early"] == 0
    if not sampling:
        whole = [PREFIX + p for p in prompts] \
            if kind == "shared-prefix" else prompts
        for rid, (p, n) in enumerate(zip(whole, budgets)):
            assert early.stream(rid) == _reference(
                params, p, n, early.b.cfg), rid


def test_one_draw_program_whatever_the_wave_held(params, retrace_guard):
    """Waves of one and of two rows, of one bucket and of two: ONE traced
    draw, and it is no admission program."""
    b = ContinuousBatcher(params, CFG, batch=2, max_len=47, chunk=3)
    outs = b.serve(_prompts(12, (5, 20, 3, 6, 18)), max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)
    # (none where an earlier test of this process drew at this width)
    retrace_guard.assert_max("first_tokens", 1)
    assert b.phase_times.count("first_fetch") == b.phase_times.count("admit")


# ---------------------------------------------------- when the token leaves
class _Logged(ContinuousBatcher):
    """The order log on the seams: every chunk's issue, the start and the
    return of its fetch, every admission wave, by ``seq``."""

    log = None

    def _issue(self):
        self.log.append(("issue", self.seq))
        return super()._issue()

    def _fetch(self, handle):
        seq = self._unfetched[0][0]
        self.log.append(("fetch", seq))
        out = super()._fetch(handle)
        self.log.append(("fetched", seq))
        return out

    def _admit_batch(self, pairs, prompts):
        first = self.seq
        out = super()._admit_batch(pairs, prompts)
        # rids are the streams here: submitted in order, nothing pinned
        self.log.append(("admitted", tuple(rid for _, rid in pairs),
                         first, self.seq))
        return out


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["pipelined", "sequential"])
def test_the_first_delta_leaves_in_device_queue_order(params, pipeline):
    """A request's first delta holds ONE token and leaves before the
    fetch of the first chunk enqueued behind its admission — and after
    the consumption of every chunk enqueued ahead of it: waiting for the
    draw ahead of an older chunk would add the admission to every live
    stream's gap. In the pipelined loop an admission here stands behind
    a chunk in flight."""
    b = _Logged(params, CFG, batch=2, max_len=40, chunk=3,
                pipeline=pipeline)
    served = _Served(b)
    b.log = served.log
    consume = served.engine._consume

    def consumed(host_toks, snap):
        consume(host_toks, snap)
        served.log.append(("consumed", b._seq_run))
    served.engine._consume = consumed
    prompts = _prompts(13, (5, 4, 6, 3))
    budgets = [9, 4, 7, 5]

    def join(*rids):
        for rid in rids:
            served.engine.submit(rid, prompts[rid], budgets[rid])
    # one request on two slots; a second joins as its first delta leaves
    # (a chunk is in flight by then, a slot free); two more then wait
    join(0)
    served.on_first[0] = lambda: join(1)
    served.on_first[1] = lambda: (join(2, 3), served.engine.drain())
    served.engine.run()
    log = served.log
    for rid, n in enumerate(budgets):
        assert served.stream(rid) == _reference(params, prompts[rid], n)
    at = {e: i for i, e in enumerate(log)}
    chunks = [e[1] for e in log if e[0] == "issue"]
    behind_flight = 0
    for i, e in enumerate(log):
        if e[0] != "admitted":
            continue
        _, rids, first_seq, next_seq = e
        # the first chunk enqueued behind the wave, and those ahead of it
        after = min(c for c in chunks if c >= next_seq)
        ahead = [c for c in chunks if c < first_seq]
        for rid in rids:
            firsts = [j for j, x in enumerate(log)
                      if x[0] == "delta" and x[1] == rid]
            assert log[firsts[0]] == ("delta", rid, 1)
            assert firsts[0] < at[("fetch", after)], (rid, log)
            for c in ahead:
                assert at[("consumed", c)] < firsts[0], (rid, c, log)
            # ... and the rest of its first chunk comes with that chunk
            assert at[("fetched", after)] < firsts[1]
        # an older chunk still unfetched when the wave was enqueued?
        behind_flight += any(
            at[("issue", c)] < i < at[("fetch", c)] for c in ahead)
    assert behind_flight >= (1 if pipeline else 0)
    if not pipeline:
        assert behind_flight == 0


# ------------------------------------------ a first token that ends it all
@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["pipelined", "sequential"])
@pytest.mark.parametrize("how", ["eos", "budget"])
def test_a_request_its_first_token_ends_retires_there(params, how,
                                                      pipeline):
    """``eos`` as the first token, or a budget of one: the request
    retires ONCE, where the token leaves, with the token as the final
    delta of its retirement (no ``on_delta``), its slot is free for the
    next request, and its row of the chunk behind is discarded."""
    prompts = _prompts(14, (5, 4, 6, 3))
    refs = [_reference(params, p, 6) for p in prompts]
    eos = refs[0][0] if how == "eos" else None
    budgets = [6 if how == "eos" else 1, 6, 6, 6]
    b = _Logged(params, CFG, batch=2, max_len=40, chunk=3, eos_id=eos,
                pipeline=pipeline)
    served = _Served(b)
    b.log = served.log
    for rid, (p, n) in enumerate(zip(prompts, budgets)):
        served.engine.submit(rid, p, n)
    served.run()
    assert served.retired[0] == [(how, 1, [refs[0][0]])]
    assert 0 not in served.deltas
    # retired before the chunk behind its admission was even fetched
    log = served.log
    assert log.index(("retired", 0, how)) < log.index(("fetch", 1))
    for rid in (1, 2, 3):
        want = refs[rid]
        if eos is not None and eos in want:
            want = want[:want.index(eos) + 1]
        assert served.stream(rid) == want, rid
        assert len(served.retired[rid]) == 1
    stats = served.engine.stats()
    assert stats["active"] == 0 and stats["first_tokens_early"] == 4
    # the freed slot was taken by the next request in line
    waves = [e[1] for e in log if e[0] == "admitted"]
    assert waves[0] == (0, 1) and waves[1][0] == 2


# --------------------- a cancel, a preemption between the token and its chunk
def test_a_cancel_behind_the_first_token_sends_nothing_more(params):
    """Cancelled as its first token leaves (from the callback, so the
    cancel lands between the token and the chunk that carries it again):
    the client holds exactly that token, the retirement says one, the
    chunk's row is discarded, and the slot serves the next request."""
    b = ContinuousBatcher(params, CFG, batch=2, max_len=40, chunk=3)
    served = _Served(b)
    prompts = _prompts(15, (5, 4, 6))
    served.on_first[0] = lambda: served.engine.cancel(0)
    for rid, p in enumerate(prompts):
        served.engine.submit(rid, p, 6)
    served.run()
    assert served.stream(0) == _reference(params, prompts[0], 6)[:1]
    assert served.retired[0] == [("cancelled", 1, [])]
    for rid in (1, 2):
        assert served.stream(rid) == _reference(params, prompts[rid], 6)
    assert served.engine.stats()["active"] == 0


@pytest.mark.parametrize("sampling", [{}, SAMPLED],
                         ids=["greedy", "sampled"])
def test_a_preemption_behind_the_first_token_resumes_after_it(params,
                                                              sampling):
    """Evicted between its first token and the chunk that carries it
    again (the loop driven by hand up to there): the reincarnation
    re-prefills prompt + THAT token and draws from the position after
    it, so the client's stream loses no token and holds none twice —
    what an undisturbed run serves, greedy and sampled."""
    prompts = _prompts(16, (5, 4, 6))

    def engine_of():
        b = ContinuousBatcher(params, CFG, batch=2, max_len=40, chunk=3,
                              **sampling)
        served = _Served(b)
        for rid in (0, 1):
            served.engine.submit(rid, prompts[rid], 8,
                                 request_class="batch")
        return b, served

    b, calm = engine_of()
    calm.run()

    b, served = engine_of()
    eng = served.engine
    eng._admit_free()                       # both in; the draw enqueued
    handle, snap = b._issue(), list(eng._occupant)
    seq = b._unfetched[0][0]
    _, first, rows = eng._firsts.popleft()  # as _fetch would, and stop
    eng._consume_first(np.asarray(first), rows, seq)
    assert [served.stream(r) for r in (0, 1)] == \
        [calm.stream(r)[:1] for r in (0, 1)]
    victim = eng._occupant[0]
    assert victim.history == served.stream(0) and victim.emitted == 1
    # an interactive request arrives: request 0 (fewest tokens out, first
    # in row order) is evicted and queued again under its own rid
    eng.submit(2, prompts[2], 4, request_class="interactive")
    eng._pick_admissions()
    assert victim.done and victim.requeued
    again = eng._reqs[0]
    assert again.prompt == prompts[0] + served.stream(0)
    assert (again.emitted, again.rng_skip, again.budget) == (1, 1, 7)
    eng._consume(eng._fetch(handle), snap)  # its row: discarded
    assert served.stream(0) == calm.stream(0)[:1]
    assert len(served.stream(1)) == 3       # the early one + columns 1, 2
    served.run()
    for rid in (0, 1):
        assert served.stream(rid) == calm.stream(rid), rid
        assert [r for r, _, _ in served.retired[rid]] == ["budget"]
    if not sampling:
        assert served.stream(2) == _reference(params, prompts[2], 4)


# ------------------------------------------------------- the loops, the books
@pytest.mark.parametrize("sampling", [{}, SAMPLED],
                         ids=["greedy", "sampled"])
def test_pipelined_is_sequential_chunk_for_chunk(params, sampling):
    """Budgets around the chunk (1, chunk − 1, chunk, chunk + 1, …): a row
    with its first token already out gets one token fewer from its first
    chunk and ends in the chunk it always ended in, so the loops foresee
    every budget end as they did — same tokens, same ``steps_executed``,
    pipelined, sequential and the parent's delivery alike."""
    prompts = _prompts(17, (4, 5, 3, 6, 4, 5, 3))
    budgets = [1, 2, 3, 4, 7, 6, 1]

    def run(cls, pipeline):
        b = cls(params, CFG, batch=2, max_len=40, chunk=3,
                pipeline=pipeline, **sampling)
        outs = b.serve(prompts, budgets)
        assert [len(o) for o in outs] == budgets
        return outs, b.steps_executed, b.phase_times.count("dispatch")

    runs = [run(cls, pipeline) for cls in (ContinuousBatcher, _Late)
            for pipeline in (True, False)]
    assert all(r == runs[0] for r in runs[1:])


def test_the_counter_and_the_waits_move_with_the_token(params):
    """``tony_serve_first_tokens_early_total`` and the stats key count the
    requests whose first token left ahead of its chunk; the time to first
    token is observed THERE, once a request, and the engine-side gap
    histogram gets the gap from that token to the rest of its chunk."""
    reg = MetricsRegistry()
    b = ContinuousBatcher(params, CFG, batch=2, max_len=40, chunk=3)
    served = _Served(b, registry=reg)
    prompts = _prompts(18, (5, 4, 6))
    for rid, p in enumerate(prompts):
        served.engine.submit(rid, p, 5)
    served.run()
    pt = b.phase_times
    assert reg.counter("tony_serve_first_tokens_early_total").value == 3
    assert served.engine.stats()["first_tokens_early"] == 3
    assert pt.count("first_token") == pt.count("first_token_early") == 3
    assert pt.total("first_token") == pt.total("first_token_early")
    assert reg.histogram("tony_serve_ttft_seconds").count == 3
    # 5 tokens a request: the early one, two more of its first chunk,
    # two of its second — two gaps a request
    assert reg.histogram("tony_serve_intertoken_seconds").count == 6
    assert reg.counter("tony_serve_tokens_total").value == 15


def test_the_speculative_batcher_keeps_its_own_first_token(params):
    """Its first token is the seed its admission drew into ``pending``,
    which the next round's dispatch donates: it leaves with its first
    chunk, as before — no draw, no early token, the greedy streams."""
    b = SpeculativeContinuousBatcher(params, CFG, params, CFG, batch=2,
                                     max_len=40, num_speculative=2,
                                     chunk=1)
    served = _Served(b)
    prompts = _prompts(19, (5, 4, 6))
    for rid, p in enumerate(prompts):
        served.engine.submit(rid, p, 8)
    served.run()
    for rid, p in enumerate(prompts):
        assert served.stream(rid) == _reference(params, p, 8)
        assert len(served.deltas[rid][0]) > 1
    assert served.engine.stats()["first_tokens_early"] == 0
    assert b.phase_times.count("first_fetch") == 0
    assert b.phase_times.count("first_token") == 3
