"""Continuous batching for the serving path.

Static-batch serving (one :func:`~tony_tpu.models.decode.generate` call
per request batch) leaves rows idle from the moment they finish until the
LAST row finishes — at mixed request lengths most of the batch is dead
weight. Continuous batching retires a row the step it completes and
admits the next queued request into its cache slot while the other rows
keep decoding; utilization follows the OFFERED load, not the slowest
request. (The industry-standard serving pattern; green-field here —
SURVEY.md §2.3, the reference delegates all compute and has no serving
path.)

The round-5 per-row decode machinery is exactly what makes this cheap
(models/decode.py): cache ``length`` is a [B] vector, RoPE positions,
causal masks, and K/V writes all take per-row frontiers, and the
length-aware block-wise attention reads only each batch's LIVE rows of a
shared padded cache. On top of that, the device programs:

- :func:`admit_rows` — a BUCKETED, BATCHED admission: K prompts padded
  to one power-of-two length bucket prefill in a single dispatch and
  land in K freed cache slots (one scatter per buffer), compiling once
  per bucket instead of once per distinct prompt length and paying one
  transport dispatch however many slots freed in the chunk. Its
  variants keep the same contract: :func:`prefix_admit_rows` (suffixes
  against a prefix template), :func:`spec_admit_rows` and
  :func:`spec_prefix_admit_rows` (both models' caches);
- :func:`admit_row_ring` — the one per-row admission: a rolling (ring)
  cache's wrapped writes cannot take padded prompts, so each request
  prefills at its exact length. Which of the five runs is decided by
  the cache type alone (``cfg.kv_cache_capacity``, a template, a draft
  model), never by an option;
- :func:`step_rows` — a ``lax.scan`` of ``n`` per-row decode steps over
  the whole batch, ONE program (greedy by default, or sampled through
  the same top-k/temperature/nucleus stack as ``decode.generate`` — from
  PER-REQUEST key streams, see below). ``n`` is the unit of delivery:
  the host sees a program's ``[B, n]`` tokens when the whole of it has
  returned. The batchers run ``n = _DECODE_STEPS`` (2) unless told
  otherwise: a token waits for one more step at most, not for seven;
- :func:`first_tokens` — the draw step 0 of the next program will make,
  made once a wave right behind its admissions, so that a request's
  first token leaves when its admission has run (below);
- :func:`retire_rows` — zero the freed rows' frontiers so idle slots
  never walk off the end of the cache.

Correctness argument for slot reuse: a row's queries attend positions
``<= pos_r`` only. A new occupant's prefill rewrites positions
``[0, S_prompt)`` and its decode steps write exactly at ``pos_r`` before
reading it, so every position a query can reach was written by the
CURRENT occupant — the previous request's stale K/V beyond the frontier
is unreachable by construction (the same argument the speculative
decoder makes for rejected-draft entries). Bucketed admission extends it
one step: the padding tail's K/V (positions [len, bucket)) sits beyond
the frontier and every decode step overwrites position ``pos_r`` before
reading it, so padding rows are unreachable too.

The admission loop itself (:class:`ContinuousBatcher`) is host-driven —
admission is inherently data-dependent control flow (which request, into
which slot, at what length) and runs at human/request rate, while the
token loop stays on device in ``step_rows`` programs. The loop is
PIPELINED over a QUEUE of issued programs: ``depth`` of them stand in
the device's queue while the host fetches the oldest, so the host-side
EOS/budget bookkeeping, the device→host fetch and the admissions'
marshalling overlap device compute instead of serializing with it, and
each program's tokens leave the turn it returns. WHY SHORT PROGRAMS AND
A QUEUE, where this file ran eight steps a program and one program
ahead until PR 42: the eight were set when one host↔device sync cost
70–100 ms through a remote transport (docs/performance.md), so a sync a
token would have been the whole budget; on a machine where a small fetch
behind a busy queue returns when its own program ends and costs the host
well under a millisecond, the same overlap is bought by keeping several
short programs in flight, and a token waits out ``_DECODE_STEPS − 1``
steps at most. Why two steps and not one: a program of its own costs the
device ~2% of a step (measured; the constant's comment), which one cell
could not pay. ``depth`` is nobody's argument: the engine reads it off its own
turns (:class:`_Depth` — its host time a turn over the device's time a
step, plus one; at least 2, never more than 16 steps ahead).
Nothing on the host feeds the device between programs — per-request rng
streams are derivable ahead of time — EXCEPT retirement/admission, which
the loop handles two ways (:meth:`ServeEngine._foresee`): a completion
the host can FORESEE (budget exhaustion with requests still queued) hands
the row over BEHIND ITS LAST STEP — the admission is enqueued right after
the program that ends the row, on a busy device, exactly where the
sequential loop would have placed it; unforeseeable completions (an eos,
a cancel) are caught up as they are consumed — the freed row ran the
steps already queued behind it as garbage that the host discards exactly
as idle-slot garbage is discarded, and the late admission overwrites the
slot before anything reads it.

Sampling uses PER-REQUEST key streams: request ``q``'s draw at its
``t``-th generated token comes from ``fold_in(fold_in(seed_key, q), t)``
— a function of the workload seed, the request index, and the step
alone. A request's sampled output is therefore independent of admission
timing and batch composition (the pre-pipelining loop's shared stream
made samples depend on WHEN a request was admitted), which is also what
lets the pipelined loop shift an admission by some steps without
changing any output: pipelined — at any depth, at any ``chunk`` — and
sequential (``pipeline=False``) serving are token-identical in every
mode — greedy, sampled, speculative, and shared-prefix (test-enforced
on CPU).

:class:`SpeculativeContinuousBatcher` composes the two serving features:
every slot runs draft-propose/target-verify rounds at its own frontier
(:func:`spec_step_rows`) while admission/retirement reuse slots exactly
as in the greedy batcher — vLLM-style continuous batching with
speculative decoding, token-identical to per-request greedy decode.

Shared-prefix caching (``shared_prefix=``, both batchers): a system
prompt every request continues from prefills ONCE into a K/V template;
admission copies the template into the slot and runs only the request's
own tokens through the model (:func:`prefix_admit_rows` — a chunked
``extend_step`` against the copied prefix history), token-identical to
serving prefix+prompt in full.

The host loop itself is OPEN-LOOP (:class:`ServeEngine`): the
issue/fetch/consume/settle cycle runs against a LIVE admission queue —
requests are submitted (and cancelled) at any time, from any thread, and
each request's newly generated tokens are emitted as a DELTA the moment
the program that produced them is consumed, not when the request
retires — ``_DECODE_STEPS`` tokens a live request a program at the
default, one at ``chunk=1``.
THE FIRST TOKEN LEAVES WITH ITS ADMISSION: it is step 0 of the program
enqueued behind the admission — a function of the logits the admission
seeded and of the request's own key, so it exists the instant the
admission ends. After each admission wave the engine enqueues one
:func:`first_tokens` draw over those logits, fetches it in device-queue
order (before the fetch of the first chunk enqueued after it, never
before a chunk enqueued ahead of it: :meth:`ServeEngine._fetch`) and
sends each admitted request a first delta of ONE token; ``emitted``,
``budget`` and ``history`` move with it, and the chunk that carries the
same token as its column 0 drops it. (The speculative batcher keeps its
own first token — the seed its admission draws into ``pending`` — with
its first chunk.)
That is what a streaming serving data plane needs: time-to-first-token
and inter-token latency are properties of delta emission, and a
persistent-connection server (``tony_tpu/serving/``) pushes each delta
to its client while the next chunk is still computing.
:meth:`ContinuousBatcher.serve` is a thin CLOSED-BATCH wrapper over the
engine — submit everything, drain, collect — and remains token-identical
(and ``steps_executed``-identical) to the pre-engine loop in every mode
(test-enforced).

DISAGGREGATED serving splits the two device workloads above across two
GANGS: a prefill gang runs :func:`prefill_ship_rows` on admitted
prompts and ships each row's K/V + last-real logits + rng stream state
as a :class:`KVPackage` (``tony_tpu/serving/kvship.py`` is the wire
codec, ``tony_tpu/serving/disagg.py`` the servers); the decode gang's
engine adopts packages through :meth:`ServeEngine.submit_prefilled`,
lands them with :func:`land_kv_rows` (a
:func:`~tony_tpu.models.decode.place_rows` scatter — no model forward),
and runs pure :func:`step_rows` chunks that are never preempted by
prefill compute. Token-identity argument: the shipped buffers are the
SAME mini cache ``admit_rows`` would have landed (truncated at the true
length — the padding tail beyond each frontier is unreachable by
construction, see the slot-reuse argument above), the logits are the
same last-real-position logits, and the shipped per-request rng key +
stream position reproduce the sampled stream exactly, so disaggregated
outputs match the colocated engine bit-for-bit (greedy AND sampled;
test-pinned end-to-end across two processes).

Where the engine thread's wall goes is named from inside
(``batcher.phase_times``, a :class:`~tony_tpu.runtime.profiler
.PhaseTimes` that also enters each phase as the profiler row
``tony.engine.<phase>``): ``dispatch`` / ``fetch`` / ``admit`` /
``retire`` in the batcher; in the engine ``consume`` (all of
:meth:`ServeEngine._consume`) with ``emit`` NESTED in it (the
``on_delta`` / ``on_retired`` callbacks: frame packing and socket sends
on the engine thread — ``consume``'s self time is ``consume − emit``),
``admit_pick`` (the admission sweep outside the device dispatch: lock,
class-priority pop, preemption), ``first_fetch`` (blocked on a wave's
first-token draw; its delivery is a ``consume`` with its ``emit``, like
a chunk's) and ``wait`` (blocked on an empty queue). Two request WAITS
ride the same accumulator through ``observe``: ``queue_wait`` (entering
the wait queue → slot admission, once per admission) and ``first_token``
(admission → first delta, once per request that produced a token;
``first_token_early`` is the same wait of the requests whose first token
left ahead of its chunk — over ``first_token``'s count, the share that
did; ``tony_serve_first_tokens_early_total`` and ``stats()`` hold the
count). They are waits, not loop phases: they overlap across requests
and do not sum to the wall. ``admit`` is a whole admission wave;
``admit_dispatch`` nests in it, one per device dispatch (a wave of two
buckets is two): the marshalling that feeds the jit call and the call,
so ``admit − admit_dispatch`` is the padding, the stream rebinding and
the draw's dispatch. ``account`` is the host arithmetic that is
instrumentation itself (the cache-row count at each issue, the device
counters' fold at each fetch and consume): what counting costs is
counted.

THE DEVICE-QUEUE TIMELINE is measured in the same accumulator, with no
capture running. The device runs programs in the order the engine
thread enqueued them, so that order is the causal chain:

- every program — a decode chunk or an admission dispatch — takes the
  next sequence number ``seq`` as it is enqueued (a wave's first-token
  draw takes none: it stands right ahead of the next chunk, whose
  ``seq`` its ``first_fetch`` row carries, and no turn knows it).
  ``dispatch`` rows carry ``seq``, ``live`` (occupied slots) and
  ``waiting`` (requests in the wait queues); ``admit_dispatch`` rows
  ``seq``, ``bucket``, ``rows`` (the dispatch's width) and ``tokens``
  (real positions); ``fetch`` rows the ``seq`` they block on. In a
  capture the n-th
  ``jit_step_rows`` / ``jit_admit_rows`` execution on the device line
  after its first ``seq`` is the row with that number.
- a RUN is the feeding of the device between two ``wait`` blocks; within
  it a TURN is the interval from one fetch's return to the next (the
  run's first opens at its first enqueue). A turn holds exactly one
  decode program — "chunk" below and in the readers' names:
  ``_DECODE_STEPS`` steps at the default — and the admission dispatches enqueued between it and
  the program before. ``steps_in_flight``, at every issue: the decode
  steps issued and unfetched, that program's own included — a COUNT
  beside the intervals (its "seconds" are steps); the ``dispatch`` row
  carries it (``in_flight``, before the issue) with the ``depth`` the
  loop holds. Observed where the fetch returns
  (:meth:`ContinuousBatcher._await`): ``turn`` every one; ``turn_clean``
  when the chunk was enqueued before the previous fetch returned and no
  admission lies between the two (device-bound, it is the chunk's own
  time); ``turn_admit`` when at least one does (its mean less
  ``turn_clean``'s is what an admission adds to the gap of every live
  stream); ``turn_loaded`` when it held an admission or a request was
  waiting at its close — offered load, not a drain, so shares over it
  do not move with how long the tail runs.
- ``starved``, at every enqueue that finds nothing enqueued before it
  still unfetched (a deferred issue, every chunk of the sequential loop,
  a restart after everything retired): the time since the run's last
  fetch returned — the device's idle time the host caused, by
  construction.
- a request's ``first_token`` splits where the NEWEST program that was
  in flight at its admission returned: ``first_token_queued`` (the
  admission stood behind every step already issued; 0 when none was in
  flight) and ``first_token_ride``
  (the admission on the device, the fetch of the draw and its
  delivery — no chunk; the speculative batcher's still rides its first
  chunk); the two sum to ``first_token``. ``slot_vacant``, at each
  admission, is ``t_admit − max(the return of the chunk whose
  consumption freed the slot — or, for a FORESEEN handover, the instant
  the loop saw the row's last step in the queue — the request's
  t_queued)``: how long a free
  slot and a runnable request both waited for the loop to come round.
  The request spans name their causes: ``engine.queued`` ends with
  ``slot`` and ``freed_seq`` (the chunk that freed it, −1 for a slot
  never used), ``engine.first_token`` with ``admit_seq``, ``chunk_seq``
  (the first chunk enqueued behind the admission: the one that carries
  the token) and ``early`` (it left ahead of that chunk).

``wait`` and the turns tile the engine thread's wall but for each run's
edges (the sweep before its first enqueue, the consume and settle after
its last fetch); inside a turn ``dispatch + fetch + first_fetch +
consume + admit_pick + admit + retire + account`` leave only the loop's
own branches untimed.

``TRACE_COUNTS`` records one entry per (program, static shape) TRACE —
a Python side effect inside the jitted bodies, executed at trace time
only — so tests (and the conftest retrace guard) can pin "bucketed
admission compiles once per bucket" as a regression invariant.
"""

from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.models import transformer as T
from tony_tpu.models.decode import (MOE_COUNTS, _check_draft_vocab,
                                    cache_bytes_by_kind,
                                    cache_rows_visited, ring_rows,
                                    _check_no_ring, _filter_logits, _kv_bufs,
                                    _propose_and_verify,
                                    _propose_and_verify_sampled,
                                    decode_step, extend_step,
                                    init_kv_cache, kv_from_wire,
                                    kv_to_wire, kv_wire_layout, place_rows,
                                    prefill, prefill_rows)
from tony_tpu.runtime import goodput as goodput_mod
from tony_tpu.runtime import metrics as metrics_mod
from tony_tpu.runtime import tracing
from tony_tpu.runtime.profiler import PhaseTimes

#: Trace-time program counters keyed by (program name, static shape):
#: incremented when a serving device program is TRACED (compiled), not
#: when it is called. The bucketed-admission tests and the conftest
#: ``retrace_guard`` fixture assert on deltas of this counter.
TRACE_COUNTS: collections.Counter = collections.Counter()

#: name prefix of the host loop's phases: each ``phase_times.phase(x)``
#: is also the row ``tony.engine.<x>`` of a running profiler capture
ENGINE_PHASES = tracing.PROFILER_PREFIX + "engine"

#: smallest bucketed-admission pad length — prompts shorter than this
#: share one program rather than compiling 16 tiny variants
_MIN_ADMIT_BUCKET = 16

#: positions (rows x bucket) one bucketed admission dispatch prefills:
#: the dispatch is ``clamp(budget // bucket, 1, slots)`` rows wide
#: (:func:`admit_width`), a function of the bucket alone, so each bucket
#: still compiles one program. Below about peak FLOP/s over peak bytes/s
#: positions (197e12 / 819e9 = 240 on a v5e, bf16 weights) a prefill
#: costs the read of the weights whatever its width, so padding rows are
#: free and a wave of short prompts lands in one call; above it every
#: padded position costs its FLOPs, so a bucket at or over the budget
#: dispatches ONE row and a wider wave goes through in several
#: dispatches. Padded to the slot count instead, a closed loop that
#: frees one slot an admission prefilled 6 x the bucket for one prompt:
#: 41% of a saturated Phi-3 window (PERF.md section 6, PR 29, with the
#: budgets read on the chip).
_ADMIT_TOKEN_BUDGET = 256

#: rng-stream id for rows with no occupant (their draws are garbage the
#: host discards; any fixed stream works)
_IDLE_STREAM = 0x7FFFFFFF

#: decode steps ONE ``step_rows`` program runs unless a caller says
#: otherwise. A program is the unit of delivery — the host sees a
#: program's tokens when the whole of it has returned — so at 8 (the
#: default until PR 42, set when a host<->device sync cost 70-100 ms
#: through a remote transport, docs/performance.md) a token drawn at
#: step 0 waited out seven more steps; here a small fetch behind a busy
#: queue returns when its own program ends. A DERIVED constant, from the
#: chip (PERF.md section 6, PR 42): at ONE step a program every serve
#: cell's gap fell to a step, but a step costs the device ~2% more as a
#: program of its own (Kimi: a clean turn of 8.549 ms against 67.014 / 8,
#: over the 0.5% ISSUE 42 allowed it) and the cell with the least to win
#: back from the queue paid it whole (Command A+: 966.20 -> 954.42
#: tokens/s, -1.22% under a bound of 1%); at TWO the same cell reads
#: 977.60 -> 973.44 as a pair (-0.43%) and 966.33 unpaired. So two: the
#: smallest count at which every cell's rate keeps its bound, and a gap
#: a quarter of the chunk's. Not an argument's default to tune per
#: model: one value.
_DECODE_STEPS = 2

#: fewest decode programs the pipelined loop keeps issued and unfetched
#: (the double buffer: one running while the host consumes the one
#: before), and the most decode STEPS it may stand ahead of the host —
#: the two chunks of eight the loop held at its deepest before PR 42
_DEPTH_FLOOR = 2
_MAX_STEPS_AHEAD = 16


def _nobody_waiting() -> tuple[int, int]:
    """(occupied slots, requests waiting) of a batcher no engine drives."""
    return 0, 0


def _count_trace(name: str, shape) -> None:
    TRACE_COUNTS[(name, tuple(shape))] += 1


class _Depth:
    """How many decode programs the pipelined loop keeps issued and
    unfetched, from what the engine thread measures of itself at every
    fetch's return (:meth:`ContinuousBatcher._await`): its HOST time a
    turn — the turn less what it spent blocked on the device — against
    the device's time a program, the clean turns'. While the host works
    through one turn the device works through the queue, so the queue
    has to hold the host turn's worth of programs and the one the host
    fetches next: ``ceil(host / step) + 1``, never under
    ``_DEPTH_FLOOR``, never more than ``_MAX_STEPS_AHEAD`` steps. The
    host estimate rises at once and falls slowly — the turn that has to
    be covered is the long one, an admission's marshalling, not the
    mean — and one observation counts for no more than the cap can
    cover, so a stall does not hold the depth up for long after it. No
    argument and no per-model constant: 6 slots or 64, a step of 8 ms
    or 19, each deployment reads its own."""

    def __init__(self, steps_a_program: int) -> None:
        self.cap = max(_DEPTH_FLOOR, _MAX_STEPS_AHEAD // steps_a_program)
        self.host = 0.0      # seconds of host work a turn (rises at once)
        self.step = 0.0      # seconds of a clean turn: a program's own
        self.depth = _DEPTH_FLOOR

    def turn(self, host: float, took: float, clean: bool) -> None:
        """Fold one closed turn: ``took`` seconds long, ``host`` of
        them not blocked on the device; ``clean`` as ``turn_clean``."""
        if clean:
            self.step += (took - self.step) / 8 if self.step else took
        if not self.step:
            return
        host = min(host, self.cap * self.step)
        self.host = max(host, self.host + (host - self.host) / 16)
        self.depth = min(self.cap, max(
            _DEPTH_FLOOR, 1 + int(-(-self.host // self.step))))


def admit_width(bucket: int, slots: int) -> int:
    """Rows of one bucketed admission dispatch at ``bucket`` positions a
    row: as many as fit ``_ADMIT_TOKEN_BUDGET``, at least one, at most
    the slot count."""
    return max(1, min(slots, _ADMIT_TOKEN_BUDGET // bucket))


def bucket_for(n: int, cap: int,
               ladder: Sequence[int] | None = None) -> int:
    """Padded admission length for an ``n``-token prompt: the smallest
    power-of-two (or custom ``ladder``) bucket >= n, clamped to ``cap``
    (the cache's admissible length). Powers of two are
    flash-block-aligned at every size, so TPU prefill never re-pads a
    bucket. THE bucket ladder — shared by the batcher's admission, the
    disaggregated prefill gang, and the decode gang's KV landing, so two
    gangs padding independently agree on the compiled-program set."""
    if ladder is not None:
        for b in ladder:
            if b >= n:
                return min(b, cap)
        return cap
    b = _MIN_ADMIT_BUCKET
    while b < n:
        b <<= 1
    return min(b, cap)


@jax.named_scope("sample")
def _row_samples(logits, keys, temperature, top_k, top_p):
    """One sampling decision per row from PER-ROW keys [B, 2] — argmax
    at ``temperature == 0`` (keys unused; pass None), otherwise the same
    filter stack as :func:`decode.generate` followed by a vmapped
    per-row categorical. The SINGLE implementation behind
    :func:`step_rows`' scan body and the batched speculative admitters'
    seed draws, so the "same filter stack as generate" contract cannot
    drift between the admission seed and the step/round draws."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    f = _filter_logits(logits.astype(jnp.float32), temperature, top_k,
                       top_p)
    return jax.vmap(jax.random.categorical)(keys, f)


def _stream_draws(logits, keys, offsets, temperature, top_k, top_p):
    """One decision per row at its OWN stream position: row ``r`` samples
    from ``fold_in(keys[r], offsets[r])`` (argmax at ``temperature ==
    0``). The one draw behind every step of :func:`step_rows` and behind
    :func:`first_tokens`, so the token that leaves ahead of a chunk IS
    that chunk's step 0."""
    step_keys = (jax.vmap(jax.random.fold_in)(keys, offsets)
                 if temperature > 0.0 else None)
    return _row_samples(logits, step_keys, temperature, top_k, top_p)


def _place_prefill(cache, mini, row, s_p):
    """Land a batch-1 prefill's K/V into cache slot ``row`` (one
    contiguous ``dynamic_update_slice`` per buffer — k/v plus int8
    scales when the cache is quantized) and set the row's frontier to
    the prompt length."""
    placed = {n: jax.lax.dynamic_update_slice(
                  cache[n], mini[n], (0, row) + (0,) * (mini[n].ndim - 2))
              for n in _kv_bufs(mini)}
    return dict(placed, length=cache["length"].at[row].set(s_p))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache", "logits"))
def admit_row_ring(params, cache, logits, row, prompt, cfg):
    """Admit a request into cache slot ``row`` at its EXACT length —
    the rolling (ring) cache admission: wrapped writes cannot take
    padded prompts (padding at position p would land on ring row
    ``p % capacity``, clobbering real history), so ring admission keeps
    one compiled program per distinct prompt length by construction.

    prompt: [1, S_p]. Returns (cache, logits) with the row's K/V
    filled, its frontier at S_p, and its next-step logits seeded."""
    _count_trace("admit_row_ring", prompt.shape)
    lg1, mini = prefill(params, prompt, cfg, max_len=prompt.shape[1])
    return (_place_prefill(cache, mini, row, prompt.shape[1]),
            logits.at[row].set(lg1[0]))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache", "logits"))
def admit_rows(params, cache, logits, rows, prompts, lengths, cfg):
    """BUCKETED, BATCHED admission: land K prompts (one length bucket)
    into K freed cache slots in ONE dispatch.

    prompts: [K, S_bucket] right-padded to the bucket; lengths: [K]
    true prompt lengths (TRACED — any mix of real lengths reuses the
    bucket's compiled program); rows: [K] target slots, with unused
    entries set to DISTINCT out-of-range sentinels (>= batch) whose
    scatter updates drop — the batcher always pads K to the bucket's
    :func:`admit_width`, so each bucket compiles exactly one program
    however many slots freed. The prefill runs all K rows
    (:func:`~tony_tpu.models.decode.prefill_rows`), each slot's K/V land
    via one batch-axis scatter per buffer
    (:func:`~tony_tpu.models.decode.place_rows`), and each slot's
    next-step logits seed from its true last prompt position. Returns
    (cache, logits, stats): ``stats`` is :func:`_device_stats` of the
    prefill — empty for a model without experts, which gains no
    output."""
    _count_trace("admit_rows", prompts.shape)
    lg, mini = prefill_rows(params, prompts, lengths, cfg)
    return (place_rows(cache, mini, rows, lengths),
            logits.at[rows].set(lg, mode="drop", unique_indices=True),
            _device_stats(mini))


def _device_stats(cache: dict) -> dict:
    """What the program counted on the device, as it leaves with the
    tokens: the expert layers' ``[assignments landed on held experts,
    held experts touched]`` and, where the model has zero experts,
    ``assignments that were one`` (summed over layers; a decode chunk's
    idle slots included — they are routed like any other — a prompt's
    padding not) for a model with experts,
    NOTHING for one without: an empty pytree adds no output, so the dense
    programs lower to the HLO they always had."""
    return {MOE_COUNTS: cache[MOE_COUNTS]} if MOE_COUNTS in cache else {}


def prefix_template(params, prefix, cfg):
    """Prefill a SHARED PREFIX once (a system prompt every request
    continues from); returns the [L, 1, P, KV·hd] K/V template (the
    cache's own stored form) :func:`prefix_admit_rows` copies into each
    admitted slot. prefix:
    [P] ints. Rolling caches are rejected up front: a ring-shaped
    buffer's shape[2] is the capacity, which the template consumers
    would misread as the prefix length and build a corrupt cache."""
    _check_no_ring(cfg, "prefix templates")
    cfg.refuse("prefix templates")
    _, mini = prefill(params, jnp.asarray(prefix, jnp.int32)[None], cfg,
                      max_len=len(prefix))
    return _kv_bufs(mini)


class PrefixEntry:
    """One RESIDENT shared prefix in a batcher's prefix store: the
    token sequence (for matching and suffix splitting) plus its
    precomputed K/V ``template`` (:func:`prefix_template` shape —
    ``[L, 1, P, KV·hd]`` per buffer). ``draft_template`` is the
    speculative batcher's draft-model template (computed locally at
    install — template ships carry only the target's K/V)."""

    __slots__ = ("id", "tokens", "template", "draft_template")

    def __init__(self, prefix_id: str, tokens: list, template: dict,
                 draft_template: dict | None = None) -> None:
        self.id = prefix_id
        self.tokens = tokens
        self.template = template
        self.draft_template = draft_template


class _PrefixHit:
    """Engine-side admission payload for a request that matched a
    resident prefix: only ``suffix`` runs a forward; the prefix K/V
    come from ``entry.template``. Routed by ``_admit_batch`` exactly
    like :class:`KVPackage` payloads are — one admission seam, three
    admission kinds."""

    __slots__ = ("entry", "suffix")

    def __init__(self, entry: PrefixEntry, suffix: list) -> None:
        self.entry = entry
        self.suffix = suffix


def validate_template_bufs(proto: dict, tokens, bufs: dict) -> dict:
    """Validate a (possibly shipped) prefix template — wire form,
    ``[L, 1, P, KV, hd]`` per buffer — against the serving config's
    wire layout ``proto`` (:func:`~tony_tpu.models.decode.
    kv_wire_layout`): buffer-name set, dtypes, layer count, and
    trailing head dims must match, and the sequence extent must equal
    the prefix length. Raises ``ValueError`` naming the mismatch —
    request-scoped at the install path, exactly like a mismatched KV
    row shipment. Returns the buffers as device arrays in the cache's
    stored form."""
    p_len = len(tokens)
    if set(bufs) != set(proto):
        raise ValueError(
            f"template buffers {sorted(bufs)} do not match this cache's "
            f"layout {sorted(proto)} (quantization mismatch?)")
    out = {}
    for n, c in proto.items():
        a = np.asarray(bufs[n])
        if a.dtype != c.dtype:
            raise ValueError(f"template buffer {n!r} dtype {a.dtype} "
                             f"!= cache dtype {c.dtype}")
        if a.ndim == c.ndim and a.shape[0] != c.shape[0]:
            raise ValueError(
                f"template buffer {n!r} carries {a.shape[0]} layers; "
                f"this model has {c.shape[0]} (layer mismatch between "
                f"producer and installer?)")
        if (a.ndim != c.ndim or a.shape[1] != 1
                or a.shape[3:] != c.shape[3:]):
            raise ValueError(f"template buffer {n!r} shape "
                             f"{list(a.shape)} does not fit this "
                             f"cache's wire layout {list(c.shape)}")
        if a.shape[2] != p_len:
            raise ValueError(f"template buffer {n!r} holds {a.shape[2]} "
                             f"positions for a {p_len}-token prefix")
        out[n] = a
    return {n: jnp.asarray(a) for n, a in kv_from_wire(out).items()}


def _extend_rows_from_template(model_params, template, suffixes, lengths,
                               model_cfg):
    """Tile the prefix template across K rows and run all K right-padded
    suffixes [K, S_b] through the model against it in one chunked
    :func:`extend_step` (suffix queries attend the full prefix history
    exactly as a monolithic prefill of prefix+suffix would). Each row's
    padding-tail K/V land beyond its frontier (unreachable — the
    bucketed-admission argument). Returns (per-row
    last-REAL-suffix-position logits [K, V], mini cache, per-row totals
    P + lengths)."""
    p_len = template["k"].shape[2]
    k_rows, s_len = suffixes.shape
    mini = dict(
        {n: jnp.concatenate(
            [jnp.broadcast_to(x, (x.shape[0], k_rows) + x.shape[2:]),
             jnp.zeros(x.shape[:1] + (k_rows, s_len) + x.shape[3:],
                       x.dtype)], axis=2)
         for n, x in template.items()},
        length=jnp.asarray(p_len, jnp.int32))
    lg, mini = extend_step(model_params, suffixes, mini, p_len, model_cfg)
    return (lg[jnp.arange(k_rows), lengths - 1], mini,
            p_len + lengths.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache", "logits"))
def prefix_admit_rows(params, cache, logits, rows, template, suffixes,
                      lengths, cfg):
    """Bucketed, batched shared-prefix admission: K suffixes (one length
    bucket, right-padded) continue the precomputed prefix ``template``
    and land in K freed slots in one dispatch — the
    :func:`admit_rows` contract (sentinel-padded ``rows``, traced true
    ``lengths``, one compiled program per bucket) applied to
    O(suffix)-cost prefix admission."""
    _count_trace("prefix_admit_rows", suffixes.shape)
    lg, mini, totals = _extend_rows_from_template(params, template,
                                                  suffixes, lengths, cfg)
    return (place_rows(cache, mini, rows, totals),
            logits.at[rows].set(lg, mode="drop", unique_indices=True))


@functools.partial(jax.jit, static_argnames=("cfg", "n", "temperature",
                                             "top_k", "top_p"),
                   donate_argnames=("cache", "logits"))
def step_rows(params, cache, logits, keys, offsets, n, cfg,
              temperature=0.0, top_k=0, top_p=0.0):
    """``n`` decode steps for every row at its OWN frontier — greedy at
    ``temperature=0`` (default), otherwise sampled per row through the
    same filter stack as :func:`tony_tpu.models.decode.generate`
    (top-k → temperature → nucleus). ``keys``: [B, 2] PER-ROW PRNG keys
    (each row's occupant request's stream); ``offsets``: [B] int32
    per-row counts of draws already taken, so step ``j`` samples row
    ``r`` from ``fold_in(keys[r], offsets[r] + j)`` — a request's
    samples are a function of its own stream position alone, independent
    of batch composition or admission timing (what lets the pipelined
    loop shift admissions without changing outputs). Returns (tokens
    [B, n], cache, logits, stats — :func:`_device_stats` of this chunk).
    Idle rows decode garbage that the host discards — uniform batch math
    keeps this one compiled program regardless of which rows are
    live."""
    _count_trace("step_rows",
                 (next(iter(_kv_bufs(cache).values())).shape, n))
    if MOE_COUNTS in cache:             # count this chunk alone
        cache = dict(cache, **{MOE_COUNTS: jnp.zeros_like(
            cache[MOE_COUNTS])})

    def body(carry, j):
        lg, c = carry
        tok = _stream_draws(lg, keys, offsets + j, temperature, top_k,
                            top_p)
        lg, c = decode_step(params, tok, c, c["length"], cfg)
        return (lg, c), tok

    (lg, cache), toks = jax.lax.scan(body, (logits, cache),
                                     jnp.arange(n))
    return toks.T, cache, lg, _device_stats(cache)


@functools.partial(jax.jit, static_argnames=("temperature", "top_k",
                                             "top_p"))
def first_tokens(logits, keys, offsets, temperature=0.0, top_k=0,
                 top_p=0.0):
    """Every row's NEXT token from the engine's ``logits`` as they stand:
    the draw step 0 of the next :func:`step_rows` makes
    (:func:`_stream_draws`), enqueued right behind an admission wave so
    that an admitted request's first token can leave when its admission
    has run and not a chunk later. One width (all rows in, [B] tokens
    out; the host keeps the rows it admitted) and nothing donated: ONE
    traced program whatever the wave held, and whichever admission
    program seeded the logits."""
    _count_trace("first_tokens", logits.shape)
    return _stream_draws(logits, keys, offsets, temperature, top_k, top_p)


@functools.partial(jax.jit, donate_argnames=("cache",))
def retire_rows(cache, mask):
    """Reset retired rows' frontiers to 0 (mask: [B] bool). Keeps idle
    slots from marching their garbage frontier into the cache end."""
    return dict(cache, length=jnp.where(mask, 0, cache["length"]))


# ---------------------------------------------------------------------------
# Disaggregated prefill/decode (the KV-shipping device programs)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill_ship_rows(params, prompts, lengths, cfg):
    """Prefill one admission bucket of prompts FOR SHIPMENT: the exact
    :func:`~tony_tpu.models.decode.prefill_rows` program the colocated
    ``admit_rows`` runs, minus the landing — the prefill gang has no
    persistent cache to land into; each row's mini-cache K/V, last-real
    logits, and frontier ship to a decode gang instead
    (``tony_tpu/serving/disagg.py``). Because both sides run the same
    bucket ladder and the same prefill program, the shipped buffers are
    bit-identical to what colocated admission would have written —
    which is what makes disaggregated serving token-identical. prompts:
    [K, S_bucket] right-padded, lengths: [K] TRACED; one compiled
    program per (K, bucket) — the worker pads K to its wave size."""
    _count_trace("prefill_ship_rows", prompts.shape)
    return prefill_rows(params, prompts, lengths, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill_ship_row(params, prompt, cfg):
    """Exact-length batch-1 prefill for shipment — the rolling (ring)
    cache path, whose wrapped writes cannot take padded prompts; the
    FULL capacity-row ring ships (the wrap is positional — landing is a
    whole-slot write). Retraces per distinct prompt length, exactly as
    colocated ring admission does. prompt: [1, S_p]."""
    _count_trace("prefill_ship_row", prompt.shape)
    return prefill(params, prompt, cfg, max_len=prompt.shape[1])


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefix_ship_rows(params, template, suffixes, lengths, cfg):
    """Prefill one admission bucket of SUFFIXES for shipment against a
    resident prefix ``template`` — the disaggregated counterpart of
    :func:`prefix_admit_rows`: only the suffix tokens run a forward
    (:func:`_extend_rows_from_template`), and the resulting
    prefix+suffix mini cache ships to a decode gang exactly like a
    full-prefill row. The prefill tier's prefix fast path: at a hot
    shared prefix, shipped-row prefill compute drops from O(P+S) to
    O(S) tokens per request while the decode gang needs no prefix
    knowledge at all. suffixes: [K, S_bucket] right-padded; lengths:
    [K] TRACED true suffix lengths. Returns (per-row last-real-suffix
    logits [K, V], mini cache [L, K, P+S_bucket, ...])."""
    _count_trace("prefix_ship_rows", suffixes.shape)
    lg, mini, _ = _extend_rows_from_template(params, template, suffixes,
                                             lengths, cfg)
    return lg, mini


@functools.partial(jax.jit, donate_argnames=("cache", "logits"))
def land_kv_rows(cache, logits, rows, mini, lengths, row_logits, keys,
                 row_keys):
    """Land K shipped-and-bucket-padded KV rows into freed cache slots:
    pure scatters — :func:`~tony_tpu.models.decode.place_rows` on the
    buffers plus the logits and rng-key rebinds — with NO model
    forward, which is the entire point of disaggregation: admission on
    the decode gang costs a memcpy, never a prefill that stalls the
    in-flight decode chunk. Takes the ``admit_rows`` sentinel contract
    (``rows`` padded to the slot count with out-of-range entries whose
    scatters drop), so each bucket compiles exactly one program.
    ``row_keys``: [B, 2] shipped per-request rng stream keys landing
    into the batcher's key table ``keys`` — the decode gang samples
    from the SAME per-request stream the prefill gang derived. Returns
    (cache, logits, keys)."""
    _count_trace("land_kv_rows", mini["k"].shape)
    return (place_rows(cache, mini, rows, lengths),
            logits.at[rows].set(row_logits, mode="drop",
                                unique_indices=True),
            keys.at[rows].set(row_keys, mode="drop",
                              unique_indices=True))


class KVPackage:
    """One prefilled request's device state, shipped from a prefill
    gang to a decode gang (disaggregated serving).

    - ``bufs``: host copies of the row's cache buffers (``k``/``v``
      plus int8 ``k_scale``/``v_scale`` when the cache is quantized —
      int8 caches ship QUANTIZED, ~half the bytes of a dequantized
      ship), each ``[L, 1, S, KV, hd]``-shaped, truncated at the true
      prompt length for linear caches (the padding tail past the
      frontier is unreachable garbage — why ship it) or the full
      capacity ring for rolling caches;
    - ``length``: the row's frontier (true prompt length; for rings the
      absolute position, which may exceed the capacity);
    - ``logits``: [V] last-REAL-position logits seeding the first
      decode step;
    - ``rng_key``: [2] uint32 per-request stream key and ``rng_off``
      stream position — the rng stream state that makes SAMPLED
      disaggregated output identical to colocated serving.

    The wire form lives in ``tony_tpu/serving/kvship.py`` (jax-free);
    this class is the decode-side landing record
    (:meth:`ServeEngine.submit_prefilled`)."""

    __slots__ = ("bufs", "length", "logits", "rng_key", "rng_off")

    def __init__(self, bufs: dict, length: int, logits, rng_key,
                 rng_off: int = 0) -> None:
        self.bufs = bufs
        self.length = int(length)
        self.logits = logits
        self.rng_key = rng_key
        self.rng_off = int(rng_off)

    @property
    def width(self) -> int:
        """Shipped cache positions per layer (the buffers' S extent)."""
        return self.bufs["k"].shape[2]


@functools.partial(jax.jit, static_argnames=("cfg", "draft_cfg",
                                             "temperature", "top_k",
                                             "top_p"),
                   donate_argnames=("t_cache", "d_cache", "pending"))
def spec_admit_rows(params, draft_params, t_cache, d_cache, pending,
                    rows, prompts, lengths, keys, cfg, draft_cfg,
                    temperature=0.0, top_k=0, top_p=0.0):
    """Bucketed, batched speculative admission: K prompts (one length
    bucket) prefill BOTH models in one dispatch each and land in K
    freed slots — the :func:`admit_rows` contract applied to the
    speculative batcher's dual caches. ``keys``: [K, 2] per-request
    seed-draw keys (stream position 0 of each request; rounds consume
    positions 1+), used only at ``temperature > 0``."""
    _count_trace("spec_admit_rows", prompts.shape)
    lg, mini_t = prefill_rows(params, prompts, lengths, cfg)
    _, mini_d = prefill_rows(draft_params, prompts, lengths, draft_cfg)
    t_cache = place_rows(t_cache, mini_t, rows, lengths)
    d_cache = place_rows(d_cache, mini_d, rows, lengths)
    seed_tok = _row_samples(lg, keys, temperature, top_k, top_p)
    pending = pending.at[rows].set(seed_tok.astype(pending.dtype),
                                   mode="drop", unique_indices=True)
    return t_cache, d_cache, pending


@functools.partial(jax.jit, static_argnames=("cfg", "draft_cfg",
                                             "temperature", "top_k",
                                             "top_p"),
                   donate_argnames=("t_cache", "d_cache", "pending"))
def spec_prefix_admit_rows(params, draft_params, t_cache, d_cache,
                           pending, rows, t_template, d_template,
                           suffixes, lengths, keys, cfg, draft_cfg,
                           temperature=0.0, top_k=0, top_p=0.0):
    """Bucketed, batched shared-prefix speculative admission: K suffixes
    (one length bucket) continue both models' templates in one chunked
    extend each (:func:`_extend_rows_from_template`) and land in K freed
    slots, seeding each slot's pending from its true last suffix
    position."""
    _count_trace("spec_prefix_admit_rows", suffixes.shape)
    lg, mini_t, totals = _extend_rows_from_template(params, t_template,
                                                    suffixes, lengths,
                                                    cfg)
    _, mini_d, _ = _extend_rows_from_template(draft_params, d_template,
                                              suffixes, lengths,
                                              draft_cfg)
    t_cache = place_rows(t_cache, mini_t, rows, totals)
    d_cache = place_rows(d_cache, mini_d, rows, totals)
    seed_tok = _row_samples(lg, keys, temperature, top_k, top_p)
    pending = pending.at[rows].set(seed_tok.astype(pending.dtype),
                                   mode="drop", unique_indices=True)
    return t_cache, d_cache, pending


@functools.partial(jax.jit, static_argnames=("cfg", "draft_cfg", "n", "k",
                                             "temperature", "top_k",
                                             "top_p"),
                   donate_argnames=("t_cache", "d_cache", "pending"))
def spec_step_rows(params, draft_params, t_cache, d_cache, pending, keys,
                   offsets, n, cfg, draft_cfg, k, temperature=0.0,
                   top_k=0, top_p=0.0):
    """``n`` speculative rounds for every row at its OWN frontier — the
    serving analog of :func:`step_rows` built on the same
    propose-and-verify round the speculative decoder uses
    (:func:`tony_tpu.models.decode._propose_and_verify`). Each round every
    row commits its full per-row acceptance ``acc_r + 1`` (serving has no
    generation budget on device — the host truncates at each request's
    budget/eos and discards idle rows' garbage, exactly as in greedy
    continuous batching). Returns ``(packed [n, B, k+2], t_cache,
    d_cache, pending)`` where ``packed[i, r, 0]`` is round i's per-row
    commit count and ``packed[i, r, 1:]`` its k+1-wide token chunk —
    row r's committed tokens for round i are
    ``packed[i, r, 1:1+packed[i, r, 0]]``, in order. ONE output array by
    design: the host syncs on this value every ``n`` rounds, and each
    separately-fetched device array costs its own device→host round
    trip (returning chunks and counts apart doubles the per-sync cost
    against the greedy batcher's single token array).

    ``temperature > 0`` runs SAMPLED rounds instead
    (:func:`decode._propose_and_verify_sampled`, handed PER-ROW round
    keys ``fold_in(keys[r], offsets[r] + i)`` — each slot's draws come
    from its occupant request's own stream, the same
    admission-timing-independence contract as :func:`step_rows`):
    serving commits the full per-row acceptance every round, so each
    slot's next pending is simply the round's residual/bonus sample,
    and each request's committed stream is distributed exactly as
    target-only sampling through the same filter stack."""
    _count_trace("spec_step_rows", (t_cache["k"].shape, n, k))

    def body(carry, i):
        t_cache, d_cache, pending = carry
        pos = t_cache["length"]                                  # [B]
        if temperature == 0.0:
            chunk, argmaxes, acc, t_cache, d_cache = _propose_and_verify(
                params, draft_params, t_cache, d_cache, pending, pos,
                cfg, draft_cfg, k, None, pending.dtype)
            pending = jnp.take_along_axis(argmaxes, acc[:, None],
                                          axis=1)[:, 0]
        else:
            round_keys = jax.vmap(jax.random.fold_in)(keys, offsets + i)
            chunk, extra, acc, t_cache, d_cache = (
                _propose_and_verify_sampled(
                    params, draft_params, t_cache, d_cache, pending,
                    pos, cfg, draft_cfg, k, None, pending.dtype,
                    round_keys, temperature, top_k, top_p))
            pending = extra
        count = acc + 1
        new_len = (pos + count).astype(jnp.int32)
        t_cache = dict(t_cache, length=new_len)
        d_cache = dict(d_cache, length=new_len)
        packed = jnp.concatenate(
            [count[:, None].astype(jnp.int32),
             chunk.astype(jnp.int32)], axis=1)                   # [B, k+2]
        return (t_cache, d_cache, pending), packed

    (t_cache, d_cache, pending), packed = jax.lax.scan(
        body, (t_cache, d_cache, pending), jnp.arange(n))
    return packed, t_cache, d_cache, pending


class ContinuousBatcher:
    """Host-side admission loop over the device programs above.

    ``serve(prompts, max_new_tokens)`` runs every request to completion
    (``max_new_tokens`` or ``eos_id``) through a fixed ``batch`` of cache
    slots, admitting the next queued request the moment a slot frees.
    At the default ``temperature=0`` outputs are the same greedy tokens
    :func:`decode.generate` produces for each request alone
    (test-verified token-identical on CPU); with ``temperature``/
    ``top_k``/``top_p`` set, slots sample through the same filter stack
    as ``generate`` instead, from per-request key streams (see
    ``__init__``).

    The serve loop is PIPELINED (``pipeline=True``): a queue of issued
    programs — one decode step each unless ``chunk`` says otherwise — as
    deep as the engine's own host turn needs (:class:`_Depth`),
    overlapping the fetch and the host bookkeeping with device compute;
    each step's tokens leave when it has run. ``pipeline=False`` is the
    sequential
    issue→fetch→bookkeep→admit loop that the tests hold the pipelined
    one to, token for token, in the sampled and speculative modes where
    ``decode.generate`` is no oracle; nothing else selects it.

    Admission is BUCKETED and BATCHED: prompts pad to power-of-two
    length buckets (compile once per bucket, not once per distinct
    prompt length) and the slots freed in the same chunk land in
    :func:`admit_width`-wide :func:`admit_rows` dispatches. A rolling
    (ring) cache, which the batcher reads off ``cfg.kv_cache_capacity``,
    takes one exact-length :func:`admit_row_ring` dispatch a request —
    padded prompts cannot take wrapped writes. A model with layer_kinds
    whose WINDOW kinds hold a ring beside full kinds' linear rows is no
    such cache: its prefill writes a linear mini cache that
    :func:`~tony_tpu.models.decode.place_rows` lands in the rings by
    each row's length, so it is admitted by :func:`admit_rows` like
    every linear-cache model, and a request may not outgrow the FULL
    kind's ``max_len`` rows (the window kind never limits a length).
    """

    #: first per-request stream position consumed by step_rows sampling
    #: (the speculative batcher's admission seed-draw takes position 0,
    #: so its rounds start at 1)
    _off0 = 0

    def __init__(self, params, cfg: T.TransformerConfig, batch: int,
                 max_len: int, eos_id: int | None = None,
                 chunk: int = _DECODE_STEPS, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0,
                 seed: int = 0,
                 shared_prefix=None, pipeline: bool = True,
                 admission_buckets: Sequence[int] | None = None) -> None:
        self.params = params
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.eos_id = eos_id
        if shared_prefix is not None:
            cfg.refuse("shared-prefix caching")
        #: shared-prefix caching: when set (a token sequence, e.g. a
        #: system prompt), every request's prompt is interpreted as a
        #: CONTINUATION of it — the prefix prefills once into a K/V
        #: template that admission copies into the slot, and only the
        #: request's own tokens run a forward (prefix_admit_rows).
        #: Outputs are token-identical to serving prefix+prompt in full.
        self.shared_prefix = (None if shared_prefix is None
                              else list(shared_prefix))
        if self.shared_prefix is not None and not self.shared_prefix:
            raise ValueError("shared_prefix must be non-empty when given")
        #: rolling KV cache (cfg.kv_cache_capacity): slots hold a ring
        #: of O(window) rows and requests may run past max_len — the
        #: budget check below relaxes accordingly. Prefix templates are
        #: positional and don't survive ring wraparound.
        self._ring = bool(cfg.kv_cache_capacity)
        if self.shared_prefix is not None:
            # prefix templates are positional; they don't survive ring
            # wraparound
            _check_no_ring(cfg, "shared-prefix caching")
        self._prefix_template = (
            prefix_template(params, self.shared_prefix, cfg)
            if self.shared_prefix else None)
        #: RESIDENT prefix templates (prefix-aware serving): id ->
        #: PrefixEntry. Entries are immutable once published and the
        #: dict is only ever grown, so install threads and the engine's
        #: reader-thread resolution need no lock (GIL-atomic dict ops).
        self._prefix_store: dict = {}
        self._ring_prefix_warned = False
        #: host-side prefill-compute accounting (the prefix fast path's
        #: FLOPs story, folded into the metrics plane by ServeEngine):
        #: true tokens run through a prefill/extend forward at
        #: admission vs prefix positions satisfied by a template COPY
        self.prefill_forward_tokens = 0
        #: positions those forwards RAN: a bucketed dispatch's rows x
        #: bucket (:func:`admit_width`), a ring admission's own length.
        #: forward / padded is the share of prefilled positions that
        #: were a real prompt's
        self.prefill_padded_tokens = 0
        self.prefix_copied_tokens = 0
        self.prefix_admits = 0
        #: sampling controls (greedy by default). Streams are
        #: PER-REQUEST: request q's t-th draw comes from
        #: fold_in(fold_in(PRNGKey(seed), q), t) — a re-served workload
        #: with the same seed reproduces its outputs, and a request's
        #: samples depend only on (seed, its index, its prompt), not on
        #: admission timing or what else shares the batch
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.seed = seed
        #: decode steps ONE ``step_rows`` program runs (``_DECODE_STEPS``
        #: unless given): the unit of delivery — a program's tokens reach
        #: the host together. How many programs stand in the device's
        #: queue is the pipelined loop's (:class:`_Depth`), not this
        self.chunk = max(1, chunk)
        #: a queue of issued programs (see class docstring)
        self.pipeline = bool(pipeline)
        if admission_buckets is not None:
            ladder = sorted({int(b) for b in admission_buckets})
            if not ladder or ladder[0] < 1:
                raise ValueError("admission_buckets must be positive "
                                 f"lengths, got {admission_buckets}")
            self.admission_buckets: tuple[int, ...] | None = tuple(ladder)
        else:
            self.admission_buckets = None          # auto: powers of two
        self.cache = init_kv_cache(cfg, batch, max_len)
        # per-row frontiers from the start (decode.py's [B] position path)
        self.cache = dict(self.cache,
                          length=jnp.zeros((batch,), jnp.int32))
        self.logits = jnp.zeros((batch, cfg.vocab_size),
                                cfg.logits_storage_dtype)
        self.steps_executed = 0
        self.rounds_executed = 0
        #: the expert layers' device counters, folded per program kind
        #: ("decode": step_rows chunks, "admit": admit_rows prefills) as
        #: each chunk's tokens are fetched: (token, pick) assignments
        #: that landed on held experts, and (layer, held expert) pairs
        #: with at least one — the weights a step had to read. A decode
        #: chunk's idle slots count (the device routes them like any
        #: other); a prompt's padding is not routed.
        self.moe_assignments = {"decode": 0, "admit": 0}
        self.moe_touches = {"decode": 0, "admit": 0}
        #: (token, pick) assignments that were a ZERO expert (the
        #: identity: no weight read, no product); 0 for a model without
        self.moe_zero_assignments = {"decode": 0, "admit": 0}
        #: bytes of the cache by the kind of state that owns them
        #: (decode.cache_bytes_by_kind): a window kind's ring beside a
        #: full kind's max_len rows
        self.cache_bytes = cache_bytes_by_kind(cfg, batch, max_len)
        #: (rows a slot's ring holds, layers that write one): the
        #: whole-model ring of the flat decoder, or the window kinds' of
        #: a model with layer_kinds; (0, 0) without a ring
        self._ring_shape = (0, 0)
        if self._ring:
            self._ring_shape = (cfg.kv_cache_capacity, cfg.n_layers)
        elif cfg.kinded and cfg.attn_window:
            self._ring_shape = (ring_rows(cfg),
                                cfg.attention_layers()["window"])
        #: ring rows a finished request's positions overwrote (a layer a
        #: position at or past the ring's rows): where this is 0 the
        #: window never bound and a linear buffer of the ring's size
        #: would have served
        self.ring_rows_overwritten = 0
        #: the host's copy of the device's per-row frontiers
        #: (``cache["length"]``): an admission sets a row's, every decode
        #: step advances every row's, a retirement zeroes it
        self._row_len = np.zeros((batch,), np.int64)
        #: cache rows the decode chunks' reads VISITED and rows LIVE
        #: under their masks, by the kind of state, summed over its
        #: layers (decode.cache_rows_visited, at each chunk's issue; an
        #: idle slot counts as the device reads it). live / read is how
        #: far the cached read follows each row's own length
        kinds = cache_rows_visited(cfg, max_len,
                                   np.zeros((batch, 0), np.int64))
        self.cache_rows_read = dict.fromkeys(kinds, 0)
        self.cache_rows_live = dict.fromkeys(kinds, 0)
        #: a model with state-space mixers (``cfg.ssm``; {} without):
        #: host arithmetic, as the rows above. ``state_bytes``: bytes of
        #: the recurrence the decode chunks' steps read AND wrote, every
        #: slot (the device steps idle ones too); ``state_live_bytes``:
        #: the occupied slots' part — live / all is what idle slots
        #: cost. ``scan_positions``: positions the admissions' prompt
        #: scans ran over, a mixer a position; ``scan_live_positions``:
        #: those below their rows' lengths — the rest was padding.
        self._ssm_mixers = (cfg.attention_layers().get("ssm", 0)
                            if cfg.kinded else 0)
        self.ssm_counts = dict.fromkeys(
            ("state_bytes", "state_live_bytes", "scan_positions",
             "scan_live_positions"), 0) if self._ssm_mixers else {}
        self._device_stats: collections.deque = collections.deque()
        self.phase_times = PhaseTimes(ENGINE_PHASES)
        #: () -> (occupied slots, requests waiting) as the loop driving
        #: this batcher sees them (:meth:`ServeEngine._queue_load`): what
        #: a ``dispatch`` row says of its batch, and what makes a turn
        #: loaded. An engine lends its own for the length of its run.
        self._load = _nobody_waiting
        # seams usable standalone (no serve() call required); serve()
        # re-seeds for per-workload reproducibility
        self._reset_streams()
        self._reset_timeline()

    # --- per-request rng streams ---

    def _reset_streams(self) -> None:
        self._base_key = jax.random.PRNGKey(self.seed)
        idle = jax.random.fold_in(self._base_key, _IDLE_STREAM)
        #: [B, 2] per-row keys: each row carries its occupant REQUEST's
        #: stream key; idle rows draw garbage from a fixed idle stream
        self._row_keys = jnp.tile(idle[None], (self.batch, 1))
        #: per-row stream positions consumed so far (host-side ints)
        self._row_off = [self._off0] * self.batch
        #: stream index -> positions ALREADY consumed elsewhere (a
        #: migrated session re-admitting with its streamed prefix folded
        #: into the prompt): the row's first sample is drawn at this
        #: offset instead of 0, so the continuation is sample-identical
        #: to the placement it left. Consumed at admission.
        self._stream_skip: dict[int, int] = {}

    def _req_key(self, req: int):
        return jax.random.fold_in(self._base_key, req)

    # --- the device-queue timeline (module docstring) ---

    def _reset_timeline(self) -> None:
        #: programs enqueued so far — decode chunks and admission
        #: dispatches alike, in device-queue order: the next one's
        #: sequence number
        self.seq = 0
        #: decode chunks issued and not fetched, oldest first: (seq,
        #: admission dispatches between the chunk before it and it,
        #: issued right behind that chunk while it was unfetched)
        self._unfetched: collections.deque = collections.deque()
        #: seq of the newest fetched chunk: the device has run every
        #: program up to it (a chunk dropped unfetched stays ahead of
        #: this mark until a later fetch returns, as it does on the
        #: device)
        self._seq_run = -1
        #: one past the last chunk issued (the run's first seq before
        #: its first chunk): the programs since are admissions
        self._seq_chunk = 0
        #: when the turn in progress opened — the last fetch's return,
        #: or the first enqueue of a run; None between runs
        self._t_turn: float | None = None
        #: seq of the admission dispatch that last landed in each row
        self._row_seq = [-1] * self.batch
        #: the pipelined loop's queue depth, from the turns measured here
        self._ahead = _Depth(self.chunk)
        #: seconds the thread had spent blocked on the device (``fetch``
        #: and ``first_fetch``) when the turn in progress opened
        self._waited = 0.0

    def _enqueued(self, rows=()) -> None:
        """Count one program into the device queue, as the call that
        enqueued it returns (inside the phase that made the call);
        ``rows`` are the slots an admission dispatch lands in. A program
        that finds nothing enqueued before it still unfetched starts on
        an idle device, and the host is why: ``starved`` is the time
        since the last fetch returned."""
        now = time.perf_counter()
        if self._t_turn is None:
            self._t_turn = now          # a run's first turn opens here
            self._seq_chunk = self.seq
            self._waited = self._blocked()
        elif self.seq - 1 == self._seq_run:
            self.phase_times.observe("starved", now - self._t_turn)
        for row in rows:
            self._row_seq[row] = self.seq
        self.seq += 1

    def _blocked(self) -> float:
        """Seconds this thread has spent blocked on the device so far:
        the ``fetch`` phase and the engine's ``first_fetch``."""
        pt = self.phase_times
        return pt.total("fetch") + pt.total("first_fetch")

    def _dispatch_phase(self):
        """The ``dispatch`` phase of the program about to be issued; its
        row says which program of the queue it is, how full its batch
        was, how many decode steps stood issued and unfetched ahead of
        it and the depth the loop holds the queue to."""
        live, waiting = self._load()
        return self.phase_times.phase(
            "dispatch", seq=self.seq, live=live, waiting=waiting,
            in_flight=len(self._unfetched) * self.chunk,
            depth=self._ahead.depth)

    def _chunk_enqueued(self) -> None:
        """File the chunk just enqueued among the unfetched, with what
        its turn will be when its fetch returns: since the device runs
        programs in the order they were enqueued, the turn holds this
        chunk and the admission dispatches since the chunk before it."""
        seq = self.seq
        clean = bool(self._unfetched) and self._unfetched[-1][0] == seq - 1
        self._enqueued()
        self._unfetched.append((seq, seq - self._seq_chunk, clean))
        self._seq_chunk = seq + 1
        # decode steps issued and unfetched, this program's own included:
        # a count beside the intervals (its "seconds" are steps)
        self.phase_times.observe("steps_in_flight",
                                 len(self._unfetched) * self.chunk)

    def _await(self, handle):
        """Block on the oldest unfetched chunk (the ``fetch`` phase; its
        row carries the seq it blocks on) and close the turn its return
        ends — the interval since the fetch before it returned:
        ``turn`` always; ``turn_admit`` when admission dispatches lay
        between the two chunks; ``turn_clean`` when none did and the
        chunk was enqueued before that fetch returned (a device-bound
        turn is then the chunk's own time on the device);
        ``turn_loaded`` when it held an admission or a request is
        waiting at its close (offered load, not a drain)."""
        seq, admits, clean = self._unfetched.popleft()
        pt = self.phase_times
        with pt.phase("fetch", seq=seq):
            host = np.asarray(handle)
        now = time.perf_counter()
        took, self._t_turn, self._seq_run = now - self._t_turn, now, seq
        pt.observe("turn", took)
        if admits:
            pt.observe("turn_admit", took)
        elif clean:
            pt.observe("turn_clean", took)
        if admits or self._load()[1]:
            pt.observe("turn_loaded", took)
        # what the loop's depth follows: the turn's host part is the turn
        # less what the thread spent blocked on the device in it
        waited, self._waited = self._waited, self._blocked()
        self._ahead.turn(took - (self._waited - waited), took, clean)
        return host

    # --- resident prefix templates (prefix-aware serving) ---

    def install_prefix(self, prefix_id: str, tokens,
                       template: dict | None = None) -> bool:
        """Make a shared prefix RESIDENT: admissions whose prompt
        continues ``tokens`` run only their suffix through the model
        (:func:`prefix_admit_rows` against the stored template) —
        token-identical to full prefill, test-pinned. ``template``
        None computes the prefill here (ONE forward for the whole
        serve); a template shipped from a peer replica installs with
        ZERO prefix forwards (:func:`validate_template_bufs` guards
        the layout). Rolling (ring) caches cannot host positional
        templates: the batcher DEGRADES to prefix-blind serving with
        one warning and returns False — never an error (ring replicas
        still serve every request, just without the fast path).
        Raises ``ValueError`` for an unusable request (empty tokens,
        no room for a suffix, legacy ``shared_prefix`` mode, or a
        mismatched shipped template)."""
        self.cfg.refuse("resident prefix templates")
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError("prefix tokens must be non-empty")
        if self.shared_prefix is not None:
            raise ValueError(
                "legacy shared_prefix mode already interprets every "
                "prompt as a continuation; per-request prefix "
                "templates compose with plain batchers only")
        if self._ring:
            if not self._ring_prefix_warned:
                self._ring_prefix_warned = True
                import logging
                logging.getLogger(__name__).warning(
                    "rolling (ring) caches cannot host prefix "
                    "templates (positional K/V do not survive "
                    "wraparound); serving prefix-blind")
            return False
        if len(tokens) + 2 > self.max_len:
            raise ValueError(
                f"prefix of {len(tokens)} tokens leaves no room for a "
                f"suffix + generation under max_len {self.max_len}")
        if template is None:
            template = prefix_template(self.params, tokens, self.cfg)
            self.prefill_forward_tokens += len(tokens)
            self.prefill_padded_tokens += len(tokens)
        else:
            template = validate_template_bufs(kv_wire_layout(self.cfg),
                                              tokens, template)
        self._prefix_store[str(prefix_id)] = self._build_entry(
            str(prefix_id), tokens, template)
        return True

    def _build_entry(self, prefix_id: str, tokens: list,
                     template: dict) -> PrefixEntry:
        """Entry construction hook — the speculative subclass adds the
        draft-model template BEFORE the entry is published to the
        store (a half-built entry must never be resolvable)."""
        return PrefixEntry(prefix_id, tokens, template)

    def install_prefix_template(self, meta: dict, bufs: dict) -> str:
        """Land an unpacked SHIPPED template (``kvship.unpack_template``
        output): vocab is checked against this model up front — a
        template from a differently-shaped model is a request-scoped
        ``ValueError`` at the install path, never garbage K/V
        discovered mid-serve. Returns the installed prefix id."""
        if int(meta["vocab"]) != self.cfg.vocab_size:
            raise ValueError(
                f"template vocab {meta['vocab']} != this model's "
                f"{self.cfg.vocab_size} (shipped from a different "
                f"model?)")
        if not self.install_prefix(meta["id"], meta["tokens"],
                                   template=bufs):
            raise ValueError("rolling-cache layout cannot host prefix "
                             "templates (degraded prefix-blind)")
        return str(meta["id"])

    def resident_prefixes(self) -> list:
        """Ids of the installed prefix templates (what the serving
        server advertises via HELLO/STATS for residency-aware
        routing)."""
        return sorted(self._prefix_store)

    def export_prefix_blob(self, prefix_id: str) -> bytes:
        """Pack the resident ``prefix_id`` for publication to a peer
        replica (the warm-ship path); raises ``ValueError`` when not
        resident."""
        from tony_tpu.serving import kvship
        entry = self._prefix_store.get(str(prefix_id))
        if entry is None:
            raise ValueError(f"prefix {prefix_id!r} is not resident")
        return kvship.pack_template(
            entry.id, entry.tokens,
            kv_to_wire({n: np.asarray(a)
                        for n, a in entry.template.items()}, self.cfg),
            self.cfg.vocab_size)

    def _resolve_prefix(self, prefix_id, prompt) -> PrefixEntry | None:
        """Resolve a submission against the resident store: the named
        entry when ``prefix_id`` is given and the prompt properly
        continues its tokens, else the LONGEST resident match
        (token-boundary, proper prefix). None = serve prefix-blind —
        a miss is never an error (the fast path is an optimization
        with token-identical outputs)."""
        if self._ring:
            if prefix_id is not None and not self._ring_prefix_warned:
                self._ring_prefix_warned = True
                import logging
                logging.getLogger(__name__).warning(
                    "prefix-id admission on a rolling (ring) cache; "
                    "serving prefix-blind")
            return None
        if not self._prefix_store or self.shared_prefix is not None:
            return None
        if prefix_id is not None:
            entry = self._prefix_store.get(prefix_id)
            if (entry is not None and len(entry.tokens) < len(prompt)
                    and prompt[:len(entry.tokens)] == entry.tokens):
                return entry
        # tokenized fallback: longest resident proper prefix (ONE copy
        # of the matching invariant, snapshot-safe vs install threads)
        from tony_tpu.serving.prefix import match_prefix
        entries = list(self._prefix_store.values())
        pid = match_prefix(prompt, ((e.id, e.tokens) for e in entries))
        return next((e for e in entries if e.id == pid), None) \
            if pid is not None else None

    # --- admission (bucketed and batched; per request on a ring) ---

    def _bucket_for(self, n: int) -> int:
        """Padded admission length for an n-token prompt (suffix, when a
        shared prefix is set): :func:`bucket_for` against the cache's
        admissible length."""
        cap = self.max_len - (len(self.shared_prefix)
                              if self.shared_prefix else 0)
        return bucket_for(n, cap, self.admission_buckets)

    def _marshal_wave(self, pairs, width: int | None = None):
        """THE home of the sentinel scheme: ([width] row targets, [width,
        2] per-request base rng keys) for a set of admitted (row,
        request) pairs, padded to ``width`` (the full slot count unless
        given) — unused entries
        get DISTINCT out-of-range row sentinels (their scatters drop)
        and the idle rng stream. One marshalling shared by prompt
        placement, stream rebinding, and the speculative seed draws, so
        the scheme cannot drift apart between paths; the keys come from
        ONE vmapped fold_in per wave, not one dispatch per row."""
        width = self.batch if width is None else width
        rows = self.batch + np.arange(width, dtype=np.int32)
        req_ids = [_IDLE_STREAM] * width
        for i, (row, req) in enumerate(pairs):
            rows[i] = row
            req_ids[i] = req
        return (jnp.asarray(rows),
                jax.vmap(self._req_key)(jnp.asarray(req_ids)))

    @staticmethod
    def _seq_of(payload):
        """The token sequence an admission payload runs through the
        model: the whole prompt, or only the suffix of a prefix hit."""
        return payload.suffix if isinstance(payload, _PrefixHit) \
            else payload

    def _pad_prompts_to(self, grp, prompts, bucket, width: int):
        """[width, bucket] right-padded prompt matrix plus [width] true
        lengths for one bucket group (entries past the group are inert —
        their scatter targets are :meth:`_marshal_wave`'s out-of-range
        sentinels). Prefix hits pad their SUFFIX (the only tokens that
        run a forward)."""
        toks = np.zeros((width, bucket), np.int64)
        lens = np.ones((width,), np.int32)
        for i, (_, req) in enumerate(grp):
            p = self._seq_of(prompts[req])
            toks[i, :len(p)] = p
            lens[i] = len(p)
        return jnp.asarray(toks, jnp.int32), jnp.asarray(lens)

    def _admit_batch(self, pairs, prompts) -> None:
        """Admit (row, request-index) pairs. ``prompts`` maps each
        request index to EITHER a token sequence (the colocated path —
        prefill here) or a :class:`KVPackage` (disaggregated serving —
        the prefill already ran on another gang; landing is a scatter).
        The engine's admission sweep feeds both through one seam, so
        the slot/occupancy machinery cannot diverge between modes. The
        whole wave is the phase ``admit``; each device dispatch in it —
        the marshalling that feeds the call, and the call — is an
        ``admit_dispatch`` nested in it, so ``admit − admit_dispatch``
        is the padding, the rebinding and the dispatch of the wave's
        first-token draw. Returns that draw (:meth:`_draw_first`)."""
        pkg, toks = [], []
        shared_p = len(self.shared_prefix) if self.shared_prefix else 0
        for pair in pairs:
            p = prompts[pair[1]]
            (pkg if isinstance(p, KVPackage) else toks).append(pair)
            self._row_len[pair[0]] = (
                p.length if isinstance(p, KVPackage)
                else len(p.entry.tokens) + len(p.suffix)
                if isinstance(p, _PrefixHit) else shared_p + len(p))
        with self.phase_times.phase("admit"):
            if pkg:
                self._admit_packages(pkg, prompts)
            if toks:
                self._admit_prompts(toks, prompts)
            return self._draw_first()

    def _draw_first(self):
        """Enqueue the draw of the first token of every row the wave
        just admitted (:func:`first_tokens`, behind the wave's last
        dispatch and ahead of the next chunk) and return its
        not-yet-materialized [B] tokens; the engine fetches them in
        device-queue order (:meth:`ServeEngine._fetch`) and keeps the
        rows it admitted. It reads ``logits`` and not an admission
        program's internals, so it serves every admission program
        alike. Not an admission dispatch and not a chunk: it takes no
        ``seq``, and no turn's meaning moves."""
        return first_tokens(self.logits, self._row_keys,
                            np.asarray(self._row_off, np.int32),
                            self.temperature, self.top_k, self.top_p)

    def _admit_packages(self, pairs, pkgs) -> None:
        """Land shipped-KV admissions: group by the landing bucket
        (:func:`bucket_for` over each package's shipped width — the
        SAME ladder as prompt admission, so the decode gang compiles
        one :func:`land_kv_rows` program per bucket) and land each
        group in one scatter dispatch. The host stages each group into
        a slot-count-wide, zero-padded buffer set (sentinel rows drop
        on device); zero padding differs from the colocated path's
        prefill-garbage padding only beyond the frontiers, where no
        query can reach — token outputs are identical."""
        groups: dict[int, list] = {}
        for row, req in pairs:
            w = pkgs[req].width
            s_b = w if self._ring else bucket_for(
                w, self.max_len, self.admission_buckets)
            groups.setdefault(s_b, []).append((row, req))
        for s_b in sorted(groups):
            self._land_group(groups[s_b], pkgs, s_b)

    def _land_group(self, grp, pkgs, s_b: int) -> None:
        b = self.batch
        proto = pkgs[grp[0][1]].bufs
        rows = b + np.arange(b, dtype=np.int32)
        lens = np.zeros((b,), np.int32)
        lgs = np.zeros((b, self.cfg.vocab_size),
                       pkgs[grp[0][1]].logits.dtype)
        keys = np.zeros((b, 2), np.uint32)
        # packages arrive in the wire form; the staging buffers are in
        # the cache's stored form (a host reshape per row, no copy)
        mini = {n: np.zeros((a.shape[0], b, s_b) + a.shape[3:], a.dtype)
                for n, a in kv_from_wire(proto).items()}
        for i, (row, req) in enumerate(grp):
            pkg = pkgs[req]
            rows[i] = row
            lens[i] = pkg.length
            lgs[i] = pkg.logits
            keys[i] = pkg.rng_key
            for n, a in kv_from_wire(pkg.bufs).items():
                mini[n][:, i:i + 1, :a.shape[2]] = a
        with self.phase_times.phase("admit_dispatch", seq=self.seq,
                                    bucket=s_b, rows=b,
                                    tokens=int(lens.sum())):
            self.cache, self.logits, self._row_keys = land_kv_rows(
                self.cache, self.logits, jnp.asarray(rows),
                {n: jnp.asarray(a) for n, a in mini.items()},
                jnp.asarray(lens), jnp.asarray(lgs), self._row_keys,
                jnp.asarray(keys))
            self._enqueued(row for row, _ in grp)
        for row, req in grp:
            self._row_off[row] = pkgs[req].rng_off

    def _validate_package(self, pkg, max_new: int) -> None:
        """Reject a shipped-KV admission the decode batcher could not
        land: wrong buffer set/dtypes/trailing dims vs this cache, a
        width past the cache extent, or frontier + budget past
        ``max_len`` (linear caches). Raises ``ValueError`` naming the
        mismatch — request-scoped at the decode server, exactly like
        prompt validation."""
        if not isinstance(pkg, KVPackage):
            raise ValueError(f"expected a KVPackage, got {type(pkg)}")
        if max_new <= 0:
            raise ValueError(f"max_new_tokens must be positive, "
                             f"got {max_new}")
        if pkg.length < 1:
            raise ValueError(f"package frontier must be >= 1, "
                             f"got {pkg.length}")
        lg = np.asarray(pkg.logits)
        if lg.ndim != 1 or lg.shape[0] != self.cfg.vocab_size:
            raise ValueError(
                f"package logits shape {list(lg.shape)} != "
                f"[{self.cfg.vocab_size}] (vocab mismatch between the "
                f"prefill and decode gangs?)")
        want = kv_wire_layout(self.cfg)
        if set(pkg.bufs) != set(want):
            raise ValueError(
                f"package buffers {sorted(pkg.bufs)} do not match this "
                f"cache's layout {sorted(want)} (quantization mismatch "
                f"between the prefill and decode gangs?)")
        rows = self.cache["k"].shape[2]
        for n, a in pkg.bufs.items():
            c = want[n]
            if a.dtype != c.dtype:
                raise ValueError(f"package buffer {n!r} dtype {a.dtype} "
                                 f"!= cache dtype {c.dtype}")
            if (a.shape[0] != c.shape[0] or a.shape[1] != 1
                    or a.shape[3:] != c.shape[3:]):
                raise ValueError(f"package buffer {n!r} shape {a.shape} "
                                 f"does not fit this cache's wire "
                                 f"layout {c.shape}")
            if a.shape[2] > rows:
                raise ValueError(f"package width {a.shape[2]} exceeds "
                                 f"the cache's {rows} rows")
        if self._ring:
            if pkg.width != rows:
                raise ValueError(
                    f"ring landings must ship the full {rows}-row "
                    f"capacity, got {pkg.width}")
        elif pkg.length + max_new > self.max_len:
            raise ValueError(f"frontier {pkg.length} + {max_new} new "
                             f"tokens exceeds max_len {self.max_len}")
        if pkg.length > pkg.width and not self._ring:
            raise ValueError(f"frontier {pkg.length} exceeds shipped "
                             f"width {pkg.width}")

    def _admit_prompts(self, pairs, prompts) -> None:
        """Admit prompt (row, request-index) pairs: group by (resident
        prefix, length bucket) and land each group in
        :func:`admit_width`-wide device dispatches; on a ring cache, one
        exact-length :func:`admit_row_ring` dispatch a request (a ring
        hosts no prefix template, so every payload there is a whole
        prompt). A prefix-hit group runs only its SUFFIXES through the
        model against the stored template (:func:`prefix_admit_rows`) —
        the admission fast path. Also rebinds each row's rng stream to
        its new occupant — one scatter of the wave's marshalled keys,
        not a dispatch per row."""
        pt = self.phase_times
        if self._ring:
            for row, req in pairs:
                n = len(prompts[req])
                with pt.phase("admit_dispatch", seq=self.seq, bucket=n,
                              rows=1, tokens=n):
                    self.cache, self.logits = admit_row_ring(
                        self.params, self.cache, self.logits, row,
                        jnp.asarray(prompts[req], jnp.int32)[None],
                        self.cfg)
                    self._enqueued((row,))
                self.prefill_padded_tokens += n
            rows, keys = self._marshal_wave(pairs)
            self._rebind_streams(pairs, rows, keys)
            self._count_admission(pairs, prompts)
            return
        groups: dict[tuple, list] = {}
        for row, req in pairs:
            p = prompts[req]
            if isinstance(p, _PrefixHit):
                cap = self.max_len - len(p.entry.tokens)
                key = (p.entry.id,
                       bucket_for(len(p.suffix), cap,
                                  self.admission_buckets))
            else:
                key = (None, self._bucket_for(len(p)))
            groups.setdefault(key, []).append((row, req))
        for pid, bucket in sorted(groups,
                                  key=lambda k: (k[0] or "", k[1])):
            whole = groups[(pid, bucket)]
            entry = (prompts[whole[0][1]].entry if pid is not None
                     else None)
            w = admit_width(bucket, self.batch)
            for i in range(0, len(whole), w):
                grp = whole[i:i + w]
                toks, lens = self._pad_prompts_to(grp, prompts,
                                                  bucket, w)
                n_tok = sum(len(self._seq_of(prompts[req]))
                            for _, req in grp)
                with pt.phase("admit_dispatch", seq=self.seq,
                              bucket=bucket, rows=w, tokens=n_tok):
                    rows, keys = self._marshal_wave(grp, w)
                    self._admit_rows(rows, toks, lens, keys,
                                     entry=entry)
                    self._enqueued(row for row, _ in grp)
                self.prefill_padded_tokens += w * bucket
                if self._ssm_mixers:
                    self.ssm_counts["scan_positions"] += (
                        self._ssm_mixers * w * bucket)
                    self.ssm_counts["scan_live_positions"] += (
                        self._ssm_mixers * n_tok)
                self._rebind_streams(grp, rows, keys)
                self._count_admission(grp, prompts)

    def _count_admission(self, pairs, prompts) -> None:
        """Fold one admitted group into the host-side prefill-compute
        accounting (forward tokens vs template-copied prefix
        positions — the FLOPs contrast the prefix fast path exists
        for). Legacy ``shared_prefix`` mode counts its template copies
        too: prompts there are already suffixes."""
        shared_p = len(self.shared_prefix) if self.shared_prefix else 0
        for _, req in pairs:
            p = prompts[req]
            self.prefill_forward_tokens += len(self._seq_of(p))
            if isinstance(p, _PrefixHit):
                self.prefix_copied_tokens += len(p.entry.tokens)
                self.prefix_admits += 1
            elif shared_p:
                self.prefix_copied_tokens += shared_p

    def _rebind_streams(self, pairs, rows, keys) -> None:
        """Rebind the admitted rows' rng streams to their new occupants:
        ONE scatter of the wave's already-marshalled base keys (the
        sentinel rows drop), plus the host-side stream-position
        resets."""
        self._row_keys = self._row_keys.at[rows].set(
            keys, mode="drop", unique_indices=True)
        for row, req in pairs:
            self._row_off[row] = (self._off0
                                  + self._stream_skip.pop(req, 0))

    def _admit_rows(self, rows, toks, lens, keys, entry=None) -> None:
        if entry is not None:
            self.cache, self.logits = prefix_admit_rows(
                self.params, self.cache, self.logits, rows,
                entry.template, toks, lens, self.cfg)
        elif self._prefix_template is not None:
            self.cache, self.logits = prefix_admit_rows(
                self.params, self.cache, self.logits, rows,
                self._prefix_template, toks, lens, self.cfg)
        else:
            self.cache, self.logits, stats = admit_rows(
                self.params, self.cache, self.logits, rows, toks, lens,
                self.cfg)
            if stats:
                self._device_stats.append(("admit", stats))

    # --- dispatch/fetch seams (overridden by the speculative batcher) ---

    #: most tokens one chunk can commit per row (the greedy step loop
    #: commits exactly one per step; the speculative batcher overrides)
    def _chunk_tokens_max(self) -> int:
        return self.chunk

    def _issue(self):
        """Issue one decode program (``chunk`` steps) WITHOUT fetching it
        (async dispatch — returns the not-yet-materialized device
        tokens). The pipelined loop keeps ``depth`` of them issued ahead
        of the one it fetches."""
        with self._dispatch_phase():
            offs = np.asarray(self._row_off, np.int32)
            toks, self.cache, self.logits, stats = step_rows(
                self.params, self.cache, self.logits, self._row_keys,
                offs, self.chunk, self.cfg, self.temperature, self.top_k,
                self.top_p)
            if stats:
                self._device_stats.append(("decode", stats))
            self._chunk_enqueued()
        # counting is host time too: ``account`` holds what it costs
        with self.phase_times.phase("account"):
            visited = cache_rows_visited(
                self.cfg, self.max_len,
                self._row_len[:, None] + np.arange(self.chunk))
            for kind, (read, live) in visited.items():
                self.cache_rows_read[kind] += read
                self.cache_rows_live[kind] += live
            if self._ssm_mixers:
                # a step reads and writes every slot's state once; a row
                # is occupied where its frontier is past 0 (every idle
                # one was retired to 0 before this issue)
                moved = 2 * self.chunk * self.cache_bytes["ssm"]
                self.ssm_counts["state_bytes"] += moved
                self.ssm_counts["state_live_bytes"] += (
                    moved * int((self._row_len > 0).sum()) // self.batch)
        self._row_len += self.chunk
        self.steps_executed += self.chunk
        for r in range(self.batch):
            self._row_off[r] += self.chunk
        return toks

    def _fetch(self, handle):
        """Block on a previously issued chunk: remaining device compute
        plus the transport round trip — the cost the pipelined loop
        overlaps with the NEXT chunk. Returns per-row sequences of newly
        generated tokens."""
        toks = self._await(handle)
        with self.phase_times.phase("account"):
            self._collect_device_stats()
        return toks

    def _collect_device_stats(self) -> None:
        """Fold what the device counted, up to and including the OLDEST
        outstanding decode chunk — the one whose tokens were just
        fetched, so its counters (outputs of the same program) and those
        of every admission dispatched before it are already computed: no
        new device sync. Later entries wait for their own chunk's
        fetch."""
        while self._device_stats:
            where, stats = self._device_stats.popleft()
            landed, touched, *zeros = (int(v) for v in
                                       np.asarray(stats[MOE_COUNTS]))
            self.moe_assignments[where] += landed
            self.moe_touches[where] += touched
            self.moe_zero_assignments[where] += sum(zeros)
            if where == "decode":
                break

    def _retire(self, mask) -> None:
        mask = np.asarray(mask, bool)
        self.cache = retire_rows(self.cache, mask)
        self._row_len[mask] = 0

    def count_finished(self, prompt_len: int, emitted: int) -> int:
        """Fold a finished request into the ring's accounting: it wrote
        rows [0, prompt + emitted - 1) (its last token is never fed
        back), and every one at or past the ring's rows overwrote an
        older row in each layer that holds a ring. Returns the rows it
        added (0 without a ring)."""
        rows, layers = self._ring_shape
        over = layers * max(0, prompt_len + emitted - 1 - rows) if rows \
            else 0
        self.ring_rows_overwritten += over
        return over

    def _validate_request(self, prompt, max_new: int) -> None:
        """Reject a request the batcher could not serve: empty prompt,
        non-positive budget, or (linear caches — rolling caches have no
        length ceiling) prompt + budget past ``max_len``. Raises
        ``ValueError`` naming the offending dimension."""
        p_len = len(self.shared_prefix) if self.shared_prefix else 0
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if max_new <= 0:
            raise ValueError(f"max_new_tokens must be positive, "
                             f"got {max_new}")
        if not self._ring and p_len + len(prompt) + max_new > self.max_len:
            raise ValueError(
                (f"shared prefix {p_len} + " if p_len else "")
                + f"prompt {len(prompt)} + {max_new} new tokens exceeds "
                  f"max_len {self.max_len}")

    def _validate_prefix_hit(self, hit: "_PrefixHit",
                             max_new: int) -> None:
        """Validate a prefix-hit admission (resident template + suffix)
        against the cache geometry — the fast-path counterpart of
        :meth:`_validate_request` (suffix non-emptiness is guaranteed
        by the proper-prefix match)."""
        total = len(hit.entry.tokens) + len(hit.suffix)
        if max_new <= 0:
            raise ValueError(f"max_new_tokens must be positive, "
                             f"got {max_new}")
        if total + max_new > self.max_len:
            raise ValueError(
                f"prefix {len(hit.entry.tokens)} + suffix "
                f"{len(hit.suffix)} + {max_new} new tokens exceeds "
                f"max_len {self.max_len}")

    def serve(self, prompts: Sequence, max_new_tokens):
        """Run all ``prompts`` (each a [S_p] int sequence) to completion;
        returns a list of per-request generated-token lists, order-
        matching the input. ``max_new_tokens``: one int for all requests
        or a per-request sequence (mixed-length serving is the whole
        point). ``self.steps_executed`` counts device decode steps run —
        the utilization denominator (each step advances every slot);
        ``self.phase_times`` holds per-phase host wall clock
        (dispatch/fetch/admit/retire) for the call.

        A thin CLOSED-BATCH wrapper over :class:`ServeEngine`: submit
        every request up front, drain, run the engine on the calling
        thread, and collect each request's streamed deltas into its
        output list. Token-identical (and ``steps_executed``-identical)
        to the pre-engine fixed-queue loop in every mode — the engine's
        live admission queue degenerates to the old FIFO when everything
        is submitted before the loop starts (test-enforced).

        The call also observes into the default metrics registry
        (``runtime/metrics.py``): admitted/retired request counters,
        useful-token counter, queue-depth gauge, TTFT/inter-token
        histograms, and — on return — the PhaseTimes accumulation as
        per-phase ``tony_serve_phase_*`` counters. Swap in a
        :class:`~tony_tpu.runtime.metrics.NullRegistry` to serve
        uninstrumented."""
        if isinstance(max_new_tokens, int):
            budget = [max_new_tokens] * len(prompts)
        else:
            budget = list(max_new_tokens)
            if len(budget) != len(prompts):
                raise ValueError("per-request max_new_tokens length "
                                 "must match prompts")
        outputs: list[list[int]] = [[] for _ in prompts]
        engine = ServeEngine(
            self, on_delta=lambda rid, toks: outputs[rid].extend(toks),
            on_retired=lambda rid, reason, n, final:
                outputs[rid].extend(final))
        # every submit happens BEFORE run(), so a bad request anywhere
        # in the list still fails the whole call up front — nothing is
        # admitted, no completed output is discarded mid-serve
        for req, (p, b) in enumerate(zip(prompts, budget)):
            try:
                engine.submit(req, p, b)
            except ValueError as e:
                # unwind the earlier submits (clears the wait queue and
                # zeroes the queue-depth gauge — no phantom depth from
                # an engine that never runs)
                engine._abort_outstanding("stopped")
                raise ValueError(f"request {req}: {e}") from None
        engine.drain()
        engine.run()
        return outputs


class SpeculativeContinuousBatcher(ContinuousBatcher):
    """Continuous batching with speculative decoding per slot — the two
    serving features composed. A cheap draft model proposes
    ``num_speculative`` tokens per round for EVERY slot at its own
    frontier; the target verifies each slot's chunk in one wide
    ``extend_step``; each slot commits its own acceptance
    (:func:`spec_step_rows`, built on the same propose-and-verify round
    as ``decode.speculative_generate_device``). Slot reuse works exactly
    as in the greedy batcher: admission prefills BOTH caches (bucketed
    and batched — :func:`spec_admit_rows`), retirement frees
    the slot, and idle rows decode garbage the host discards. The
    pipelined loop and its catch-up semantics are inherited unchanged —
    one packed array per sync keeps the double-buffered fetch a single
    transport round trip.

    Outputs are token-identical to the greedy batcher (and therefore to
    per-request ``decode.generate``) wherever chunked and single-step
    logits agree — bit-exact on CPU, matmul-noise near-ties on TPU, the
    same caveat as all speculative paths. Wall-clock wins need a draft
    that predicts the target AND enough per-request work to amortize the
    round structure; ``rounds_executed`` counts speculative rounds run
    (tokens-per-round = the acceptance-driven efficiency).

    ``chunk`` here counts speculative ROUNDS per host sync, not tokens:
    one round commits between 1 and k+1 tokens per live slot, so a
    finished request idles at most ``chunk-1`` rounds before its slot is
    reused.

    Accounting: ``steps_executed`` counts TARGET-MODEL positions
    verified per slot (``rounds * (k+1)``) so the base class's
    step-utilization reading remains meaningful — useful tokens /
    (steps_executed * slots) is the fraction of verified positions that
    became committed tokens (acceptance efficiency × occupancy).
    ``rounds_executed`` counts speculative rounds.

    ``temperature > 0`` switches every slot's rounds to SPECULATIVE
    SAMPLING (``decode._propose_and_verify_sampled``): each request's
    committed stream is distributed exactly as target-only sampling
    through the same temperature/top-k/top-p stack, for any draft —
    greedy rounds remain the token-exact default. Draws come from
    per-request streams (the admission seed takes stream position 0,
    round ``r`` takes position ``1 + r``), so a request's sampled output
    is independent of admission timing — pipelined == sequential here
    too."""

    #: stream position 0 is the admission seed draw; rounds start at 1
    _off0 = 1

    def __init__(self, params, cfg: T.TransformerConfig,
                 draft_params, draft_cfg: T.TransformerConfig,
                 batch: int, max_len: int,
                 num_speculative: int = 4, eos_id: int | None = None,
                 chunk: int = 4, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0,
                 seed: int = 0, shared_prefix=None,
                 pipeline: bool = True,
                 admission_buckets: Sequence[int] | None = None) -> None:
        for c in (cfg, draft_cfg):
            c.refuse("speculative decoding")
        super().__init__(params, cfg, batch, max_len, eos_id=eos_id,
                         chunk=chunk, temperature=temperature,
                         top_k=top_k, top_p=top_p, seed=seed,
                         shared_prefix=shared_prefix, pipeline=pipeline,
                         admission_buckets=admission_buckets)
        if num_speculative < 1:
            raise ValueError("num_speculative must be >= 1")
        _check_draft_vocab(cfg, draft_cfg)
        _check_no_ring(cfg, "speculative serving (chunked verify)")
        _check_no_ring(draft_cfg, "speculative serving (draft)")
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        # the draft needs its own prefix template (its K/V dims differ)
        self._draft_prefix_template = (
            prefix_template(draft_params, self.shared_prefix, draft_cfg)
            if self.shared_prefix else None)
        self.k = num_speculative
        self.d_cache = init_kv_cache(draft_cfg, batch, max_len)
        self.d_cache = dict(self.d_cache,
                            length=jnp.zeros((batch,), jnp.int32))
        # pending token per slot (the committed token whose K/V is not
        # yet written) replaces the greedy batcher's per-slot logits
        self.pending = jnp.zeros((batch,), jnp.int32)
        # a round's reads are the draft's steps and the verify's chunk
        # (extend_step): not the single-position reads the greedy
        # batcher counts, so there is no count and no counter
        self.cache_rows_read, self.cache_rows_live = {}, {}

    def _chunk_tokens_max(self) -> int:
        # one sync = chunk rounds x up to k+1 commits per row
        return self.chunk * (self.k + 1)

    def _build_entry(self, prefix_id: str, tokens: list,
                     template: dict) -> PrefixEntry:
        # the draft keeps its own per-slot K/V history, so a resident
        # prefix needs a DRAFT template too; template ships carry only
        # the target's buffers, so it is computed locally (the draft is
        # the cheap model — one small prefill per install)
        return PrefixEntry(
            prefix_id, tokens, template,
            draft_template=prefix_template(self.draft_params, tokens,
                                           self.draft_cfg))

    def _admit_rows(self, rows, toks, lens, keys, entry=None) -> None:
        # the seed draw takes stream position 0 of each admitted
        # request's base key — one vmapped fold over the wave's
        # ALREADY-marshalled keys (shared with the rebind scatter), not
        # a second per-request derivation
        seed_keys = jax.vmap(lambda kk: jax.random.fold_in(kk, 0))(keys)
        if entry is not None:
            self.cache, self.d_cache, self.pending = (
                spec_prefix_admit_rows(
                    self.params, self.draft_params, self.cache,
                    self.d_cache, self.pending, rows, entry.template,
                    entry.draft_template, toks, lens, seed_keys,
                    self.cfg, self.draft_cfg, self.temperature,
                    self.top_k, self.top_p))
        elif self._prefix_template is not None:
            self.cache, self.d_cache, self.pending = (
                spec_prefix_admit_rows(
                    self.params, self.draft_params, self.cache,
                    self.d_cache, self.pending, rows,
                    self._prefix_template, self._draft_prefix_template,
                    toks, lens, seed_keys, self.cfg, self.draft_cfg,
                    self.temperature, self.top_k, self.top_p))
        else:
            self.cache, self.d_cache, self.pending = spec_admit_rows(
                self.params, self.draft_params, self.cache, self.d_cache,
                self.pending, rows, toks, lens, seed_keys, self.cfg,
                self.draft_cfg, self.temperature, self.top_k, self.top_p)

    def _admit_packages(self, pairs, pkgs) -> None:
        # EXPLICIT disaggregation exclusion (not an oversight): a
        # speculative slot needs the DRAFT model's per-slot K/V history
        # too, which the KV shipment does not carry. Serve speculative
        # colocated, or disaggregate the greedy/sampled batcher.
        raise NotImplementedError(
            "speculative serving is not supported in disaggregated "
            "mode (the shipment carries no draft-model cache)")

    def _draw_first(self):
        # the first token here is the seed the admission itself drew
        # into ``pending``, a buffer the next round's dispatch donates:
        # it leaves with its first chunk, as every committed token does
        return None

    def _issue(self):
        with self._dispatch_phase():
            offs = np.asarray(self._row_off, np.int32)
            packed, self.cache, self.d_cache, self.pending = (
                spec_step_rows(self.params, self.draft_params, self.cache,
                               self.d_cache, self.pending, self._row_keys,
                               offs, self.chunk, self.cfg, self.draft_cfg,
                               self.k, self.temperature, self.top_k,
                               self.top_p))
            self._chunk_enqueued()
        self.rounds_executed += self.chunk
        self.steps_executed += self.chunk * (self.k + 1)
        for r in range(self.batch):
            self._row_off[r] += self.chunk
        return packed

    def _fetch(self, handle):
        # ONE host fetch per sync (see spec_step_rows: separate fetches
        # pay separate transport round trips)
        packed = self._await(handle)                   # [n, B, k+2]
        return [
            [int(t) for i in range(packed.shape[0])
             for t in packed[i, row, 1:1 + packed[i, row, 0]]]
            for row in range(self.batch)]

    def _retire(self, mask) -> None:
        m = np.asarray(mask, bool)
        self.cache = retire_rows(self.cache, m)
        self.d_cache = retire_rows(self.d_cache, m)


#: QoS tiers in admission-priority order (mirrors
#: ``serving.protocol.QOS_CLASSES`` — the wire-side authority; kept as
#: a local literal so the models layer stays importable without the
#: serving plane).
QOS_CLASSES = ("interactive", "standard", "batch")


class EngineBusy(RuntimeError):
    """Explicit overload shed: the engine refused to QUEUE a
    standard/batch submission past its bounded queue depth. A statement
    about load, not about the request — the identical submit is
    expected to succeed once pressure clears; ``retry_after_ms`` is the
    server's backoff hint (the BUSY frame's payload)."""

    def __init__(self, retry_after_ms: int) -> None:
        super().__init__(
            f"engine overloaded; retry after {retry_after_ms} ms")
        self.retry_after_ms = int(retry_after_ms)


class _EngineRequest:
    """Engine-side record of one live request. ``stream`` is the
    request's rng-stream index (assigned in submission order, so the
    closed-batch wrapper reproduces the fixed-queue loop's per-request
    streams exactly); ``budget`` counts REMAINING tokens."""

    __slots__ = ("rid", "prompt", "budget", "stream", "rng_skip",
                 "emitted", "done", "reason", "t_submit", "t_last",
                 "t_queued", "t_admit", "t_ride", "admit_seq", "span",
                 "queued_span", "first_span", "cls", "history", "requeued",
                 "sent_ahead", "flight")

    def __init__(self, rid, prompt, budget: int, stream: int,
                 t_submit: float, rng_skip: int = 0,
                 cls: str = "standard") -> None:
        self.rid = rid
        self.prompt = prompt
        self.budget = budget
        self.stream = stream
        #: stream positions already consumed by a previous placement of
        #: this request (router-coordinated migration) — the batcher
        #: draws this row's first sample at this offset
        self.rng_skip = rng_skip
        self.emitted = 0
        self.done = False
        self.reason: str | None = None
        self.t_submit = t_submit
        self.t_last = t_submit
        #: when this record entered the wait queue (the submit, or the
        #: requeue of a preempted stream) and when a slot admitted it:
        #: the ends of the ``queue_wait`` / ``first_token`` waits
        self.t_queued = t_submit
        self.t_admit = t_submit
        #: when the chunk that was in flight at the admission returned
        #: (``t_admit`` itself when none was): ``first_token`` splits
        #: here into ``first_token_queued`` and ``first_token_ride``
        self.t_ride = t_submit
        #: seq of the admission dispatch that carried it
        self.admit_seq = -1
        #: QoS tier (one of :data:`QOS_CLASSES`)
        self.cls = cls
        #: emitted token VALUES, tracked only for evictable rows (batch
        #: class, foldable payload) — a preemption folds prompt+history
        #: into the reincarnation's prompt so the PR 12 rng-offset
        #: re-prefill resumes the stream token-identically
        self.history: list | None = None
        #: True on the tombstone left behind by a preemption whose
        #: stream was re-queued IN-ENGINE under the same rid: its
        #: retirement must not be emitted (the rid is still live) and
        #: its counters must not move
        self.requeued = False
        #: the token that left ahead of this placement's first chunk
        #: (:meth:`ServeEngine._consume_first`), until that chunk is
        #: consumed: its column 0 is the same draw, and is dropped
        self.sent_ahead: int | None = None
        #: decode programs issued with this request in their snapshot
        #: and not consumed yet: what the pipelined loop foresees its
        #: end from (:meth:`ServeEngine._spare`)
        self.flight = 0
        # TTFT-decomposition spans (tracing.NOOP_SPAN when unsampled):
        # engine.request (submit→retire) with children engine.queued
        # (submit→slot admit) and engine.first_token (admit→first
        # consumed delta)
        self.span = tracing.NOOP_SPAN
        self.queued_span = tracing.NOOP_SPAN
        self.first_span = tracing.NOOP_SPAN


class ServeEngine:
    """Open-loop serving engine: the issue/fetch/consume/settle loop of
    a :class:`ContinuousBatcher` (or its speculative subclass) run
    against a LIVE admission queue.

    - :meth:`submit`/:meth:`cancel` are thread-safe and callable while
      :meth:`run` is live — a streaming server's per-connection reader
      threads feed admissions straight into the loop.
    - ``on_delta(rid, tokens)`` fires the moment a chunk's tokens for a
      request are consumed (NOT on retirement) — the emission point
      time-to-first-token and inter-token latency are measured at
      (``tony_serve_ttft_seconds`` / ``tony_serve_intertoken_seconds``
      land in the registry here). A request's FIRST delta holds one
      token and fires when its admission has run on the device, ahead
      of the chunk enqueued behind it (:meth:`_fetch`).
    - ``on_retired(rid, reason, n_tokens, final_tokens)`` fires exactly
      once per request, reason one of ``"eos"``/``"budget"``/
      ``"cancelled"``/``"stopped"``/``"preempted"`` (the last only for
      a KV-adopted row evicted for an interactive admission — the
      router re-places it; a colocated batch row preempts WITHOUT
      retiring, reincarnated in-engine under the same rid). A request
      retiring on eos/budget
      delivers its LAST delta here (``final_tokens``) rather than
      through ``on_delta``, so a transport can write the final tokens
      and the retirement atomically — a peer can then never observe
      the one without the other.
    - :meth:`drain` is the graceful shutdown: no further submits, run()
      returns once every accepted request has retired. :meth:`stop`
      aborts — outstanding requests retire as ``"stopped"``.

    QoS (SLO-tiered serving): every submission carries a class —
    ``interactive`` / ``standard`` / ``batch`` — with one admission
    queue per class (interactive jumps, batch waits), per-class
    decode-slot floors (``class_floors``, the ``tony.serve.slots.*``
    keys), interactive-over-batch row preemption (evict-to-queue with
    a token-identical resume), and an explicit overload shed
    (:class:`EngineBusy` past ``max_queue_depth``, the BUSY frame).
    Classless callers land as ``standard`` and see the exact pre-QoS
    admission order.

    Callback threading: deltas and eos/budget retirements fire on the
    thread driving :meth:`run`; a ``"cancelled"`` retirement fires on
    the CANCELLING thread (so a streaming client sees its CANCEL
    acknowledged without waiting out the in-flight chunk). Consumers
    that serialize writes (the frame server) take a per-connection send
    lock. A delta already being consumed when its request is cancelled
    may still be emitted after the retirement — cancellation discards,
    so late tokens for a retired rid are dropped by the caller.

    Cancel semantics reuse the pipelined loop's proven catch-up path: a
    cancelled occupant is only MARKED done; the slot frees when the next
    consumed program crosses it (its tokens are discarded exactly like
    idle-slot garbage, and the freed slot readmits from the live
    queue). CANCEL racing retirement is idempotent — unknown or
    already-done rids are no-ops.

    One engine run per batcher at a time; creating the engine resets the
    batcher's per-serve state (``steps_executed``, phase times, rng
    streams), exactly as ``serve()`` did before the refactor.
    """

    def __init__(self, batcher: ContinuousBatcher, on_delta=None,
                 on_retired=None, registry=None,
                 class_floors: dict | None = None,
                 max_queue_depth: int = 128,
                 busy_retry_ms: int = 250,
                 latency_buckets=None) -> None:
        # guard BEFORE the state reset below: constructing a second
        # engine over a live one would silently rebind the running
        # engine's rng streams and counters mid-flight
        if getattr(batcher, "_engine_running", False):
            raise RuntimeError("batcher is already driven by a live "
                               "engine")
        self.b = batcher
        self.on_delta = on_delta
        self.on_retired = on_retired
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        #: rids waiting for a slot, FIFO per QoS class (deque: O(1)
        #: admission pops). Admission drains interactive first, then
        #: standard, then batch; a preemption reincarnation goes to the
        #: FRONT of its class queue (it already waited its turn).
        self._waitq: dict[str, collections.deque] = {
            c: collections.deque() for c in QOS_CLASSES}
        #: per-class decode-slot floors (soft reservations, clamped to
        #: the batcher's slot count; the ``tony.serve.slots.<class>``
        #: keys). A class past its floor takes a free slot only if the
        #: REMAINING free slots still cover every other class's unmet
        #: floor.
        self._floors = {c: 0 for c in QOS_CLASSES}
        for c, n in (class_floors or {}).items():
            if c not in self._floors:
                raise ValueError(f"unknown QoS class in floors: {c!r}")
            self._floors[c] = max(0, min(int(n), batcher.batch))
        if sum(self._floors.values()) > batcher.batch:
            raise ValueError(
                f"class floors {self._floors} exceed {batcher.batch} "
                f"decode slots")
        #: total queued admissions past which standard/batch submits
        #: are shed with :class:`EngineBusy` (0 = unbounded, the
        #: pre-QoS queue); interactive admissions always queue
        self._max_queue_depth = max(0, int(max_queue_depth))
        self._busy_retry_ms = max(0, int(busy_retry_ms))
        self._reqs: dict = {}                    # rid -> _EngineRequest
        self._occupant: list[_EngineRequest | None] = \
            [None] * batcher.batch
        self._draining = False
        self._stopped = False
        self._next_stream = 0
        # one engine == one serve lifetime: the closed-batch serve()'s
        # per-call reset moved here
        batcher.steps_executed = 0
        batcher.rounds_executed = 0
        batcher.phase_times = PhaseTimes(ENGINE_PHASES)
        batcher._reset_streams()
        batcher._reset_timeline()
        batcher._load = self._queue_load
        #: per slot, (when the chunk whose consumption freed it returned,
        #: that chunk's seq) — (0.0, -1) for a slot never occupied: the
        #: start of ``slot_vacant`` and the cause ``engine.queued`` names
        self._freed = [(0.0, -1)] * batcher.batch
        #: requests admitted behind programs still in flight, each with
        #: the seq of the newest of them: its ``first_token_queued`` ends
        #: when that one's fetch returns
        self._behind: list[tuple[_EngineRequest, int]] = []
        #: first-token draws enqueued and not fetched, oldest first:
        #: (the ``seq`` the next chunk took, the draw's device tokens,
        #: the wave's (row, request) pairs). A draw stands in the device
        #: queue ahead of that chunk and behind every older one, and is
        #: fetched in that order (:meth:`_fetch`)
        self._firsts: collections.deque = collections.deque()
        self._redraw_warned = False
        # Registry instrumentation: a handful of locked increments per
        # host SYNC (token counts batch into one inc per consume; the
        # TTFT/ITL histograms observe once per DELTA, <= slots per
        # sync).
        reg = registry or metrics_mod.get_default()
        self._reg = reg
        buckets = (metrics_mod.TIME_BUCKETS_S if latency_buckets is None
                   else tuple(latency_buckets))
        self._admitted_c = reg.counter(
            "tony_serve_requests_admitted_total",
            help="requests admitted into cache slots")
        self._retired_c = reg.counter(
            "tony_serve_requests_retired_total",
            help="requests retired (eos or budget)")
        self._cancelled_c = reg.counter(
            "tony_serve_requests_cancelled_total",
            help="requests cancelled before completion")
        self._tokens_c = reg.counter("tony_serve_tokens_total",
                                     help="useful generated tokens")
        self._qdepth_g = reg.gauge("tony_serve_queue_depth",
                                   help="requests waiting for a free slot")
        self._ttft_h = reg.histogram(
            "tony_serve_ttft_seconds",
            help="submit -> first consumed token delta (time to first "
                 "token, engine-side)", buckets=buckets)
        self._itl_h = reg.histogram(
            "tony_serve_intertoken_seconds",
            help="mean per-token gap of each consumed delta after a "
                 "request's first (inter-token latency, engine-side)",
            buckets=buckets)
        # per-class series alongside the aggregates: the same names
        # with a ``class`` label, so classless dashboards keep working
        # while SLO alerting reads only its tier
        self._qdepth_by_cls = {
            c: reg.gauge("tony_serve_queue_depth",
                         help="requests waiting for a free slot",
                         **{"class": c}) for c in QOS_CLASSES}
        self._ttft_by_cls = {
            c: reg.histogram("tony_serve_ttft_seconds",
                             buckets=buckets, **{"class": c})
            for c in QOS_CLASSES}
        self._itl_by_cls = {
            c: reg.histogram("tony_serve_intertoken_seconds",
                             buckets=buckets, **{"class": c})
            for c in QOS_CLASSES}
        self._early_c = reg.counter(
            "tony_serve_first_tokens_early_total",
            help="requests whose first token left with its admission, "
                 "ahead of the chunk enqueued behind it (over the "
                 "first_token phase's ops: the share that did)")
        self._depth_g = reg.gauge(
            "tony_serve_pipeline_depth",
            help="decode programs the pipelined loop keeps issued and "
                 "unfetched: its host time a turn over the device's "
                 "time a program, plus one (serve._Depth)")
        self._preempt_c = reg.counter(
            "tony_serve_preemptions_total",
            help="batch rows evicted-to-queue for an interactive "
                 "admission (the stream resumes token-identically)")
        self._shed_c = {
            c: reg.counter(
                "tony_serve_shed_total",
                help="submissions refused with BUSY past the bounded "
                     "queue depth", **{"class": c})
            for c in QOS_CLASSES}
        self._prefill_tok_c = reg.counter(
            "tony_serve_prefill_tokens_total",
            help="true prompt/suffix tokens run through a prefill or "
                 "extend forward at admission (the prefill-FLOPs "
                 "proxy the prefix fast path shrinks)")
        self._prefill_pad_c = reg.counter(
            "tony_prefill_padded_tokens_total",
            help="positions those forwards ran: a bucketed dispatch's "
                 "rows x bucket (serve.admit_width); prefill tokens "
                 "over this is the share that was a real prompt's")
        self._prefix_tok_c = reg.counter(
            "tony_serve_prefix_tokens_total",
            help="prefix positions satisfied by a resident-template "
                 "COPY instead of a forward (prefix-aware serving)")
        self._prefix_admits_c = reg.counter(
            "tony_serve_prefix_admits_total",
            help="admissions that went through a resident prefix "
                 "template (only suffix tokens ran the model)")
        # a model with sparse experts: what its expert layers counted on
        # the device, per program kind (the batcher folds them as each
        # chunk's tokens are fetched, _collect_device_stats)
        self._moe_c = {
            (what, program): reg.counter(
                f"tony_moe_{what}_total", help=text, program=program)
            for program in ("decode", "admit")
            for what, text in (
                ("assignments", "(token, pick) assignments that landed "
                                "on experts held here"),
                ("expert_touches", "(layer, held expert) pairs with at "
                                   "least one assignment: the expert "
                                   "weights a program had to read"),
                ("zero_assignments", "(token, pick) assignments that "
                                     "were a zero expert: the identity, "
                                     "no weight read"))
        } if batcher.cfg.experts is not None else {}
        self._moe_seen = {k: 0 for k in self._moe_c}
        #: the batcher's running totals behind each counter (dicts it
        #: updates in place)
        self._moe_totals = {
            "assignments": batcher.moe_assignments,
            "expert_touches": batcher.moe_touches,
            "zero_assignments": batcher.moe_zero_assignments}
        for kind, n in batcher.cache_bytes.items():
            reg.gauge("tony_cache_bytes", kind=kind,
                      help="bytes of the cache's buffers, by the kind of "
                           "state that owns them (a window kind's ring, "
                           "a full kind's max_len rows, a latent row, a "
                           "state-space mixer's ssm / conv state, or the "
                           "dense decoder's linear / ring cache)").set(n)
        self._ring_over_c = reg.counter(
            "tony_ring_rows_overwritten_total",
            help="ring rows finished requests overwrote: a layer a "
                 "position at or past the ring's rows (0 = the window "
                 "never bound)")
        # what the decode chunks' cached reads visited against what was
        # live (the batcher counts at each issue; folded at each consume)
        self._rows_c = {
            (what, kind): reg.counter(
                f"tony_cache_rows_{what}_total", help=text, kind=kind)
            for kind in batcher.cache_rows_read
            for what, text in (
                ("read", "cache rows the decode steps' reads visited, a "
                         "layer a row: whole blocks, idle slots too"),
                ("live", "cache rows the decode steps' masks admitted; "
                         "over rows read, how far the cached read "
                         "follows each row's own length"))}
        self._rows_seen = {k: 0 for k in self._rows_c}
        # a model with state-space mixers: what their state and their
        # prompt scans moved (the batcher's ``ssm_counts``)
        self._ssm_c = {
            what: reg.counter(f"tony_ssm_{what}_total", help=text)
            for what, text in (
                ("state_bytes", "bytes of recurrent state the decode "
                                "steps read and wrote, every slot"),
                ("state_live_bytes", "the occupied slots' part of those "
                                     "bytes: over all, what idle slots "
                                     "cost"),
                ("scan_positions", "positions the admissions' prompt "
                                   "scans ran over, a mixer a position"),
                ("scan_live_positions", "those below their rows' "
                                        "lengths: the rest was padding"))
            if what in batcher.ssm_counts}
        self._ssm_seen = dict.fromkeys(self._ssm_c, 0)
        self._qdepth_g.set(0)
        for g in self._qdepth_by_cls.values():
            g.set(0)

    # --- thread-safe control surface ---

    def submit(self, rid, prompt, max_new_tokens: int,
               trace_ctx: dict | None = None,
               prefix_id: str | None = None,
               rng: tuple | None = None,
               request_class: str = "standard") -> None:
        """Enqueue a request under caller-chosen id ``rid`` (any
        hashable; must not collide with a LIVE request's). Raises
        ``ValueError`` for un-servable requests (validated up front, so
        a bad request never strands engine state) and ``RuntimeError``
        once draining/stopped.

        ``prefix_id`` optionally names a resident shared-prefix
        template the prompt continues (the ADMIT frame's ``prefix``
        field); the engine also auto-matches the prompt against its
        resident store. A hit admits only the SUFFIX through the model
        — token-identical to full prefill, test-pinned; a miss (or a
        replica degraded prefix-blind) serves normally, never errors.

        ``trace_ctx`` is the submitter's span context (``{"tid", "sid"}``
        off the ADMIT frame): the request's engine-side spans — the TTFT
        decomposition — join that trace; without one the engine
        head-samples a fresh trace per ``tony.trace.sample-rate``.

        ``rng`` optionally pins the request's rng stream:
        ``(stream, off)`` uses stream index ``stream`` (instead of the
        engine's submission counter) with the first ``off`` positions
        treated as already consumed — how a router-coordinated
        migration continues a SAMPLED stream token-identically on a new
        replica (the ADMIT frame's ``rng`` field; see
        ``protocol.parse_rng``).

        ``request_class`` is the QoS tier (:data:`QOS_CLASSES`):
        ``interactive`` jumps the admission queue and may preempt a
        batch row, ``batch`` yields and absorbs preemption; a
        standard/batch submit past the bounded queue depth raises
        :class:`EngineBusy` (the BUSY shed) instead of queueing."""
        prompt = [int(t) for t in prompt]
        max_new_tokens = int(max_new_tokens)
        if request_class not in QOS_CLASSES:
            raise ValueError(
                f"unknown request class {request_class!r} (expected "
                f"one of {', '.join(QOS_CLASSES)})")
        entry = self.b._resolve_prefix(prefix_id, prompt)
        if entry is None:
            self.b._validate_request(prompt, max_new_tokens)
            self._enqueue(rid, prompt, max_new_tokens, trace_ctx,
                          rng=rng, cls=request_class,
                          prompt_tokens=len(prompt))
        else:
            hit = _PrefixHit(entry, prompt[len(entry.tokens):])
            self.b._validate_prefix_hit(hit, max_new_tokens)
            self._enqueue(rid, hit, max_new_tokens, trace_ctx,
                          rng=rng, cls=request_class,
                          prompt_tokens=len(prompt),
                          prefix=entry.id)

    def submit_prefilled(self, rid, package: KVPackage,
                         max_new_tokens: int,
                         trace_ctx: dict | None = None,
                         request_class: str = "standard") -> None:
        """Enqueue an ALREADY-PREFILLED request (disaggregated serving):
        ``package`` is the :class:`KVPackage` a prefill gang shipped —
        admission lands it with :func:`land_kv_rows` (a scatter, no
        model forward), so adopting a row never preempts the in-flight
        decode chunk. Same contract as :meth:`submit` otherwise:
        caller-chosen ``rid``, up-front validation
        (:meth:`ContinuousBatcher._validate_package`),
        ``RuntimeError`` once draining. The shipped rng stream state
        rides the package, so sampled output matches the colocated
        engine serving the same request index.

        ``request_class`` applies the decode tier's per-class floors
        and queue order to an adopted package, but a package is NEVER
        shed with BUSY: the prefill work is already paid — the prefill
        tier sheds before prefilling (see ``serving/disagg.py``)."""
        max_new_tokens = int(max_new_tokens)
        if request_class not in QOS_CLASSES:
            raise ValueError(
                f"unknown request class {request_class!r} (expected "
                f"one of {', '.join(QOS_CLASSES)})")
        self.b._validate_package(package, max_new_tokens)
        self._enqueue(rid, package, max_new_tokens, trace_ctx,
                      cls=request_class, prompt_tokens=package.length,
                      prefilled=True)

    def _wait_total_locked(self) -> int:
        return sum(len(q) for q in self._waitq.values())

    def _set_qdepth_locked(self) -> None:
        self._qdepth_g.set(self._wait_total_locked())
        for c, q in self._waitq.items():
            self._qdepth_by_cls[c].set(len(q))

    def _enqueue(self, rid, payload, max_new_tokens: int,
                 trace_ctx: dict | None, *, prompt_tokens: int,
                 rng: tuple | None = None, cls: str = "standard",
                 prefilled: bool = False, **span_attrs) -> None:
        """The shared admission-queue push behind :meth:`submit` and
        :meth:`submit_prefilled`: drain/duplicate checks, the bounded-
        queue BUSY shed, request registration, the engine-side span
        pair, and the wakeup — ONE place, so the two admission paths
        cannot drift."""
        shed = False
        with self._work:
            if self._draining or self._stopped:
                raise RuntimeError(
                    "engine is draining; not accepting new requests")
            if rid in self._reqs:
                raise ValueError(f"request id {rid!r} is already active")
            # the explicit overload shed: a standard/batch submit past
            # the bounded queue depth is refused NOW with a retry hint
            # instead of growing the queue into a latency grave.
            # Interactive always queues (its overload story is the
            # floor + preemption); an already-prefilled package is
            # exempt too — its work is paid, the prefill tier shed
            # before prefilling.
            if (self._max_queue_depth and cls != "interactive"
                    and not prefilled
                    and self._wait_total_locked() >= self._max_queue_depth):
                shed = True
            else:
                stream = self._next_stream if rng is None else int(rng[0])
                skip = 0 if rng is None else int(rng[1])
                req = _EngineRequest(rid, payload, max_new_tokens, stream,
                                     time.perf_counter(), rng_skip=skip,
                                     cls=cls)
                if cls == "batch" and isinstance(payload,
                                                 (list, _PrefixHit)):
                    # evictable: track emitted values so a preemption
                    # can fold them into the reincarnation's prompt
                    req.history = []
                tr = tracing.get_tracer()
                req.span = tr.start_span("engine.request", ctx=trace_ctx,
                                         prompt_tokens=prompt_tokens,
                                         budget=max_new_tokens,
                                         request_class=cls,
                                         prefilled=prefilled,
                                         **span_attrs)
                req.queued_span = tr.start_span("engine.queued",
                                                parent=req.span)
                if rng is None:
                    # pinned streams live in the router's reserved range;
                    # the local counter keeps its own sequence untouched
                    self._next_stream += 1
                self._reqs[rid] = req
                self._waitq[cls].append(rid)
                self._set_qdepth_locked()
                self._work.notify_all()
        if shed:
            self._shed_c[cls].inc()
            raise EngineBusy(self._busy_retry_ms)

    def cancel(self, rid) -> None:
        """Cancel ``rid``. Idempotent: unknown / already-retired ids are
        no-ops (CANCEL racing retirement is safe). A waiting request
        retires immediately; an admitted one is marked done and its slot
        frees at the next consumed chunk."""
        with self._work:
            req = self._reqs.pop(rid, None)
            if req is None or req.done:
                return
            req.done = True
            req.reason = "cancelled"
            try:
                self._waitq[req.cls].remove(rid)
            except ValueError:
                pass          # admitted: the loop's consume frees it
            self._set_qdepth_locked()
            self._work.notify_all()
        self._cancelled_c.inc()
        req.queued_span.end()
        req.first_span.end()
        req.span.end(reason="cancelled", tokens=req.emitted)
        self._emit_retired(req)

    def drain(self) -> None:
        """Graceful drain: reject further submits; :meth:`run` returns
        once every accepted request has retired."""
        with self._work:
            self._draining = True
            self._work.notify_all()

    def stop(self) -> None:
        """Abort: run() returns after at most the in-flight chunk, and
        every outstanding request retires as ``"stopped"``."""
        with self._work:
            self._draining = True
            self._stopped = True
            self._work.notify_all()

    def live_requests(self) -> list:
        """rids accepted and not yet retired (waiting or admitted) —
        what a transport sweeps when its delivery path dies (the decode
        server's sink-loss cancel)."""
        with self._lock:
            return [rid for rid, r in self._reqs.items() if not r.done]

    def stats(self) -> dict:
        """Live occupancy snapshot (the serving server's STATS payload).
        ``queue_depth`` mirrors the ``tony_serve_queue_depth`` gauge."""
        with self._lock:
            return {
                "queue_depth": self._wait_total_locked(),
                "queue_depths": {c: len(q)
                                 for c, q in self._waitq.items()},
                "active": sum(1 for r in self._occupant
                              if r is not None and not r.done),
                "slots": self.b.batch,
                "class_floors": dict(self._floors),
                "draining": self._draining,
                # the prefix fast path's compute story, readable
                # cross-process (the e2e zero-prefix-forward pin)
                "prefill_tokens": self.b.prefill_forward_tokens,
                "prefill_padded_tokens": self.b.prefill_padded_tokens,
                "prefix_tokens": self.b.prefix_copied_tokens,
                "prefix_admits": self.b.prefix_admits,
                # the expert layers' device counters per program kind
                # (zeros for a model without experts)
                "moe_assignments": dict(self.b.moe_assignments),
                "moe_expert_touches": dict(self.b.moe_touches),
                "moe_zero_assignments": dict(self.b.moe_zero_assignments),
                # the cache by the kind of state that owns it, and what
                # the rings overwrote (0 without a ring)
                "cache_bytes": dict(self.b.cache_bytes),
                "ring_rows_overwritten": self.b.ring_rows_overwritten,
                # rows the decode chunks' cached reads visited and rows
                # live under their masks, by kind (live / read: how far
                # the read follows each row's own length)
                "cache_rows_read": dict(self.b.cache_rows_read),
                "cache_rows_live": dict(self.b.cache_rows_live),
                # a model with state-space mixers: the state its decode
                # steps moved and the positions its prompt scans ran
                # ({} without)
                "ssm": dict(self.b.ssm_counts),
                "steps_executed": self.b.steps_executed,
                # requests whose first token left ahead of its chunk
                "first_tokens_early":
                    self.b.phase_times.count("first_token_early"),
                # decode steps issued and unfetched, the mean over the
                # issues so far, and the depth (in programs of
                # ``chunk`` steps) the pipelined loop holds now
                "steps_in_flight": round(
                    self.b.phase_times.total("steps_in_flight")
                    / max(1, self.b.phase_times.count("steps_in_flight")),
                    3),
                "depth": self.b._ahead.depth,
            }

    # --- the loop (one driving thread) ---

    def run(self) -> None:
        """Drive the engine on the CALLING thread until drained or
        stopped. Between bursts of work the thread blocks on the
        admission condition — an idle engine costs nothing."""
        if getattr(self.b, "_engine_running", False):
            raise RuntimeError("batcher is already driven by an engine")
        self.b._engine_running = True
        try:
            # Goodput attribution for the serving plane: the driving
            # thread's wall is "step" (producing tokens) except the
            # blocks inside _wait_for_work, which re-enter "idle" —
            # slot busy-vs-idle falls out of the ledger breakdown.
            with goodput_mod.get_ledger().enter("step"):
                if self.b.pipeline:
                    self._run_pipelined()
                else:
                    self._run_sequential()
        finally:
            # seal the engine even on an abnormal exit (a device error
            # escaping the loop): late submits must raise rather than
            # enqueue into a dead engine the caller thinks is live
            with self._work:
                self._draining = True
                self._stopped = True
            self.b._engine_running = False
            self.b._load = _nobody_waiting     # let go of this engine
            self._abort_outstanding("stopped")
            metrics_mod.observe_phase_times(self.b.phase_times, self._reg)

    def _emit_retired(self, req: _EngineRequest, final=()) -> None:
        if self.on_retired is not None:
            self.on_retired(req.rid, req.reason, req.emitted,
                            list(final))

    def _abort_outstanding(self, reason: str) -> None:
        with self._lock:
            doomed = [r for r in self._reqs.values() if not r.done]
            for req in doomed:
                req.done = True
                req.reason = reason
            self._reqs.clear()
            for q in self._waitq.values():
                q.clear()
            self._occupant = [None] * self.b.batch
            self._firsts.clear()
            self._set_qdepth_locked()
        for req in doomed:
            req.queued_span.end()
            req.first_span.end()
            req.span.end(reason=reason, tokens=req.emitted)
            self._emit_retired(req)

    def _queue_load(self) -> tuple[int, int]:
        """(occupied slots, requests waiting), for the batcher's
        ``dispatch`` rows and its ``turn_loaded``."""
        with self._lock:
            return (sum(r is not None for r in self._occupant),
                    self._wait_total_locked())

    def _wait_for_work(self) -> bool:
        """Block until there is runnable work (True) or the engine is
        drained-empty / stopped (False). Live OCCUPANTS count as work,
        not just waiting requests: a trailing ``_settle()`` can admit a
        submission that raced the burst's last sweep, and ignoring it
        here would strand that admitted request (blocked forever, or
        wrongly aborted as ``"stopped"`` under drain)."""
        with self._work:
            while True:
                if self._stopped:
                    return False
                if (self._wait_total_locked()
                        or any(r is not None and not r.done
                               for r in self._occupant)):
                    return True
                if self._draining:
                    return False
                # the run ends here: the next enqueue opens a new one
                self.b._t_turn = None
                with self.b.phase_times.phase("wait"), \
                        goodput_mod.get_ledger().enter("idle"):
                    self._work.wait()

    def _pop_admissible_locked(self, free: int, occ: dict):
        """Pop the next admissible waiting request (class-priority
        order: interactive, standard, batch) under the floor
        discipline. ``free`` counts still-free slots INCLUDING the one
        about to be granted; ``occ`` is live per-class occupancy
        including this round's admissions."""
        for cls in QOS_CLASSES:
            # a class past its floor takes a free slot only while the
            # REMAINING free slots still cover every other class's
            # unmet floor (a floor is a reservation, held even absent
            # demand); a class under its own floor is claiming its
            # reservation and always admits
            if occ[cls] >= self._floors[cls]:
                owed = sum(max(0, self._floors[o] - occ[o])
                           for o in QOS_CLASSES if o != cls)
                if free - 1 < owed:
                    continue
            q = self._waitq[cls]
            while q:
                req = self._reqs.get(q.popleft())
                if req is not None and not req.done:
                    return req
        return None

    def _preempt_locked(self):
        """Evict batch rows for interactive admissions still waiting
        after the fill: the victim (fewest emitted tokens — cheapest
        re-prefill) is tombstoned exactly like a cancel (its slot
        frees at the next consumed chunk; stale in-flight tokens
        discard) and its stream is REINCARNATED under the same rid at
        the front of the batch queue — prompt + emitted history folded
        into the new payload, rng offset advanced by the emitted count,
        so the PR 12 re-prefill machinery resumes it token-identically.
        A KV-package victim (decode tier) has no prompt to fold: it
        genuinely retires as ``"preempted"`` and the router re-places
        it. Returns ``(requeued, evicted)`` for the off-lock span /
        retirement work."""
        waiting = len(self._waitq["interactive"])
        requeued, evicted = [], []
        if not waiting:
            return requeued, evicted
        # slots already on their way free (done occupants vacate at the
        # next consumed chunk) count against the need — without this,
        # every settle between eviction and slot-free would evict again
        vacating = sum(1 for r in self._occupant
                       if r is not None and r.done)
        need = waiting - vacating
        while need > 0:
            victims = [r for r in self._occupant
                       if r is not None and not r.done
                       and r.cls == "batch"]
            # never evict below the batch floor — the freed slot would
            # be owed straight back to the batch queue
            if len(victims) <= self._floors["batch"]:
                break
            old = min(victims, key=lambda r: r.emitted)
            old.done = True
            old.reason = "preempted"
            self._reqs.pop(old.rid, None)
            if old.history is not None:
                old.requeued = True
                if isinstance(old.prompt, _PrefixHit):
                    payload = _PrefixHit(
                        old.prompt.entry,
                        list(old.prompt.suffix) + old.history)
                else:
                    payload = list(old.prompt) + old.history
                new = _EngineRequest(old.rid, payload, old.budget,
                                     old.stream, old.t_submit,
                                     rng_skip=old.rng_skip + old.emitted,
                                     cls="batch")
                new.emitted = old.emitted  # resume deltas are ITL
                new.t_last = old.t_last
                new.t_queued = time.perf_counter()   # waits anew
                new.history = list(old.history)
                new.span = old.span        # same logical request
                old.span = tracing.NOOP_SPAN
                self._reqs[old.rid] = new
                self._waitq["batch"].appendleft(old.rid)
                requeued.append(new)
            else:
                evicted.append(old)
            need -= 1
        if requeued or evicted:
            self._set_qdepth_locked()
        return requeued, evicted

    def _admit_free(self) -> None:
        """Admit waiting requests into every free slot (row order — the
        freed order, since consume builds freed lists row-ascending),
        draining the class queues in priority order under the per-class
        floors, then preempt batch rows for any interactive admissions
        left waiting. The device dispatch runs OUTSIDE the lock; a
        request cancelled between marking and dispatch is discarded at
        its first consume. Everything up to the dispatch is the phase
        ``admit_pick``; the dispatch is the batcher's ``admit``."""
        with self.b.phase_times.phase("admit_pick"):
            pairs, prompts, admitted = self._pick_admissions()
        if not admitted:
            return
        b = self.b
        before = (b.prefill_forward_tokens, b.prefix_copied_tokens,
                  b.prefix_admits, b.prefill_padded_tokens)
        first = b._admit_batch(pairs, prompts)
        for (row, _), req in zip(pairs, admitted):
            req.admit_seq = b._row_seq[row]
        if first is not None:
            self._firsts.append((b.seq, first, [
                (row, req) for (row, _), req in zip(pairs, admitted)]))
        self._admitted_c.inc(len(admitted))
        # fold the batcher's host-side prefill accounting into the
        # registry (the batcher itself is registry-unaware)
        if b.prefill_forward_tokens > before[0]:
            self._prefill_tok_c.inc(b.prefill_forward_tokens - before[0])
        if b.prefix_copied_tokens > before[1]:
            self._prefix_tok_c.inc(b.prefix_copied_tokens - before[1])
        if b.prefix_admits > before[2]:
            self._prefix_admits_c.inc(b.prefix_admits - before[2])
        if b.prefill_padded_tokens > before[3]:
            self._prefill_pad_c.inc(b.prefill_padded_tokens - before[3])

    def _pick_admissions(self):
        """The host half of an admission sweep: pop, preempt, and close
        each admitted request's wait. Returns ``(pairs, prompts,
        admitted)`` for the device dispatch."""
        with self._lock:
            pairs, prompts, admitted = [], {}, []
            occ = {c: 0 for c in QOS_CLASSES}
            free = 0
            for r in self._occupant:
                if r is None:
                    free += 1
                elif not r.done:
                    occ[r.cls] += 1
            for row in range(self.b.batch):
                if self._occupant[row] is not None:
                    continue
                req = self._pop_admissible_locked(free, occ)
                if req is None:
                    break
                self._occupant[row] = req
                occ[req.cls] += 1
                free -= 1
                pairs.append((row, req.stream))
                prompts[req.stream] = req.prompt
                admitted.append(req)
            if admitted:
                self._set_qdepth_locked()
            requeued, evicted = self._preempt_locked()
        if requeued or evicted:
            self._preempt_c.inc(len(requeued) + len(evicted))
            tr = tracing.get_tracer()
            for new in requeued:
                if new.span.recording:
                    new.queued_span = tr.start_span("engine.queued",
                                                    parent=new.span,
                                                    preempted=True)
            for old in evicted:
                old.first_span.end()
                old.span.end(reason="preempted", tokens=old.emitted)
                self._emit_retired(old)
        if admitted:
            tr = tracing.get_tracer()
            pt = self.b.phase_times
            # the newest program its admission will stand behind
            ahead = self.b._unfetched[-1][0] if self.b._unfetched else None
            now = time.perf_counter()
            for (row, _), req in zip(pairs, admitted):
                # the wait for a slot ends here — counted (a wait, not
                # a loop phase: waits of different requests overlap and
                # do not sum to the thread's wall) at the same instant
                # the per-request span ends
                pt.observe("queue_wait", now - req.t_queued)
                # ... and so does the time a free slot and a runnable
                # request both stood waiting for the loop to come round
                t_freed, freed_seq = self._freed[row]
                pt.observe("slot_vacant", now - max(t_freed, req.t_queued))
                req.t_admit = req.t_ride = now
                if ahead is not None:
                    self._behind.append((req, ahead))
                req.queued_span.end(slot=row, freed_seq=freed_seq)
                if req.span.recording:
                    # admit → first consumed delta: the prefill+decode
                    # share of TTFT, next to engine.queued's queue share
                    req.first_span = tr.start_span("engine.first_token",
                                                   parent=req.span)
                if req.rng_skip:
                    # consumed by _rebind_streams at this admission
                    self.b._stream_skip[req.stream] = req.rng_skip
        return pairs, prompts, admitted

    def _consume(self, host_toks, snap) -> None:
        """Apply one fetched chunk under the occupancy it was ISSUED
        with, freeing completed/cancelled rows and emitting per-request
        deltas. Rows whose snapshot request already finished (a
        speculatively issued chunk crossed the completion, or a cancel
        landed mid-flight) carry garbage and are discarded — the same
        discard as idle-slot garbage. The whole of it is the phase
        ``consume``; the callbacks inside it are ``emit``."""
        if self._behind:
            # the last program it stood behind has returned; its admission
            # and the fetch of its first token are what is left
            still = []
            for req, seq in self._behind:
                if seq <= self.b._seq_run:
                    req.t_ride = self.b._t_turn
                else:
                    still.append((req, seq))
            self._behind = still
        with self.b.phase_times.phase("consume"):
            self._consume_chunk(host_toks, snap)
        with self.b.phase_times.phase("account"):
            for (what, program), c in self._moe_c.items():
                total = self._moe_totals[what][program]
                c.inc(total - self._moe_seen[(what, program)])
                self._moe_seen[(what, program)] = total
            for (what, kind), c in self._rows_c.items():
                total = (self.b.cache_rows_read if what == "read"
                         else self.b.cache_rows_live)[kind]
                c.inc(total - self._rows_seen[(what, kind)])
                self._rows_seen[(what, kind)] = total
            for what, c in self._ssm_c.items():
                total = self.b.ssm_counts[what]
                c.inc(total - self._ssm_seen[what])
                self._ssm_seen[what] = total

    def _free_slot(self, row: int, t: float) -> None:
        self._occupant[row] = None
        self._freed[row] = (t, self.b._seq_run)

    def _fetch(self, handle):
        """The loops' fetch seam: block on the oldest unfetched chunk —
        and first on every first-token draw that stands AHEAD of it in
        the device queue (enqueued before it; the wait is the phase
        ``first_fetch``, the delivery a ``consume``). Never on one
        enqueued behind it: the admission it follows has yet to run,
        and waiting for it here would add that admission to the gap of
        every live stream. So an admitted request's first token leaves
        when the device has reached it, one chunk's time ahead of the
        chunk that carries it as column 0."""
        b = self.b
        seq = b._unfetched[0][0]
        while self._firsts and self._firsts[0][0] <= seq:
            _, first, rows = self._firsts.popleft()
            with b.phase_times.phase("first_fetch", seq=seq):
                host = np.asarray(first)
            with b.phase_times.phase("consume"):
                self._consume_first(host, rows, seq)
        return b._fetch(handle)

    def _take_locked(self, req, row: int, toks, t_free: float) -> list:
        """Move ``req``'s books by ``toks``, in order, up to the token
        that ends it (eos or its budget; the surplus is discarded): a
        preemption, a cancel or a failover from here on sees what the
        client saw. A request that ends frees its slot, free since
        ``t_free``. Returns the tokens taken."""
        eos = self.b.eos_id
        new = []
        for t in toks:
            t = int(t)
            new.append(t)
            req.emitted += 1
            req.budget -= 1
            if req.budget == 0 or (eos is not None and t == eos):
                req.done = True
                req.reason = ("eos" if eos is not None and t == eos
                              else "budget")
                self._reqs.pop(req.rid, None)
                if self._occupant[row] is req:
                    self._free_slot(row, t_free)
                break
        if new and req.history is not None:
            # evictable row: a preemption folds these into the
            # reincarnation's prompt
            req.history.extend(new)
        return new

    def _consume_first(self, host, rows, chunk_seq: int) -> None:
        """Deliver a wave's first tokens (``host``: the draw's [B]
        tokens; ``rows``: the wave's (row, request) pairs), ahead of
        chunk ``chunk_seq`` — the first enqueued behind the wave, which
        carries the same tokens as its column 0. A request cancelled or
        preempted behind its admission gets nothing (its reincarnation
        re-prefills the whole prompt); one that ends on this token (eos,
        a budget of 1) retires here and frees its slot, and its row of
        the chunk is discarded as a mid-flight cancel's is."""
        deltas, retired = [], []
        now = time.perf_counter()
        with self._lock:
            for row, req in rows:
                if req.done:
                    continue
                req.sent_ahead = int(host[row])
                deltas.append((req, self._take_locked(
                    req, row, (req.sent_ahead,), now)))
                if req.done:
                    retired.append(req)
        self._deliver(deltas, retired, chunk_seq, early=True)

    def _consume_chunk(self, host_toks, snap) -> None:
        deltas, retired = [], []
        # a slot this chunk frees has been free since the chunk returned
        t_chunk = self.b._t_turn
        with self._lock:
            for row, req in enumerate(snap):
                if req is None or req.done:
                    if req is not None and self._occupant[row] is req:
                        # cancelled mid-flight: free the slot now
                        self._free_slot(row, t_chunk)
                    continue
                toks = host_toks[row]
                if req.sent_ahead is not None:
                    # column 0 left ahead of this chunk: the same draw
                    # from the same logits, so the same token
                    if int(toks[0]) != req.sent_ahead \
                            and not self._redraw_warned:
                        self._redraw_warned = True
                        import logging
                        logging.getLogger(__name__).error(
                            "request %r: first token sent as %d, its "
                            "chunk drew %d", req.rid, req.sent_ahead,
                            int(toks[0]))
                    req.sent_ahead = None
                    toks = toks[1:]
                new = self._take_locked(req, row, toks, t_chunk)
                if new:
                    deltas.append((req, new))
                if req.done:
                    retired.append(req)
        self._deliver(deltas, retired, self.b._seq_run)

    def _deliver(self, deltas, retired, chunk_seq: int,
                 early: bool = False) -> None:
        """Close the waits that end with these deltas and hand them to
        the transport: ``deltas`` is [(request, its new tokens)], and
        those of them in ``retired`` (ended by their last token, under
        the lock) retire with theirs as the final delta. ``chunk_seq``
        is the chunk that delivered, or — ``early`` — the one the tokens
        left ahead of."""
        now = time.perf_counter()
        appended = 0
        finals = {id(req): new for req, new in deltas
                  if req in retired}
        for req, new in deltas:
            appended += len(new)
            if req.emitted == len(new):      # this is the first delta
                self._ttft_h.observe(now - req.t_submit)
                self._ttft_by_cls[req.cls].observe(now - req.t_submit)
                # admission → first delta, counted where it ends (a
                # wait like queue_wait: overlapping, not a loop phase)
                pt = self.b.phase_times
                pt.observe("first_token", now - req.t_admit)
                # ... split where the chunk in flight at the admission
                # returned: behind it, then the admission on the device
                # up to this delta
                pt.observe("first_token_queued", req.t_ride - req.t_admit)
                pt.observe("first_token_ride", now - req.t_ride)
                if early:
                    # the same wait, of those that did not ride a chunk:
                    # over first_token's ops, the share that left early
                    pt.observe("first_token_early", now - req.t_admit)
                    self._early_c.inc()
                req.first_span.end(admit_seq=req.admit_seq,
                                   chunk_seq=chunk_seq, early=early)
            else:
                gap = (now - req.t_last) / len(new)
                self._itl_h.observe(gap)
                self._itl_by_cls[req.cls].observe(gap)
            req.t_last = now
        if appended:
            self._tokens_c.inc(appended)
        if retired:
            self._retired_c.inc(len(retired))
            if self.b._ring_shape[0]:
                # a request's prompt is its tokens, or a shipped prefill
                self._ring_over_c.inc(sum(
                    self.b.count_finished(
                        req.prompt.length if isinstance(req.prompt,
                                                        KVPackage)
                        else len(req.prompt), req.emitted)
                    for req in retired))
        if not deltas:
            return
        # what the transport does with the tokens, ON this thread: the
        # frame server packs and sends each delta from these callbacks
        with self.b.phase_times.phase("emit"):
            # a retiring request's FINAL delta rides its retirement
            # callback instead of on_delta, so transports can emit the
            # two atomically (a replica killed between a final TOKENS
            # frame and its RETIRED would otherwise leave a router
            # believing the stream is unfinished and re-admitting PAST
            # an already-streamed eos)
            if self.on_delta is not None:
                for req, new in deltas:
                    if id(req) not in finals:
                        self.on_delta(req.rid, new)
            for req in retired:
                req.first_span.end()     # eos on the very first delta
                req.span.end(reason=req.reason, tokens=req.emitted)
                self._emit_retired(req, finals.get(id(req), ()))

    def _settle(self) -> None:
        self._admit_free()
        # reset ALL unoccupied rows (not just newly freed): a slot idle
        # across many chunks would otherwise march its garbage frontier
        # every step until it clamps at the cache end
        with self._lock:
            idle = [r is None for r in self._occupant]
        if any(idle):
            with self.b.phase_times.phase("retire"):
                self.b._retire(idle)

    def _sweep_done_occupants(self) -> bool:
        """Free slots held by done (cancelled) occupants when no chunk
        is in flight to do it; returns True when any slot is LIVE."""
        with self._lock:
            live = False
            for row, req in enumerate(self._occupant):
                if req is None:
                    continue
                if req.done:
                    self._free_slot(row, time.perf_counter())
                else:
                    live = True
            return live

    def _spare(self, req: _EngineRequest, a_program: int) -> int:
        """Tokens the programs in flight hold for ``req`` past its
        budget, at ``a_program`` tokens each (under the lock): at 0 or
        more its end is already in the device's queue. Column 0 of its
        first program is the token that left ahead (``sent_ahead``,
        until that program is consumed) and counts for nothing."""
        return (req.flight * a_program - (req.sent_ahead is not None)
                - req.budget)

    def _foresee(self) -> bool:
        """Decide whether one more decode program goes into the queue
        now, from what the host can FORESEE step by step: a budget's end
        is host-visible ahead of time (eos and speculative acceptance
        only finish EARLIER, and every speculative round commits >= 1
        token), and ``flight`` says how many programs each request
        already has in the queue.

        - Nothing waits: a program past every live request's certain end
          is a guaranteed-garbage dispatch — hold. (A submission landing
          meanwhile is admitted at the next settle and the loop goes on.)
        - A request waits and a row's last step is CERTAINLY in the queue:
          the slot is the waiting request's from there. The row is
          vacated now and the admission enqueued behind that step, on a
          busy device, where the loop before PR 42 stopped issuing,
          drained, and admitted on an idle one; the old occupant's
          tokens still reach it through the snapshots of the programs
          that carry them. Budget-only workloads pipeline LOSSLESSLY:
          program count, admission order and utilization match the
          sequential loop.
        - A request waits and a row's end is POSSIBLY in the queue but
          not certainly (a speculative round commits 1..k+1 tokens):
          hold until the programs in flight have told.

        Unforeseen ends (eos, a cancel) are caught up as they are
        consumed: the freed row idles the steps already in the queue
        behind it — ``depth`` at most — and the late admission
        overwrites the slot before anything reads it."""
        b = self.b
        sure, most = b.chunk, b._chunk_tokens_max()
        vacated = hold = False
        with self._lock:
            live = [(row, req) for row, req in enumerate(self._occupant)
                    if req is not None and not req.done]
            if not self._wait_total_locked():
                return any(self._spare(req, sure) < 0 for _, req in live)
            now = time.perf_counter()
            for row, req in live:
                if self._spare(req, sure) >= 0:
                    self._occupant[row] = None
                    self._freed[row] = (now, b._seq_chunk - 1)
                    vacated = True
                elif most > sure and self._spare(req, most) >= 0:
                    hold = True
        if vacated:
            self._settle()
        return not hold

    def _top_up(self, queue: collections.deque) -> None:
        """Issue decode programs until ``depth`` of them stand issued
        and unfetched (``queue``: (device tokens, the occupancy each was
        issued under), oldest first) or :meth:`_foresee` holds."""
        b = self.b
        self._depth_g.set(b._ahead.depth)
        while (len(queue) < b._ahead.depth and not self._stopped
               and self._foresee()):
            handle, snap = b._issue(), list(self._occupant)
            for req in snap:
                if req is not None:
                    req.flight += 1
            queue.append((handle, snap))

    def _run_pipelined(self) -> None:
        """A queue of issued decode programs against the live queue:
        ``depth`` of them (:class:`_Depth`) stand in the device's queue
        while the host fetches the oldest, consumes it, settles and tops
        the queue up — so the fetch, the bookkeeping and the admissions'
        marshalling overlap device compute, and a program's tokens leave
        the turn it returns. Fetched strictly in the order issued."""
        b = self.b
        while self._wait_for_work():
            self._admit_free()
            if not self._sweep_done_occupants():
                self._settle()          # everything cancelled pre-issue
                continue
            queue: collections.deque = collections.deque()
            self._top_up(queue)
            while queue:
                handle, snap = queue.popleft()
                self._consume(self._fetch(handle), snap)
                for req in snap:
                    if req is not None:
                        req.flight -= 1
                self._settle()
                if self._stopped:
                    return               # drop the programs in flight
                with self._lock:
                    occupied = any(r is not None for r in self._occupant)
                if queue and not occupied and not any(
                        req is not None and not req.done
                        for _, issued in queue for req in issued):
                    # every request retired while later programs were in
                    # flight (eos beat the budget bound): drop them
                    # unfetched — all their rows are garbage, and no turn
                    # will close on them
                    for _ in queue:
                        b._unfetched.pop()
                    queue.clear()
                self._top_up(queue)

    def _run_sequential(self) -> None:
        """issue → fetch → bookkeep → admit (``pipeline=False``): the
        reference the tests hold the pipelined loop to — every fetch
        serializes the transport round trip with device compute."""
        b = self.b
        while self._wait_for_work():
            self._admit_free()
            while not self._stopped:
                if not self._sweep_done_occupants():
                    self._settle()
                    break
                snap = list(self._occupant)
                self._consume(self._fetch(b._issue()), snap)
                self._settle()
