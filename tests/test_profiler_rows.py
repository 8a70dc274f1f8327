"""The program's phases on the profiler's clock: ``PhaseTimes.phase`` and
``Tracer.span`` enter ``tony.<layer>.<phase>`` rows through ONE helper
that never imports jax; the serve engine counts each request's waits where
they end; the flash kernels carry stable names into the jaxpr; the compile
cache totals JAX's duration events without changing ``stats()``.

CPU only; no sleeps and no timing assertions — counts and containment.
"""

import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import transformer as T
from tony_tpu.models.loop import run_training
from tony_tpu.models.serve import ContinuousBatcher, ServeEngine
from tony_tpu.runtime import compile_cache, metrics as M
from tony_tpu.runtime import profiler, tracing
from tony_tpu.runtime.profiler import PhaseTimes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


class _Row:
    """Stands in for a profiler annotation: records what was entered."""

    def __init__(self, log, kind, name, **kw):
        self.entry = (kind, name, kw)
        self.log = log

    def __enter__(self):
        self.log.append(self.entry)

    def __exit__(self, *exc):
        return False


@pytest.fixture
def rows(monkeypatch):
    log = []
    monkeypatch.setattr(
        jax.profiler, "TraceAnnotation",
        lambda name, **kw: _Row(log, "row", name, **kw))
    monkeypatch.setattr(
        jax.profiler, "StepTraceAnnotation",
        lambda name, **kw: _Row(log, "step", name, **kw))
    return log


# ---------------------------------------------------------------- the helper
def test_phase_enters_prefixed_row_and_accumulates(rows):
    pt = PhaseTimes("tony.engine")
    with pt.phase("dispatch"):
        pass
    with pt.phase("dispatch"):
        pass
    assert rows == [("row", "tony.engine.dispatch", {})] * 2
    assert pt.count("dispatch") == 2 and pt.total("dispatch") >= 0.0
    assert set(pt.summary()["dispatch"]) == {"total_s", "count", "mean_ms"}


@pytest.mark.parametrize("capturing", [False, True])
def test_phase_attrs_ride_the_row_only_under_a_capture(monkeypatch,
                                                       capturing):
    """What a row says of its program (``seq``, a batch's size) is the
    profiler's to format; with no capture running nobody reads it, and it
    is let go before that. The interval is observed either way — also
    when the block raises."""
    log = []

    class Row(_Row):
        is_enabled = staticmethod(lambda: capturing)

        def __init__(self, name, **kw):
            super().__init__(log, "row", name, **kw)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Row)
    pt = PhaseTimes("tony.engine")
    with pt.phase("dispatch", seq=7, live=3):
        pass
    with pytest.raises(KeyError):
        with pt.phase("dispatch", seq=8, live=3):
            raise KeyError("x")
    want = [{"seq": 7, "live": 3}, {"seq": 8, "live": 3}] if capturing \
        else [{}, {}]
    assert log == [("row", "tony.engine.dispatch", kw) for kw in want]
    assert pt.count("dispatch") == 2


def test_phase_without_prefix_enters_no_row(rows):
    pt = PhaseTimes()
    with pt.phase("fetch"):
        pass
    assert rows == [] and pt.count("fetch") == 1


def test_observe_adds_total_and_count(rows):
    pt = PhaseTimes("tony.engine")
    pt.observe("queue_wait", 0.25)
    pt.observe("queue_wait", 0.5)
    assert rows == []                       # an interval, not a block
    assert pt.summary()["queue_wait"] == {
        "total_s": 0.75, "count": 2, "mean_ms": 375.0}


@pytest.mark.parametrize("rate", [0.0, 1.0])
def test_span_enters_row_sampled_or_not(rows, rate):
    tr = tracing.Tracer(proc="t", sample_rate=rate)
    with tr.span("train.dispatch", shard=3) as sp:
        assert sp.recording == (rate == 1.0)
    assert rows == [("row", "tony.train.dispatch", {})]
    assert tr.recorded == (1 if rate else 0)   # sampling governs STORAGE


def test_step_root_is_a_step_annotation_of_the_layer(rows):
    tr = tracing.Tracer(proc="t", sample_rate=0.0)
    with tr.span("train.step", step=7, step_num=7):
        with tr.span("train.data_wait"):
            pass
    assert rows == [("step", "tony.train", {"step_num": 7}),
                    ("row", "tony.train.data_wait", {})]


def test_annotation_is_null_until_jax_is_loaded(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    assert tracing.profiler_annotation("tony.x") is tracing.NO_ANNOTATION
    with tracing.profiler_annotation("tony.x", step_num=1):
        pass


def test_jax_free_processes_stay_jax_free():
    """What ``benchmark/drivers/serve.py`` fails a run on: the serving
    client, the tracing plane and PhaseTimes must not pull jax in — not
    at import and not when a phase and a span are entered."""
    code = (
        "import sys\n"
        "import tony_tpu.serving.client\n"
        "from tony_tpu.runtime import tracing\n"
        "from tony_tpu.runtime.profiler import PhaseTimes\n"
        "pt = PhaseTimes('tony.engine')\n"
        "with pt.phase('dispatch'):\n"
        "    pass\n"
        "pt.observe('queue_wait', 0.1)\n"
        "with tracing.get_tracer().span('client.request', step_num=None):\n"
        "    pass\n"
        "with tracing.Tracer(sample_rate=0.0).span('train.step',"
        " step_num=3):\n"
        "    pass\n"
        "assert pt.count('dispatch') == 1\n"
        "print('jax' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


# ------------------------------------------------------------ the serve engine
class _Counting(ContinuousBatcher):
    """Counts the calls the four old phases enclose."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.calls = {"dispatch": 0, "fetch": 0, "retire": 0}

    def _issue(self):
        self.calls["dispatch"] += 1
        return super()._issue()

    def _fetch(self, handle):
        self.calls["fetch"] += 1
        return super()._fetch(handle)

    def _retire(self, mask):
        self.calls["retire"] += 1
        return super()._retire(mask)


def _prompts(seed, sizes):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, CFG.vocab_size, size=n)]
            for n in sizes]


def _run_with_preemption(params, registry):
    """Two batch rows fill both slots; once both stream, an interactive
    request arrives (from the delta callback, so no clock is involved)
    and evicts one of them, which is re-admitted later: four admissions
    for three requests."""
    b = _Counting(params, CFG, batch=2, max_len=32, chunk=3)
    got, state = {}, {"sent": False}
    prompts = _prompts(41, (5, 4, 6))

    def on_delta(rid, toks):
        got.setdefault(rid, []).extend(toks)
        if not state["sent"] and got.get(0) and got.get(1):
            state["sent"] = True
            engine.submit(2, prompts[2], 6, request_class="interactive")
            engine.drain()

    engine = ServeEngine(
        b, on_delta=on_delta, registry=registry,
        on_retired=lambda rid, reason, n, final:
            got.setdefault(rid, []).extend(final))
    engine.submit(0, prompts[0], 12, request_class="batch")
    engine.submit(1, prompts[1], 12, request_class="batch")
    engine.run()
    assert state["sent"] and [len(got[r]) for r in (0, 1, 2)] == [12, 12, 6]
    return b


def test_engine_counts_waits_where_they_end(params):
    reg = M.MetricsRegistry()
    b = _run_with_preemption(params, reg)
    pt = b.phase_times
    assert reg.counter("tony_serve_preemptions_total").value == 1
    admissions = reg.counter("tony_serve_requests_admitted_total").value
    assert admissions == 4
    assert pt.count("queue_wait") == admissions      # the re-admission too
    assert pt.count("first_token") == 3              # once per request
    assert pt.total("queue_wait") >= 0.0 and pt.total("first_token") > 0.0
    # the loop's new phases: every fetched chunk is consumed, and every
    # fetched wave of first tokens; deltas and retirements are emitted
    # inside consume; the sweep runs each turn
    assert pt.count("consume") == pt.count("fetch") \
        + pt.count("first_fetch") > pt.count("fetch") > 0
    assert 0 < pt.count("emit") <= pt.count("consume")
    assert pt.total("emit") <= pt.total("consume")
    assert pt.count("admit_pick") >= pt.count("admit") > 0
    # the old four keep their meaning: one entry per call they enclose
    for name, n in b.calls.items():
        assert pt.count(name) == n, name
    assert pt.count("dispatch") == b.steps_executed // b.chunk
    assert pt.prefix == "tony.engine"
    # ... and ride the registry fold the engine already does at exit
    for phase in ("queue_wait", "first_token", "emit", "consume",
                  "admit_pick", "dispatch", "fetch", "first_fetch",
                  "admit", "retire"):
        assert reg.counter("tony_serve_phase_ops_total",
                           phase=phase).value == pt.count(phase), phase


def test_closed_batch_wait_counts(params):
    """No preemption: one admission and one first token per request; a
    drained engine never blocks, so ``wait`` is never entered."""
    b = ContinuousBatcher(params, CFG, batch=2, max_len=32, chunk=3)
    outs = b.serve(_prompts(3, (4, 4, 4)), max_new_tokens=5)
    assert [len(o) for o in outs] == [5, 5, 5]
    pt = b.phase_times
    assert pt.count("queue_wait") == pt.count("first_token") == 3
    assert pt.count("wait") == 0


# ------------------------------------------------- read back from a capture
def _tony_events(logdir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("tony."):
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, dict(e.stats)))
    return out


def test_capture_holds_the_engine_rows(params, tmp_path):
    b = ContinuousBatcher(params, CFG, batch=2, max_len=32, chunk=3)
    b.serve(_prompts(5, (4, 4)), max_new_tokens=4)        # compile outside
    with profiler.trace(str(tmp_path)):
        b.serve(_prompts(6, (4, 4, 4)), max_new_tokens=7)
    events = _tony_events(str(tmp_path))
    names = {e[0] for e in events}
    assert {"tony.engine." + p for p in (
        "dispatch", "fetch", "first_fetch", "consume", "emit", "admit",
        "admit_pick", "retire", "admit_dispatch", "account")} <= names
    # emit nests in consume: each emit lies inside some consume
    consumes = [e for e in events if e[0] == "tony.engine.consume"]
    for _, s, t, _ in (e for e in events if e[0] == "tony.engine.emit"):
        assert any(cs <= s and t <= ct for _, cs, ct, _ in consumes)


def test_capture_rows_carry_the_device_queue_order(params, tmp_path):
    """Every program the engine enqueues is a row with its ``seq``: the
    chunks' ``dispatch`` rows and the admissions' ``admit_dispatch`` rows
    together count 0, 1, 2, ... in the order of their start times (the
    device queue's order), each ``fetch`` blocks on a chunk's seq, and an
    admission row says what was dispatched."""
    b = ContinuousBatcher(params, CFG, batch=2, max_len=64, chunk=3)
    b.serve(_prompts(5, (4, 20)), max_new_tokens=4)       # compile outside
    with profiler.trace(str(tmp_path)):
        # two buckets in the first wave: two dispatches in one admit
        b.serve(_prompts(6, (4, 20, 5)), max_new_tokens=7)
    events = _tony_events(str(tmp_path))
    by = {}
    for name, s, t, stats in sorted(events, key=lambda e: e[1]):
        by.setdefault(name.rsplit(".", 1)[1], []).append((s, t, stats))
    enqueued = sorted(by["dispatch"] + by["admit_dispatch"])
    assert [int(st["seq"]) for _, _, st in enqueued] == \
        list(range(len(enqueued))) and len(enqueued) == b.seq
    chunk_seqs = [int(st["seq"]) for _, _, st in by["dispatch"]]
    assert [int(st["seq"]) for _, _, st in by["fetch"]] == chunk_seqs
    for _, _, st in by["dispatch"]:
        assert 0 <= int(st["live"]) <= 2 and int(st["waiting"]) >= 0
    first, second, third = by["admit_dispatch"]
    assert [(int(st["bucket"]), int(st["rows"]), int(st["tokens"]))
            for _, _, st in (first, second, third)] == [
        (16, 2, 4), (32, 2, 20), (16, 2, 5)]
    # both dispatches of the first wave nest in ONE admit
    (s0, t0, _), (s1, t1, _) = first, second
    assert any(s <= s0 and t1 <= t for s, t, _ in by["admit"])
    assert len(by["admit"]) == 2


def test_capture_holds_train_steps_with_their_children(tmp_path):
    seen = []

    def step(state, batch):
        seen.append(batch)
        return state + 1, {"loss": jnp.float32(0.0)}

    with profiler.trace(str(tmp_path)):
        state, _ = run_training(step, 0, iter([10, 11, 12]), 3,
                                log_every=1 << 30)
    assert state == 3 and seen == [10, 11, 12]
    events = _tony_events(str(tmp_path))
    steps = sorted((e for e in events if e[0] == "tony.train"),
                   key=lambda e: e[1])
    assert [e[3].get("step_num") for e in steps] == [0, 1, 2]
    for child in ("tony.train.data_wait", "tony.train.dispatch"):
        inside = [e for e in events if e[0] == child]
        assert len(inside) == 3, child
        for (_, s, t, _), (_, ss, st, _) in zip(
                sorted(inside, key=lambda e: e[1]), steps):
            assert ss <= s and t <= st, child


def test_data_wait_is_a_child_span_and_still_observed():
    tr = tracing.Tracer(proc="t", sample_rate=1.0)
    prev = tracing.set_tracer(tr)
    reg = M.MetricsRegistry()
    prev_reg = M.set_default(reg)
    try:
        run_training(lambda s, b: (s, {}), 0, iter([1, 2]), 5,
                     log_every=1 << 30)        # runs dry after two
    finally:
        tracing.set_tracer(prev)
        M.set_default(prev_reg)
    spans = tr.recent()
    roots = {s["sid"]: s for s in spans if s["n"] == "train.step"}
    waits = [s for s in spans if s["n"] == "train.data_wait"]
    assert len(roots) == 3 and len(waits) == 3      # the dry fetch too
    assert all(w["pid"] in roots for w in waits)
    assert reg.histogram("tony_data_wait_seconds").count == 2


# --------------------------------------------------- names in the program
def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"],
                        str(eqn.source_info.name_stack)))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


@pytest.mark.parametrize("partials_bytes,expected", [
    (1 << 40, {"tony_flash_fwd", "tony_flash_bwd_fused"}),
    (0, {"tony_flash_fwd", "tony_flash_bwd_dq", "tony_flash_bwd_dkv"})])
def test_train_step_jaxpr_carries_the_kernel_names(monkeypatch,
                                                   partials_bytes, expected):
    """Traced, never lowered: with the Mosaic arm switched on, the train
    step's pallas_call equations carry the names the device trace is read
    by, under the ``attn`` section's scope."""
    from tony_tpu.models.train import (default_optimizer, init_state,
                                       make_train_step)
    from tony_tpu.ops import attention, mosaic
    monkeypatch.setattr(mosaic, "interpret", lambda: False)
    monkeypatch.setattr(attention, "_FUSED_PARTIALS_BYTES", partials_bytes)
    cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32)     # remat on
    opt = default_optimizer(lr=1e-3, warmup_steps=1, total_steps=10)
    state = jax.eval_shape(
        lambda: init_state(T.init_params(jax.random.PRNGKey(0), cfg), opt))
    step = make_train_step(lambda p, b: T.lm_loss(p, b, cfg, None), opt,
                           None, donate=False)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 257), jnp.int32)}
    found = _pallas_names(jax.make_jaxpr(step)(state, batch).jaxpr, [])
    assert {name for name, _ in found} == expected
    # the forward appears twice: the pass itself and remat's replay
    assert sum(name == "tony_flash_fwd" for name, _ in found) == 2
    for name, stack in found:
        assert f"attn/{name}" in stack, stack


# ----------------------------------------------------------- compile seconds
def test_compile_seconds_total_and_stats_text_unchanged(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/nonexistent/unused")
    compile_cache.enable()        # registers both listeners, sets nothing
    before = compile_cache.seconds()
    assert set(before) == {"trace", "lower", "backend_compile",
                           "cache_retrieval"}
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 1.5)
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    jax.monitoring.record_event_duration_secs("/jax/some/other_event", 9.0)
    after = compile_cache.seconds()
    assert after["backend_compile"] - before["backend_compile"] == \
        pytest.approx(1.5)
    assert after["cache_retrieval"] - before["cache_retrieval"] == \
        pytest.approx(0.25)
    assert after["trace"] == before["trace"]
    # both benchmark jobs parse stats() into exactly two integers
    text = compile_cache.stats()
    assert re.fullmatch(r"compile cache: \d+ hits of \d+ requests", text)
    assert len(re.findall(r"\d+", text)) == 2
    assert compile_cache.seconds_line().startswith("compile seconds: trace ")


def test_a_real_compile_moves_the_trace_and_lower_seconds(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/nonexistent/unused")
    compile_cache.enable()
    before = compile_cache.seconds()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    after = compile_cache.seconds()
    assert after["trace"] > before["trace"]
    assert after["lower"] > before["lower"]
    assert after["backend_compile"] > before["backend_compile"]


# ------------------------------------------------- captures start quiet
@pytest.mark.parametrize("how", ["trace", "step_tracer"])
def test_captures_start_with_the_python_tracer_off(tmp_path, monkeypatch,
                                                   how):
    started = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda logdir, **kw: started.append((logdir, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    if how == "trace":
        with profiler.trace(str(tmp_path)):
            pass
    else:
        st = profiler.StepTracer(start=1, stop=2, logdir=str(tmp_path))
        for i in range(3):
            st.step(i)
        st.close()
    ((logdir, kw),) = started
    assert logdir == str(tmp_path)
    assert kw["profiler_options"].python_tracer_level == 0
