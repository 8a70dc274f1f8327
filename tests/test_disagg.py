"""Disaggregated prefill/decode serving: KV wire-codec round trips for
every cache layout, landing exactness, in-process two-tier e2e
(greedy AND sampled token identity vs the colocated engine, trace
causality, metrics-plane visibility), decode-replica failover with
zero duplicated/dropped tokens, retrace pins for the shipping/landing
programs, and the deterministic bench-arm pins.

The two-REAL-process token-identity acceptance pin lives at the
bottom (fixture pair: tests/fixtures/disagg_{prefill,decode}_fixture).

Compile frugality: one tiny f32 config for everything except the
per-layout codec cases (which are single prefills, not serve loops).
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import transformer as T
from tony_tpu.models.decode import (extract_kv_rows, generate,
                                    init_kv_cache, kv_from_wire)
from tony_tpu.models.serve import (ContinuousBatcher,
                                   SpeculativeContinuousBatcher,
                                   land_kv_rows, prefill_ship_row,
                                   prefill_ship_rows)
from tony_tpu.runtime import metrics as M
from tony_tpu.runtime import tracing
from tony_tpu.serving import kvship
from tony_tpu.serving import protocol as P
from tony_tpu.serving.client import StreamingClient
from tony_tpu.serving.disagg import DecodeServer, PrefillServer
from tony_tpu.serving.router import ServingRouter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)          # for `import bench` (repo-root script)
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")

CFG = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def _reference(params, prompt, max_new):
    out = generate(params, jnp.asarray(prompt, jnp.int32)[None], CFG,
                   max_new_tokens=max_new, rng=jax.random.PRNGKey(0),
                   temperature=0.0)
    return [int(t) for t in np.asarray(out.tokens[0, len(prompt):])]


def _prompts(seed, sizes, vocab=None):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, vocab or CFG.vocab_size,
                                         size=n)]
            for n in sizes]


class _Stack:
    """One in-process disaggregated deployment: prefill + decode +
    router, with per-tier registries, torn down in reverse order."""

    def __init__(self, params, cfg, *, slots=2, max_len=48, chunk=3,
                 seed=0, temperature=0.0, top_k=0, top_p=0.0,
                 decode_batchers=None, max_batch=2,
                 prefill_cls=PrefillServer, **prefill_kw):
        self.regp, self.regd, self.regr = (M.MetricsRegistry(),
                                           M.MetricsRegistry(),
                                           M.MetricsRegistry())
        self.prefill = prefill_cls(params, cfg, max_len=max_len,
                                   max_batch=max_batch, seed=seed,
                                   registry=self.regp, **prefill_kw)
        if decode_batchers is None:
            decode_batchers = [ContinuousBatcher(
                params, cfg, batch=slots, max_len=max_len, chunk=chunk,
                seed=seed, temperature=temperature, top_k=top_k,
                top_p=top_p)]
        self.decodes = [DecodeServer(b, registry=self.regd)
                        for b in decode_batchers]
        self.router = ServingRouter(
            [f"127.0.0.1:{self.prefill.start()}"],
            decode_replicas=[f"127.0.0.1:{d.start()}"
                             for d in self.decodes],
            health_interval_s=0.2, registry=self.regr)
        self.port = self.router.start()

    def close(self):
        self.router.stop()
        self.prefill.stop()
        for d in self.decodes:
            d.stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# KV wire codec: every cache layout round-trips through a real socket
# pair and place_rows-lands bit-identical
# ---------------------------------------------------------------------------
class TestKVWireCodec:
    LAYOUTS = {
        "f32": dict(),
        "bf16": dict(dtype=jnp.bfloat16),
        "int8": dict(kv_cache_dtype="int8"),
        "window": dict(attn_window=8),
        "ring": dict(attn_window=8, kv_cache_capacity=8),
    }

    def _ship_one(self, cfg, prompt):
        """Prefill one prompt for shipment exactly as the prefill tier
        does; returns (bufs, logits [V], length, width, mini)."""
        p = T.init_params(jax.random.PRNGKey(0), cfg)
        if cfg.kv_cache_capacity:
            lg, mini = prefill_ship_row(
                p, jnp.asarray(prompt, jnp.int32)[None], cfg)
            width = mini["k"].shape[2]
        else:
            toks = np.zeros((2, 16), np.int64)
            toks[0, :len(prompt)] = prompt
            lg, mini = prefill_ship_rows(
                p, jnp.asarray(toks, jnp.int32),
                jnp.asarray([len(prompt), 1], np.int32), cfg)
            width = len(prompt)
        bufs = extract_kv_rows(mini, [width], cfg)[0]
        # the wire form is 5-D whatever the cache stores
        assert bufs["k"].shape[3:] == (cfg.kv_heads, cfg.head_dim)
        return bufs, np.asarray(lg)[0], len(prompt), width, mini

    @pytest.mark.parametrize("layout", sorted(LAYOUTS))
    def test_socket_round_trip_lands_bit_identical(self, layout):
        """serialize -> ship through a REAL socket pair -> land into a
        fresh cache: the landed rows, frontier, logits, and rng key are
        bit-identical to the prefill-side originals, for every cache
        layout (bf16, int8+scales, sliding-window, ring)."""
        cfg = CFG.scaled(**self.LAYOUTS[layout])
        prompt = [3, 1, 4, 1, 5]
        bufs, lg, length, width, _ = self._ship_one(cfg, prompt)
        key = np.asarray(jax.random.fold_in(jax.random.PRNGKey(7), 3),
                         np.uint32)
        meta = kvship.pack_kv_meta(9, 4, length, key, rng_off=0)
        blob = kvship.pack_shipment(meta, dict(bufs, logits=lg))

        a, b = socket.socketpair()
        try:
            import threading
            got = {}
            t = threading.Thread(
                target=lambda: got.update(frame=P.recv_frame(
                    b, max_bytes=1 << 31)))
            t.start()                 # blob can exceed the socket buffer
            P.send_frame(a, P.TOKENS, 1, memoryview(blob))
            t.join(timeout=30)
            payload = got["frame"][2]
        finally:
            a.close()
            b.close()
        assert payload == blob

        meta2, bufs2 = kvship.unpack_shipment(payload)
        meta2 = kvship.parse_kv_meta(meta2)
        lg2 = bufs2.pop("logits")
        assert (meta2["rng"] == key).all() and meta2["length"] == length
        assert lg2.dtype == lg.dtype and (lg2 == lg).all()
        for n in bufs:
            assert bufs2[n].dtype == np.asarray(bufs[n]).dtype, n
            assert (bufs2[n] == np.asarray(bufs[n])).all(), n

        # place_rows-land into slot 1 of a fresh 3-slot cache
        batch, slot = 3, 1
        cache = init_kv_cache(cfg, batch, 32)
        cache = dict(cache, length=jnp.zeros((batch,), jnp.int32))
        logits = jnp.zeros((batch, cfg.vocab_size),
                           cfg.logits_storage_dtype)
        keys = jnp.zeros((batch, 2), jnp.uint32)
        rows = np.asarray([slot, batch, batch + 1], np.int32)
        s_b = bufs2["k"].shape[2]
        stored2 = kv_from_wire(bufs2)       # the host reshape at landing
        mini = {n: np.zeros((a2.shape[0], batch, s_b) + a2.shape[3:],
                            a2.dtype) for n, a2 in stored2.items()}
        for n, a2 in stored2.items():
            mini[n][:, 0:1] = a2
        lens = np.asarray([length, 0, 0], np.int32)
        lgs = np.zeros((batch, cfg.vocab_size), lg2.dtype)
        lgs[0] = lg2
        kmat = np.zeros((batch, 2), np.uint32)
        kmat[0] = meta2["rng"]
        cache, logits, keys = land_kv_rows(
            cache, logits, jnp.asarray(rows),
            {n: jnp.asarray(a2) for n, a2 in mini.items()},
            jnp.asarray(lens), jnp.asarray(lgs), keys,
            jnp.asarray(kmat))
        assert int(cache["length"][slot]) == length
        for n, a2 in stored2.items():
            landed = np.asarray(cache[n][:, slot:slot + 1, :s_b])
            assert (landed == a2).all(), n
        assert (np.asarray(logits[slot]) == lg2).all()
        assert (np.asarray(keys[slot]) == meta2["rng"]).all()

    def test_int8_ships_quantized_half_the_bytes(self):
        """The int8 cache's shipment carries int8 values + f32 scales —
        NOT a dequantized bf16/f32 blow-up: k/v payload bytes are half
        the f32 layout's for the same prompt."""
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        q_bufs, _, _, _, _ = self._ship_one(
            CFG.scaled(kv_cache_dtype="int8"), prompt)
        f_bufs, _, _, _, _ = self._ship_one(CFG, prompt)
        assert q_bufs["k"].dtype == np.int8
        assert q_bufs["k_scale"].dtype == np.float32
        assert q_bufs["k"].nbytes * 4 == f_bufs["k"].nbytes
        kv_q = q_bufs["k"].nbytes + q_bufs["v"].nbytes
        kv_f = f_bufs["k"].nbytes + f_bufs["v"].nbytes
        scales = q_bufs["k_scale"].nbytes + q_bufs["v_scale"].nbytes
        assert kv_q + scales < 0.6 * kv_f, (kv_q, scales, kv_f)

    def test_linear_caches_ship_true_length_only(self):
        """A 5-token prompt in a 16 bucket ships 5 positions, not 16 —
        the unreachable padding tail stays home."""
        bufs, _, _, width, mini = self._ship_one(CFG, [3, 1, 4, 1, 5])
        assert width == 5 and bufs["k"].shape[2] == 5
        assert mini["k"].shape[2] == 16          # the compute ran padded

    def test_malformed_shipments_are_protocol_errors(self):
        with pytest.raises(P.ProtocolError, match="header"):
            kvship.unpack_shipment(b"\x01")
        with pytest.raises(P.ProtocolError, match="implausible"):
            kvship.unpack_shipment(b"\xff\xff\xff\xff" + b"x" * 32)
        blob = kvship.pack_shipment({"rid": 1}, {"k": np.zeros((2, 2))})
        with pytest.raises(P.ProtocolError, match="truncated"):
            kvship.unpack_shipment(blob[:-8])
        with pytest.raises(P.ProtocolError, match="trailing"):
            kvship.unpack_shipment(blob + b"xx")
        with pytest.raises(P.ProtocolError, match="rng"):
            kvship.parse_kv_meta({"rid": 1, "budget": 2, "length": 3,
                                  "rng": [1]})
        import struct
        head = json.dumps({"v": 1, "meta": {}, "bufs": [
            {"name": "k", "dtype": "nope", "shape": [1]}]}).encode()
        with pytest.raises(P.ProtocolError, match="dtype"):
            kvship.unpack_shipment(struct.pack("<I", len(head)) + head
                                   + b"\x00" * 8)
        # adversarial shape whose element count overflows int64 (and
        # would wrap a numpy-based product to 0, sneaking past the
        # bounds check into a reshape crash): caught as truncated
        head = json.dumps({"v": 1, "meta": {"rid": 1}, "bufs": [
            {"name": "k", "dtype": "float32",
             "shape": [1 << 32, 1 << 32]}]}).encode()
        with pytest.raises(P.ProtocolError, match="truncated"):
            kvship.unpack_shipment(struct.pack("<I", len(head)) + head)

    def test_malformed_decode_targets_rejected(self):
        """A decode target the channel sender could not dial (missing
        host, non-numeric or out-of-range port) must be rejected at
        parse time — downstream it would detonate on the prefill tier's
        worker thread."""
        ok = {"decode": "10.0.0.1:7072"}
        assert P.parse_decode_target(ok) == "10.0.0.1:7072"
        for bad in ("host:abc", "host:", ":7072", "nohost", "h:0",
                    "h:70000", "h:7.2", 7072, "", None):
            assert P.parse_decode_target({"decode": bad}) is None, bad


# ---------------------------------------------------------------------------
# In-process two-tier e2e: token identity, trace, metrics, exclusions
# ---------------------------------------------------------------------------
class TestDisaggE2E:
    def test_greedy_token_identity_and_metrics(self, params):
        prompts = _prompts(0, (5, 3, 7, 4))
        ref = ContinuousBatcher(params, CFG, batch=2, max_len=48,
                                chunk=3).serve(prompts, 6)
        with _Stack(params, CFG) as st:
            with StreamingClient("127.0.0.1", st.port) as c:
                rids = [c.submit(p, 6) for p in prompts]
                outs = [c.result(r, timeout=120) for r in rids]
            for i, (toks, reason) in enumerate(outs):
                assert toks == ref[i], i
                assert reason == "budget"
            # the handoff wall is on the metrics plane, both sides
            assert st.regp.histogram("tony_kv_ship_seconds").count == 4
            assert st.regp.counter("tony_kv_ship_bytes_total").value > 0
            assert st.regd.histogram("tony_kv_land_seconds").count == 4
            assert st.regr.counter(
                "tony_router_handoffs_total").value == 4
            assert st.regd.gauge("tony_decode_idle_slots").value == 2
            assert st.regp.gauge("tony_prefill_queue_depth").value == 0

    def test_sampled_token_identity(self, params):
        """Per-request rng stream state rides the shipment: sampled
        disaggregated output == the colocated engine's, bit-for-bit."""
        prompts = _prompts(1, (5, 3, 7, 4))
        kw = dict(batch=2, max_len=48, chunk=3, temperature=0.8,
                  top_k=20, top_p=0.9, seed=7)
        ref = ContinuousBatcher(params, CFG, **kw).serve(prompts, 6)
        batcher = ContinuousBatcher(params, CFG, **kw)
        with _Stack(params, CFG, seed=7,
                    decode_batchers=[batcher]) as st:
            with StreamingClient("127.0.0.1", st.port) as c:
                rids = [c.submit(p, 6) for p in prompts]
                outs = [c.result(r, timeout=120)[0] for r in rids]
        assert outs == ref

    def test_int8_and_ring_configs_serve_identically(self):
        """The quantized and rolling cache layouts serve disaggregated
        with outputs identical to their colocated engines — int8 ships
        quantized, rings ship the whole capacity buffer."""
        for extra in (dict(kv_cache_dtype="int8"),
                      dict(attn_window=8, kv_cache_capacity=8)):
            cfg = CFG.scaled(**extra)
            p = T.init_params(jax.random.PRNGKey(0), cfg)
            prompts = _prompts(6, (5, 3))
            ref = ContinuousBatcher(p, cfg, batch=2, max_len=32,
                                    chunk=3).serve(prompts, 4)
            batcher = ContinuousBatcher(p, cfg, batch=2, max_len=32,
                                        chunk=3)
            with _Stack(p, cfg, max_len=32,
                        decode_batchers=[batcher]) as st:
                with StreamingClient("127.0.0.1", st.port) as c:
                    rids = [c.submit(pr, 4) for pr in prompts]
                    outs = [c.result(r, timeout=120)[0] for r in rids]
            assert outs == ref, extra

    def test_kv_ship_span_joins_the_request_trace(self, params):
        """The TTFT decomposition stays causal across the gangs:
        client.request roots the trace; the prefill tier's
        engine.request (role=prefill) parents kv.ship; the decode
        tier's engine.request (prefilled=true) parents under THAT —
        one trace id end to end."""
        tr = tracing.Tracer(proc="test:disagg", sample_rate=1.0,
                            ring_size=512)
        saved = tracing.set_tracer(tr)
        try:
            with _Stack(params, CFG) as st:
                with StreamingClient("127.0.0.1", st.port) as c:
                    rid = c.submit(_prompts(2, (5,))[0], 4)
                    c.result(rid, timeout=120)
        finally:
            tracing.set_tracer(saved)
        spans = {s["sid"]: s for s in tr._ring}
        roots = [s for s in spans.values() if s["n"] == "client.request"]
        assert roots, sorted({s["n"] for s in spans.values()})
        tid = roots[0]["tid"]
        trace = [s for s in spans.values() if s["tid"] == tid]
        names = {s["n"] for s in trace}
        assert {"client.request", "router.place", "engine.request",
                "kv.ship", "engine.first_token"} <= names, names
        ship = next(s for s in trace if s["n"] == "kv.ship")
        pre_req = spans[ship["pid"]]
        assert pre_req["n"] == "engine.request"
        assert pre_req["a"].get("role") == "prefill"
        dec_reqs = [s for s in trace if s["n"] == "engine.request"
                    and s["a"].get("prefilled")]
        assert dec_reqs, names
        # the decode tier's leg parents under the prefill tier's
        # engine.request (whose context rode the shipment)
        assert dec_reqs[0]["pid"] == pre_req["sid"]

    def test_speculative_and_prefix_are_explicitly_excluded(self, params):
        spec = SpeculativeContinuousBatcher(
            params, CFG, T.init_params(jax.random.PRNGKey(1), CFG), CFG,
            batch=2, max_len=32)
        with pytest.raises(ValueError, match="draft-model cache"):
            DecodeServer(spec)
        pref = ContinuousBatcher(params, CFG, batch=2, max_len=32,
                                 shared_prefix=[1, 2])
        with pytest.raises(ValueError, match="colocated"):
            DecodeServer(pref)

    def test_decode_tier_refuses_prompts(self, params):
        dec = DecodeServer(ContinuousBatcher(params, CFG, batch=1,
                                             max_len=32),
                           registry=M.MetricsRegistry())
        port = dec.start()
        try:
            with StreamingClient("127.0.0.1", port) as c:
                assert c.hello["role"] == "decode"
                assert c.hello["channel_port"] == dec.hub.port
                rid = c.submit([1, 2, 3], 4)
                ev = c.next_event(rid, timeout=30)
                assert ev[0] == "error" and "prefill tier" in ev[1]
        finally:
            dec.stop()

    def test_router_rejects_role_mismatch(self, params):
        """Wiring a colocated engine where the disaggregated router
        expects a prefill tier fails loudly at start, not with silent
        mis-serving."""
        from tony_tpu.serving.server import ServingServer
        srv = ServingServer(ContinuousBatcher(params, CFG, batch=1,
                                              max_len=32),
                            registry=M.MetricsRegistry())
        port = srv.start()
        dec = DecodeServer(ContinuousBatcher(params, CFG, batch=1,
                                             max_len=32),
                           registry=M.MetricsRegistry())
        dport = dec.start()
        router = ServingRouter([f"127.0.0.1:{port}"],
                               decode_replicas=[f"127.0.0.1:{dport}"],
                               registry=M.MetricsRegistry())
        try:
            with pytest.raises(ConnectionError, match="role"):
                router.start()
        finally:
            router.stop()
            srv.stop()
            dec.stop()

    def test_land_and_ship_programs_compile_once_per_bucket(
            self, params, retrace_guard):
        """The decode tier's landing and the prefill tier's shipping
        run ONE compiled program per admission bucket — mixed prompt
        lengths inside a bucket share it (the bucketed-admission
        invariant, extended across the gang split)."""
        prompts = _prompts(3, (3, 5, 8, 10, 4, 6))
        ref = [
            _reference(params, p, 4) for p in prompts]
        with _Stack(params, CFG) as st:
            with StreamingClient("127.0.0.1", st.port) as c:
                rids = [c.submit(p, 4) for p in prompts]
                outs = [c.result(r, timeout=120)[0] for r in rids]
        assert outs == ref
        retrace_guard.assert_max("prefill_ship_rows", 1)
        retrace_guard.assert_max("land_kv_rows", 1)


# ---------------------------------------------------------------------------
# Failover: kill the decode replica mid-stream
# ---------------------------------------------------------------------------
class TestDisaggFailover:
    def test_decode_loss_no_dup_no_drop(self, params):
        """THE disaggregated failover pin: kill a decode replica
        mid-stream; every stream it carried completes with exactly the
        solo-reference token sequence — re-prefilled through the
        (surviving) prefill tier onto the surviving decode replica,
        streamed prefix folded into the prompt."""
        class SlowFetch(ContinuousBatcher):
            def _fetch(self, handle):
                time.sleep(0.05)          # keep streams mid-flight
                return super()._fetch(handle)

        batchers = [SlowFetch(params, CFG, batch=2, max_len=64, chunk=2)
                    for _ in range(2)]
        prompts = _prompts(4, (5, 5, 5, 5))
        budget = 24
        with _Stack(params, CFG, max_len=64,
                    decode_batchers=batchers) as st:
            with StreamingClient("127.0.0.1", st.port) as c:
                rids = [c.submit(p, budget) for p in prompts]
                got = {r: [] for r in rids}
                started = set()
                deadline = time.time() + 90
                while len(started) < len(rids) and time.time() < deadline:
                    for r in rids:
                        if r in started:
                            continue
                        try:
                            ev = c.next_event(r, timeout=0.05)
                        except Exception:
                            continue
                        assert ev[0] == "tokens", ev
                        got[r].extend(ev[1])
                        started.add(r)
                assert len(started) == len(rids), "streams never started"
                # both decode replicas carry streams (assignment
                # tiebreak spreads the pair placements)
                actives = [d.engine.stats()["active"]
                           for d in st.decodes]
                assert all(a > 0 for a in actives), actives
                st.decodes[0].kill()      # decode replica loss
                for i, r in enumerate(rids):
                    while True:
                        ev = c.next_event(r, timeout=90)
                        if ev[0] == "tokens":
                            got[r].extend(ev[1])
                        elif ev[0] == "retired":
                            break
                        else:
                            raise AssertionError(ev)
                for i, r in enumerate(rids):
                    assert got[r] == _reference(params, prompts[i],
                                                budget), i
            assert st.regr.counter(
                "tony_router_failovers_total").value >= 1
            assert st.regr.counter(
                "tony_router_handoffs_total").value >= len(rids)

    def test_kv_ship_failure_fails_over_not_errors(self, params):
        """A decode gang's CHANNEL endpoint dies before the router's
        reader notices the replica itself (its TONYS1 link stays up):
        the prefill tier's ship fails, marks the failure RETRYABLE, and
        the router re-places the session toward the surviving decode
        replica — the client sees its tokens, never the transport
        fault."""
        batchers = [ContinuousBatcher(params, CFG, batch=2, max_len=48,
                                      chunk=3) for _ in range(2)]
        with _Stack(params, CFG, decode_batchers=batchers,
                    ship_timeout_s=1.0) as st:
            # channel endpoint only — the serving link stays healthy,
            # so placement still points at this gang
            st.decodes[0].hub.stop()
            p = _prompts(11, (5,))[0]
            with StreamingClient("127.0.0.1", st.port) as c:
                toks, reason = c.result(c.submit(p, 6), timeout=60)
            assert toks == _reference(params, p, 6)
            assert reason == "budget"
            assert st.regr.counter(
                "tony_router_failovers_total").value >= 1
            # the failover also tombstoned the old rrid on the decode
            # gang the shipment could not (verifiably) reach: "ship
            # failed" may be a delivered frame whose ack timed out, and
            # without the tombstone a late adoption would burn a decode
            # slot streaming into a stale rrid
            deadline = time.time() + 15
            while (not st.decodes[0]._tombstones
                   and time.time() < deadline):
                time.sleep(0.01)
            assert st.decodes[0]._tombstones


# ---------------------------------------------------------------------------
# Cancel across the split: wherever the CANCEL catches a request —
# queued at the prefill tier, mid-wave, or racing its KV package to the
# decode tier — the client gets EXACTLY one terminal frame and the
# router forgets the session
# ---------------------------------------------------------------------------
class _GatedPrefill(PrefillServer):
    """Prefill tier whose waves block on a gate: pins requests in the
    'queued' and 'mid-wave' states long enough to cancel them there."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.gate = threading.Event()

    def _prefill_group(self, grp, bucket, entry=None):
        self.gate.wait(timeout=60)
        super()._prefill_group(grp, bucket, entry)


class _BoomWavePrefill(PrefillServer):
    """Prefill tier whose FIRST wave dies with an unexpected error,
    paused mid-wave long enough (``in_wave``/``resume``) for the test
    to cancel one of its items there; later waves serve normally.
    ``take_gate`` holds the worker back so both prompts land in ONE
    wave."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.take_gate = threading.Event()
        self.in_wave = threading.Event()
        self.resume = threading.Event()
        self._boomed = False

    def _take_wave(self):
        self.take_gate.wait(timeout=60)
        return super()._take_wave()

    def _prefill_group(self, grp, bucket, entry=None):
        if not self._boomed:
            self._boomed = True
            self.in_wave.set()
            self.resume.wait(timeout=60)
            raise RuntimeError("injected wave failure")
        super()._prefill_group(grp, bucket, entry)


def _package_blob(params, cfg, rid, budget, prompt=(3, 1, 4, 1, 5),
                  logits_len=None):
    """A valid KV shipment blob for ``prompt``, built exactly as the
    prefill tier builds one (padded prefill, true-length extract).
    ``logits_len`` substitutes a wrong-vocab logits vector (the
    mismatched-gang-config case)."""
    prompt = list(prompt)
    toks = np.zeros((2, 16), np.int64)
    toks[0, :len(prompt)] = prompt
    lg, mini = prefill_ship_rows(
        params, jnp.asarray(toks, jnp.int32),
        jnp.asarray([len(prompt), 1], np.int32), cfg)
    bufs = extract_kv_rows(mini, [len(prompt)], cfg)[0]
    key = np.asarray(jax.random.fold_in(jax.random.PRNGKey(0), 0),
                     np.uint32)
    meta = kvship.pack_kv_meta(rid, budget, len(prompt), key, rng_off=0)
    logits = (np.zeros((logits_len,), np.float32)
              if logits_len is not None else np.asarray(lg)[0])
    return kvship.pack_shipment(meta, dict(bufs, logits=logits))


class TestDisaggDrain:
    """Planned decode-replica drain: the live-operability twin of the
    failover pin. The router fences the replica, re-prefills each of
    its sessions through the prefill tier onto the survivor (streamed
    prefix folded in, rng stream + offset pinned), and the old
    placement streams until the new one ACKs — zero duplicated or
    dropped tokens, greedy and sampled."""

    def _run(self, params, *, seed=0, temperature=0.0, top_k=0,
             top_p=0.0, ref=None):
        class SlowFetch(ContinuousBatcher):
            def _fetch(self, handle):
                time.sleep(0.05)          # keep streams mid-flight
                return super()._fetch(handle)

        # batch=4: the surviving decode replica has idle slots, so
        # migrations ACK while the old placement still streams
        kw = dict(batch=4, max_len=64, chunk=2, seed=seed,
                  temperature=temperature, top_k=top_k, top_p=top_p)
        batchers = [SlowFetch(params, CFG, **kw) for _ in range(2)]
        prompts = _prompts(44, (5, 5, 4, 6))
        budget = 20
        if ref is None:
            ref = [_reference(params, p, budget) for p in prompts]
        else:
            ref = ref(kw, prompts, budget)
        with _Stack(params, CFG, max_len=64, seed=seed,
                    decode_batchers=batchers) as st:
            with StreamingClient("127.0.0.1", st.port) as c:
                rids = [c.submit(p, budget) for p in prompts]
                got = {r: [] for r in rids}
                started = set()
                deadline = time.time() + 90
                while len(started) < len(rids) and time.time() < deadline:
                    for r in rids:
                        if r in started:
                            continue
                        try:
                            ev = c.next_event(r, timeout=0.05)
                        except Exception:
                            continue
                        assert ev[0] == "tokens", ev
                        got[r].extend(ev[1])
                        started.add(r)
                assert len(started) == len(rids), "streams never started"
                reps = st.router.stats()["replicas"]
                decode = {a: v for a, v in reps.items()
                          if v["role"] == "decode"}
                assert all(v["assigned"] > 0 for v in decode.values())
                victim = max(decode, key=lambda a: decode[a]["assigned"])
                res = c.drain_replica(victim)
                assert res.get("drained"), res
                assert res["migrated"] >= 1, res
                for r in rids:
                    while True:
                        ev = c.next_event(r, timeout=90)
                        if ev[0] == "tokens":
                            got[r].extend(ev[1])
                        elif ev[0] == "retired":
                            break
                        else:
                            raise AssertionError(ev)
                for i, r in enumerate(rids):
                    assert got[r] == ref[i], \
                        f"stream {i}: dup/drop across decode drain"
                post = st.router.stats()["replicas"]
                assert post[victim]["draining"]
                assert post[victim]["assigned"] == 0
            # planned migration, not crash failover
            assert st.regr.counter(
                "tony_router_failovers_total").value == 0
            assert st.regr.counter(
                "tony_router_drains_total").value == 1

    def test_decode_drain_zero_dup_drop_greedy(self, params):
        self._run(params)

    def test_decode_drain_zero_dup_drop_sampled(self, params):
        self._run(params, seed=7, temperature=0.8, top_k=20, top_p=0.9,
                  ref=lambda kw, prompts, budget: ContinuousBatcher(
                      params, CFG, **kw).serve(prompts, budget))


class TestDisaggCancel:
    def test_cancel_queued_and_mid_wave_both_retire(self, params):
        """Cancel a prompt still QUEUED at the prefill tier and one
        already MID-WAVE: the queued one retires from the prefill
        tier's queue; the mid-wave one finishes its (sunk) prefill but
        must NOT ship — the shipper retires it. Both cancels end in a
        client-visible RETIRED and the router drops the sessions."""
        with _Stack(params, CFG, max_batch=1,
                    prefill_cls=_GatedPrefill) as st:
            with StreamingClient("127.0.0.1", st.port) as c:
                ra = c.submit(_prompts(7, (5,))[0], 6)
                deadline = time.time() + 30
                while (st.prefill.stats()["active"] != 1
                       and time.time() < deadline):
                    time.sleep(0.01)
                assert st.prefill.stats()["active"] == 1   # A mid-wave
                rb = c.submit(_prompts(8, (4,))[0], 6)
                while (st.prefill.stats()["queue_depth"] != 1
                       and time.time() < deadline):
                    time.sleep(0.01)
                c.cancel(rb)                # still queued at prefill
                toks, reason = c.result(rb, timeout=30)
                assert reason == "cancelled" and toks == []
                c.cancel(ra)                # mid-wave
                # the CANCEL must land tier-side before the gate opens,
                # or this degenerates into the (also covered) tombstone
                # race instead of the mid-wave pin
                while st.prefill._items and time.time() < deadline:
                    time.sleep(0.01)
                st.prefill.gate.set()
                toks, reason = c.result(ra, timeout=30)
                assert reason == "cancelled" and toks == []
                assert st.regp.counter(
                    "tony_prefill_requests_total").value == 0  # no ship
                # the stack still serves: a fresh request completes
                p = _prompts(9, (5,))[0]
                toks, reason = c.result(c.submit(p, 4), timeout=60)
                assert toks == _reference(params, p, 4)
                assert reason == "budget"
            assert not st.router._sessions and not st.router._by_rrid

    def test_wave_failure_settles_midwave_cancelled_item(self, params):
        """An unexpected wave failure must settle EVERY item of the
        wave with exactly one terminal frame — including one a
        mid-wave CANCEL already popped from the item table (its
        RETIRED was deferred to the shipper, which never ran): the
        survivor fails with the wave's ERROR, the cancelled one
        retires as cancelled, and the worker thread survives to serve
        the next admission."""
        from tony_tpu.serving.client import ServingConnectionError

        with _Stack(params, CFG, max_batch=2,
                    prefill_cls=_BoomWavePrefill) as st:
            with StreamingClient("127.0.0.1", st.port) as c:
                pa, pb = _prompts(11, (5, 5))
                ra = c.submit(pa, 4)
                rb = c.submit(pb, 4)
                deadline = time.time() + 30
                while (st.prefill.stats()["queue_depth"] != 2
                       and time.time() < deadline):
                    time.sleep(0.01)
                assert st.prefill.stats()["queue_depth"] == 2
                st.prefill.take_gate.set()         # wave [A, B] starts
                assert st.prefill.in_wave.wait(timeout=30)
                c.cancel(rb)                       # mid-wave: RETIRED
                #                                  # deferred to shipper
                while (len(st.prefill._items) > 1
                       and time.time() < deadline):
                    time.sleep(0.01)
                assert len(st.prefill._items) == 1  # B popped, A still in
                st.prefill.resume.set()            # the wave dies
                toks, reason = c.result(rb, timeout=30)
                assert reason == "cancelled" and toks == []
                with pytest.raises(ServingConnectionError):
                    c.result(ra, timeout=30)
                # the worker survived: a fresh request serves
                p = _prompts(12, (5,))[0]
                toks, reason = c.result(c.submit(p, 4), timeout=60)
                assert toks == _reference(params, p, 4)
                assert reason == "budget"
            assert not st.router._sessions and not st.router._by_rrid

    def test_tombstone_drop_and_bad_shipment_cost_only_themselves(
            self, params):
        """Decode-tier landing contract, pinned over a raw sink link:
        (1) a package whose rid was cancelled before arrival is dropped
        but still pushes the terminal RETIRED (the engine never saw the
        rid — nobody else will ever speak for it); (2) a malformed
        shipment is dropped without killing the landing thread; (3) a
        healthy package then lands and streams normally."""
        from tony_tpu.channels.channel import ChannelSender

        dec = DecodeServer(ContinuousBatcher(params, CFG, batch=1,
                                             max_len=32, chunk=2),
                           registry=M.MetricsRegistry())
        port = dec.start()
        sender = sock = None
        try:
            sock = socket.create_connection(("127.0.0.1", port),
                                            timeout=10)
            sock.sendall(P.MAGIC)
            assert P.recv_frame(sock)[0] == P.HELLO
            P.send_frame(sock, P.BIND, 0)      # we are the delta sink
            P.send_frame(sock, P.CANCEL, 7)    # tombstone rid 7
            deadline = time.time() + 15
            while 7 not in dec._tombstones and time.time() < deadline:
                time.sleep(0.01)
            assert 7 in dec._tombstones
            sender = ChannelSender(f"127.0.0.1:{dec.hub.port}", "kvship",
                                   registry=M.MetricsRegistry())
            sender.send_bytes(_package_blob(params, CFG, rid=7, budget=4),
                              sync=True, timeout=30)
            fr = P.recv_frame(sock)
            assert fr[0] == P.RETIRED and fr[1] == 7, fr
            assert P.unpack_json(fr[2])["reason"] == "cancelled"
            # a malformed shipment (overflowing declared shape) between
            # two good ones: dropped, lander survives
            head = json.dumps({"v": 1, "meta": {"rid": 9}, "bufs": [
                {"name": "k", "dtype": "float32",
                 "shape": [1 << 32, 1 << 32]}]}).encode("utf-8")
            import struct
            sender.send_bytes(struct.pack("<I", len(head)) + head,
                              sync=True, timeout=30)
            # a vocab-mismatched logits vector (prefill/decode gangs on
            # different configs): request-scoped ERROR, engine intact
            sender.send_bytes(_package_blob(params, CFG, rid=11, budget=3,
                                            logits_len=7),
                              sync=True, timeout=30)
            fr = P.recv_frame(sock)
            assert fr[0] == P.ERROR and fr[1] == 11, fr
            assert "logits" in P.unpack_json(fr[2])["message"]
            sender.send_bytes(_package_blob(params, CFG, rid=8, budget=3),
                              sync=True, timeout=30)
            got = []
            while True:
                fr = P.recv_frame(sock)
                assert fr is not None and fr[1] == 8, fr
                if fr[0] == P.TOKENS:
                    got.extend(P.unpack_tokens(fr[2]))
                elif fr[0] == P.RETIRED:
                    assert P.unpack_json(fr[2])["reason"] == "budget"
                    break
            assert len(got) == 3
        finally:
            if sender is not None:
                sender.close(drain=False)
            if sock is not None:
                sock.close()
            dec.stop()

    def test_cancel_racing_the_landing_still_cancels(self, params):
        """A CANCEL that interleaves INSIDE the landing — after the
        tombstone check, before the engine registered the rid (so its
        engine.cancel no-ops) — must still win: the post-submit
        tombstone re-check cancels the freshly admitted request instead
        of letting it stream its full budget to a client that asked for
        death."""
        from tony_tpu.channels.channel import ChannelSender

        dec = DecodeServer(ContinuousBatcher(params, CFG, batch=1,
                                             max_len=48, chunk=2),
                           registry=M.MetricsRegistry())
        port = dec.start()
        real_submit = dec.engine.submit_prefilled

        def racing_submit(rid, pkg, budget, trace_ctx=None, **kw):
            real_submit(rid, pkg, budget, trace_ctx=trace_ctx, **kw)
            # the CANCEL handler runs here "mid-submit": tombstone set,
            # its engine.cancel no-oped (rid not yet visible to it)
            with dec._lock:
                dec._tombstones[rid] = True

        dec.engine.submit_prefilled = racing_submit
        sender = sock = None
        try:
            sock = socket.create_connection(("127.0.0.1", port),
                                            timeout=10)
            sock.sendall(P.MAGIC)
            assert P.recv_frame(sock)[0] == P.HELLO
            P.send_frame(sock, P.BIND, 0)
            sender = ChannelSender(f"127.0.0.1:{dec.hub.port}", "kvship",
                                   registry=M.MetricsRegistry())
            sender.send_bytes(_package_blob(params, CFG, rid=5,
                                            budget=30),
                              sync=True, timeout=30)
            while True:
                fr = P.recv_frame(sock)
                assert fr is not None and fr[1] == 5, fr
                if fr[0] == P.RETIRED:
                    assert P.unpack_json(fr[2])["reason"] == "cancelled"
                    break
                assert fr[0] == P.TOKENS     # a first chunk may slip
            assert not dec._tombstones       # consumed, not leaked
        finally:
            if sender is not None:
                sender.close(drain=False)
            if sock is not None:
                sock.close()
            dec.stop()

    def test_sink_loss_frees_every_adopted_slot(self, params):
        """Losing the delta sink — whichever side notices first, a
        failed push or the reader's EOF — cancels every live adopted
        request so its slot frees for the router's re-placements,
        instead of generating into the void until budget exhausts."""
        from tony_tpu.channels.channel import ChannelSender

        dec = DecodeServer(ContinuousBatcher(params, CFG, batch=2,
                                             max_len=64, chunk=2),
                           registry=M.MetricsRegistry())
        port = dec.start()
        sender = None
        sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        try:
            sock.sendall(P.MAGIC)
            assert P.recv_frame(sock)[0] == P.HELLO
            P.send_frame(sock, P.BIND, 0)
            sender = ChannelSender(f"127.0.0.1:{dec.hub.port}", "kvship",
                                   registry=M.MetricsRegistry())
            sender.send_bytes(_package_blob(params, CFG, rid=3,
                                            budget=50),
                              sync=True, timeout=30)
            deadline = time.time() + 60
            while (dec.engine.stats()["active"] != 1
                   and time.time() < deadline):
                time.sleep(0.02)
            assert dec.engine.stats()["active"] == 1
            sock.close()                     # the sink dies mid-stream
            while (dec.engine.stats()["active"] != 0
                   and time.time() < deadline):
                time.sleep(0.02)
            assert dec.engine.stats()["active"] == 0
        finally:
            if sender is not None:
                sender.close(drain=False)
            dec.stop()


# ---------------------------------------------------------------------------
# Bench-arm pins (deterministic tier-1; latency-realistic @slow)
# ---------------------------------------------------------------------------
class TestDisaggBenchArm:
    def test_itl_p99_and_handoff_wall_pins(self):
        """The tentpole acceptance, deterministically: with equal
        injected prefill/decode floors on both topologies, decode ITL
        p99 under concurrent admissions is >= 2x better disaggregated
        than colocated at equal slot count, the outputs are
        token-identical (asserted inside the arm), and the KV handoff
        wall is visible on the metrics plane."""
        import bench

        res = bench._disagg_arm()
        assert res["serving_disagg_itl_p99_vs_colocated"] >= 2.0, res
        assert res["serving_disagg_handoff_wall_s"] > 0, res
        assert res["serving_disagg_handoffs"] >= 9, res
        # colocated p99 actually saw the admission stall (>= the decode
        # floor + a meaningful share of the prefill floor)
        assert res["serving_colocated_itl_p99_s"] >= \
            res["serving_disagg_fetch_floor_s"] \
            + 0.2 * res["serving_disagg_prefill_floor_s"], res


@pytest.mark.slow
class TestDisaggBenchRealistic:
    def test_itl_contrast_survives_wan_latency(self):
        """Latency-realistic variant: the client path rides a
        LatencyProxy WAN hop. ITL is push-cadence, not round-trip-bound
        — the p99 contrast must hold unchanged."""
        import bench

        res = bench._disagg_arm(one_way_s=0.02)
        assert res["serving_disagg_itl_p99_vs_colocated"] >= 2.0, res


# ---------------------------------------------------------------------------
# Two REAL processes: the end-to-end token-identity acceptance pin
# ---------------------------------------------------------------------------
@pytest.mark.e2e
def test_token_identity_across_two_real_processes(tmp_path, params):
    """Greedy AND sampled disaggregated serving, with the prefill tier
    and the decode tier in two separate real processes (the driver
    holds only the routers and the client): outputs are token-identical
    to in-driver colocated references. Everything that could diverge —
    params init, bucket ladder, prefill program, rng stream state —
    crosses a process boundary here."""
    pre_ports = tmp_path / "prefill-ports.json"
    dec_ports = tmp_path / "decode-ports.json"
    done = tmp_path / "done"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(FIXTURES, fixture),
         "--port_file", str(port_file), "--done_file", str(done)],
        env=env, cwd=str(tmp_path))
        for fixture, port_file in
        (("disagg_prefill_fixture.py", pre_ports),
         ("disagg_decode_fixture.py", dec_ports))]
    routers = []
    try:
        deadline = time.time() + 150
        while time.time() < deadline and not (
                pre_ports.exists() and dec_ports.exists()):
            assert all(p.poll() is None for p in procs), \
                "a tier process died before binding"
            time.sleep(0.2)
        assert pre_ports.exists() and dec_ports.exists(), \
            "tier port files never appeared"
        pports = json.loads(pre_ports.read_text())
        dports = json.loads(dec_ports.read_text())

        prompts = _prompts(5, (5, 3, 7, 4))
        refs = {
            "greedy": ContinuousBatcher(
                params, CFG, batch=2, max_len=48, chunk=3,
                seed=7).serve(prompts, 6),
            "sampled": ContinuousBatcher(
                params, CFG, batch=2, max_len=48, chunk=3,
                temperature=0.8, top_k=20, top_p=0.9,
                seed=7).serve(prompts, 6),
        }
        for mode in ("greedy", "sampled"):
            router = ServingRouter(
                [f"127.0.0.1:{pports[mode]}"],
                decode_replicas=[f"127.0.0.1:{dports[mode]}"],
                registry=M.MetricsRegistry())
            routers.append(router)
            with StreamingClient("127.0.0.1", router.start()) as c:
                rids = [c.submit(p, 6) for p in prompts]
                outs = [c.result(r, timeout=150)[0] for r in rids]
            assert outs == refs[mode], mode
    finally:
        done.write_text("done")
        for router in routers:
            router.stop()
        for p in procs:
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
    assert all(p.returncode == 0 for p in procs), \
        [p.returncode for p in procs]
