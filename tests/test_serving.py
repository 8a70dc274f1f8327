"""Streaming serving data plane: open-loop engine semantics, TONYS1
protocol codec + robustness, server/client end-to-end, router
placement + failover, and the streamed-vs-request/response bench pins.

Compile frugality: everything here shares ONE tiny config and a small
set of (batch, max_len, chunk) shapes, so the module pays a handful of
compiled serving programs, not one per test.
"""

import os
import queue as queue_mod
import socket
import struct
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import transformer as T
from tony_tpu.models.decode import generate
from tony_tpu.models.serve import ContinuousBatcher, ServeEngine
from tony_tpu.runtime import metrics as M
from tony_tpu.serving import protocol as P
from tony_tpu.serving.client import ServingConnectionError, StreamingClient
from tony_tpu.serving.netem import LatencyProxy
from tony_tpu.serving.router import ServingRouter
from tony_tpu.serving.server import ServingServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)          # for `import bench` (repo-root script)

CFG = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def _reference(params, prompt, max_new):
    out = generate(params, jnp.asarray(prompt, jnp.int32)[None], CFG,
                   max_new_tokens=max_new, rng=jax.random.PRNGKey(0),
                   temperature=0.0)
    return [int(t) for t in np.asarray(out.tokens[0, len(prompt):])]


def _prompts(seed, sizes):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, CFG.vocab_size, size=n)]
            for n in sizes]


def _batcher(params, **kw):
    kw.setdefault("batch", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("chunk", 3)
    return ContinuousBatcher(params, CFG, **kw)


class _EngineHarness:
    """ServeEngine on a background thread with recorded deltas/retires.
    A request's final eos/budget delta arrives via on_retired (the
    atomic-final contract), so both callbacks feed ``got``."""

    def __init__(self, batcher, registry=None):
        self.got: dict = {}
        self.retired: dict = {}

        def on_retired(rid, reason, n, final):
            self.got.setdefault(rid, []).extend(final)
            self.retired.setdefault(rid, (reason, n))

        self.engine = ServeEngine(
            batcher,
            on_delta=lambda rid, t: self.got.setdefault(rid, []).extend(t),
            on_retired=on_retired, registry=registry)
        self.thread = threading.Thread(target=self.engine.run, daemon=True)
        self.thread.start()

    def finish(self, timeout=120):
        self.engine.drain()
        self.thread.join(timeout=timeout)
        assert not self.thread.is_alive(), "engine did not drain"


class TestProtocol:
    def test_frame_round_trip(self):
        a, b = socket.socketpair()
        try:
            P.send_frame(a, P.ADMIT, 7, P.pack_json({"x": 1}))
            P.send_frame(a, P.TOKENS, 9, P.pack_tokens([3, 1, 4, 1, 5]))
            ftype, rid, payload = P.recv_frame(b)
            assert (ftype, rid) == (P.ADMIT, 7)
            assert P.unpack_json(payload) == {"x": 1}
            ftype, rid, payload = P.recv_frame(b)
            assert (ftype, rid) == (P.TOKENS, 9)
            assert P.unpack_tokens(payload) == [3, 1, 4, 1, 5]
            a.close()
            assert P.recv_frame(b) is None      # clean EOF
        finally:
            b.close()

    def test_implausible_length_rejected(self):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack("<I", P.MAX_FRAME_BYTES + 1))
            with pytest.raises(P.ProtocolError, match="implausible"):
                P.recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_truncated_frame_rejected(self):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack("<I", 100) + b"\x01short")
            a.close()
            with pytest.raises(P.ProtocolError, match="truncated"):
                P.recv_frame(b)
        finally:
            b.close()

    def test_large_payload_zero_copy_path_round_trips(self):
        """Payloads >= LARGE_PAYLOAD_BYTES ship as header-then-payload
        writes (memoryview accepted, no concatenated copy); the wire is
        byte-identical — recv_frame sees one ordinary frame."""
        a, b = socket.socketpair()
        try:
            blob = bytes(range(256)) * (P.LARGE_PAYLOAD_BYTES // 256 + 1)
            assert len(blob) >= P.LARGE_PAYLOAD_BYTES
            got = {}
            t = threading.Thread(
                target=lambda: got.update(frame=P.recv_frame(b)))
            t.start()        # concurrent reader: blob exceeds socket buf
            P.send_frame(a, P.TOKENS, 5, memoryview(blob))
            t.join(timeout=10)
            ftype, rid, payload = got["frame"]
            assert (ftype, rid) == (P.TOKENS, 5)
            assert payload == blob
        finally:
            a.close()
            b.close()

    def test_memoryview_payload_small_frame(self):
        assert P.encode_frame(P.TOKENS, 3, memoryview(b"abc")) \
            == P.encode_frame(P.TOKENS, 3, b"abc")

    def test_non_byte_memoryview_uses_nbytes(self):
        """A float32 view's len() counts ELEMENTS; the frame length must
        be its byte size or the receiver desyncs."""
        arr = np.arange(6, dtype=np.float32).reshape(2, 3)
        frame = P.encode_frame(P.TOKENS, 1, memoryview(arr))
        assert frame == P.encode_frame(P.TOKENS, 1, arr.tobytes())
        a, b = socket.socketpair()
        try:
            P.send_frame(a, P.TOKENS, 1, memoryview(arr))
            ftype, rid, payload = P.recv_frame(b)
            assert (ftype, rid) == (P.TOKENS, 1)
            assert payload == arr.tobytes()
        finally:
            a.close()
            b.close()

    def test_frame_header_size_guard(self):
        with pytest.raises(P.ProtocolError, match="too large"):
            P.frame_header(P.TOKENS, 1, P.MAX_FRAME_BYTES)

    def test_recv_exact_short_read_contract(self):
        """recv_into rewrite keeps the contract: None on clean EOF at a
        boundary, ProtocolError on EOF mid-read."""
        a, b = socket.socketpair()
        a.close()
        assert P.recv_exact(b, 4) is None
        b.close()
        a, b = socket.socketpair()
        try:
            a.sendall(b"\x01\x02")
            a.close()
            with pytest.raises(P.ProtocolError, match="truncated"):
                P.recv_exact(b, 4)
        finally:
            b.close()

    def test_tokens_payload_must_be_u32s(self):
        with pytest.raises(P.ProtocolError, match="u32"):
            P.unpack_tokens(b"\x01\x02\x03")

    def test_parse_admit_validation(self):
        ok = P.pack_json({"prompt": [1, 2], "max_new_tokens": 4})
        assert P.parse_admit(ok) == ([1, 2], 4, True)
        for bad in ({"prompt": "nope", "max_new_tokens": 4},
                    {"prompt": [1, "x"], "max_new_tokens": 4},
                    {"prompt": [1], "max_new_tokens": "4"},
                    {"prompt": [1], "max_new_tokens": 4, "stream": 1},
                    {"prompt": [True], "max_new_tokens": 4}):
            with pytest.raises(P.ProtocolError):
                P.parse_admit(P.pack_json(bad))
        with pytest.raises(P.ProtocolError, match="JSON"):
            P.parse_admit(b"\xff{")


class TestOpenLoopEngine:
    def test_incremental_submission_matches_closed_batch(self, params):
        """Requests submitted WHILE the engine runs (some after earlier
        ones already streamed deltas) produce exactly the closed-batch
        serve() outputs — per-request streams make admission timing
        invisible."""
        prompts = _prompts(0, (5, 3, 7, 4))
        closed = _batcher(params).serve(prompts, 6)
        h = _EngineHarness(_batcher(params))
        h.engine.submit(0, prompts[0], 6)
        h.engine.submit(1, prompts[1], 6)
        # wait for a first delta before submitting the rest: the live
        # queue is genuinely live, not a pre-drained FIFO
        t0 = time.time()
        while not h.got and time.time() - t0 < 60:
            time.sleep(0.005)
        assert h.got, "no deltas streamed"
        h.engine.submit(2, prompts[2], 6)
        h.engine.submit(3, prompts[3], 6)
        h.finish()
        for i in range(4):
            assert h.got[i] == closed[i], i
            assert h.retired[i] == ("budget", 6)

    def test_deltas_stream_before_retirement(self, params):
        """A long request's tokens arrive across multiple deltas (one
        per consumed chunk), not as one lump at retirement — with the
        LAST delta riding the retirement callback (the atomic-final
        contract)."""
        prompts = _prompts(1, (4,))
        b = _batcher(params, batch=1, chunk=2)
        deltas = []
        eng = ServeEngine(
            b, on_delta=lambda rid, t: deltas.append(list(t)),
            on_retired=lambda rid, r, n, final: deltas.append(list(final)))
        eng.submit(0, prompts[0], 10)
        eng.drain()
        eng.run()
        assert len(deltas) >= 4, deltas       # 10 tokens / 2-step chunks
        assert all(d for d in deltas[:-1])    # live deltas are nonempty
        assert deltas[-1], "final delta must ride the retirement"
        assert [t for d in deltas for t in d] == _reference(
            params, prompts[0], 10)

    def test_cancel_waiting_and_inflight(self, params):
        """Cancelling a WAITING request retires it with zero tokens;
        cancelling an ADMITTED one frees its slot so queued work
        completes; double-cancel and cancel-after-retire are no-ops."""
        prompts = _prompts(2, (5, 4, 6, 3))
        h = _EngineHarness(_batcher(params, batch=1, chunk=2,
                                    max_len=64))
        h.engine.submit("run", prompts[0], 4)
        h.engine.submit("doomed", prompts[1], 59)           # long
        h.engine.submit("waiting", prompts[2], 4)
        h.engine.submit("last", prompts[3], 4)
        h.engine.cancel("waiting")                # still queued
        t0 = time.time()
        while "doomed" not in h.got and time.time() - t0 < 60:
            time.sleep(0.005)                     # admitted + streaming
        h.engine.cancel("doomed")
        h.engine.cancel("doomed")                 # idempotent
        h.finish()
        assert h.retired["waiting"] == ("cancelled", 0)
        assert h.got.get("waiting", []) == []     # zero tokens streamed
        assert h.retired["doomed"][0] == "cancelled"
        assert len(h.got["doomed"]) < 59          # stopped early
        ref = _reference(params, prompts[1], 59)
        assert h.got["doomed"] == ref[:len(h.got["doomed"])]
        assert h.got["run"] == _reference(params, prompts[0], 4)
        assert h.got["last"] == _reference(params, prompts[3], 4)
        h.engine.cancel("last")                   # after retirement: no-op
        assert h.retired["last"] == ("budget", 4)

    def test_queue_depth_gauge_exact(self, params):
        """The qdepth gauge tracks the live wait queue through submit,
        admission, and cancel."""
        reg = M.MetricsRegistry()
        b = _batcher(params, batch=1, chunk=2)
        eng = ServeEngine(b, registry=reg)
        g = reg.gauge("tony_serve_queue_depth")
        prompts = _prompts(3, (4, 4, 4))
        eng.submit(0, prompts[0], 4)
        eng.submit(1, prompts[1], 4)
        eng.submit(2, prompts[2], 4)
        assert g.value == 3                       # nothing admitted yet
        eng.cancel(1)
        assert g.value == 2
        eng.drain()
        eng.run()
        assert g.value == 0

    def test_stop_aborts_outstanding(self, params):
        prompts = _prompts(4, (4, 4))
        h = _EngineHarness(_batcher(params, batch=1, chunk=2,
                                    max_len=64))
        h.engine.submit(0, prompts[0], 40)
        h.engine.submit(1, prompts[1], 8)
        t0 = time.time()
        while 0 not in h.got and time.time() - t0 < 60:
            time.sleep(0.005)
        h.engine.stop()
        h.thread.join(timeout=60)
        assert not h.thread.is_alive()
        assert h.retired[0][0] == "stopped"
        assert h.retired[1][0] == "stopped"
        with pytest.raises(RuntimeError, match="draining"):
            h.engine.submit(2, prompts[0], 4)

    def test_failed_validation_leaves_no_phantom_queue_depth(self,
                                                             params):
        """A mid-list invalid request fails the whole serve() up front
        AND unwinds the earlier submits — the queue-depth gauge must
        not report phantom waiters from an engine that never ran."""
        reg = M.MetricsRegistry()
        saved = M.set_default(reg)
        try:
            b = _batcher(params, batch=1)
            with pytest.raises(ValueError, match="request 1"):
                b.serve([[1, 2], [1] * 40], 8)
            assert reg.gauge("tony_serve_queue_depth").value == 0
            # and the batcher is still serviceable
            assert b.serve([[1, 2]], 4)
        finally:
            M.set_default(saved)

    def test_second_engine_on_live_batcher_rejected(self, params):
        """Constructing an engine over a batcher another engine is
        driving must fail BEFORE touching the batcher's rng/counter
        state — a silent reset would corrupt the live run's streams."""
        b = _batcher(params, batch=1, chunk=2)
        h = _EngineHarness(b)
        t0 = time.time()
        while not getattr(b, "_engine_running", False) \
                and time.time() - t0 < 30:
            time.sleep(0.005)
        with pytest.raises(RuntimeError, match="live engine"):
            ServeEngine(b)
        with pytest.raises(RuntimeError, match="live engine"):
            b.serve([[1, 2]], 4)
        h.finish()
        assert b.serve([[1, 2]], 4)         # reusable once drained

    def test_submit_validation(self, params):
        eng = ServeEngine(_batcher(params, batch=1))
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(0, [], 4)
        with pytest.raises(ValueError, match="positive"):
            eng.submit(0, [1, 2], 0)
        with pytest.raises(ValueError, match="exceeds max_len"):
            eng.submit(0, [1] * 30, 8)
        eng.submit(0, [1, 2], 4)
        with pytest.raises(ValueError, match="already active"):
            eng.submit(0, [1, 2], 4)
        eng.stop()
        eng.run()                                 # drains the abort


class TestServingServerE2E:
    def test_streamed_tokens_match_reference(self, params):
        prompts = _prompts(0, (5, 3, 7, 4))
        reg = M.MetricsRegistry()
        srv = ServingServer(_batcher(params), registry=reg)
        port = srv.start()
        try:
            with StreamingClient("127.0.0.1", port) as c:
                assert c.hello["slots"] == 2
                rids = [c.submit(p, 6) for p in prompts]
                for i, rid in enumerate(rids):
                    toks, reason = c.result(rid)
                    assert toks == _reference(params, prompts[i], 6), i
                    assert reason == "budget"
            # latency histograms populated at the delta-emission point
            assert reg.histogram("tony_serve_ttft_seconds").count >= 4
            assert reg.histogram("tony_serve_intertoken_seconds").count > 0
        finally:
            srv.stop(drain=True)

    def test_poll_mode_and_stats(self, params):
        prompts = _prompts(5, (4, 4))
        srv = ServingServer(_batcher(params), registry=M.MetricsRegistry())
        port = srv.start()
        try:
            with StreamingClient("127.0.0.1", port) as c:
                rid = c.submit(prompts[0], 6, stream=False)
                got, polls = [], 0
                while True:
                    toks, reason = c.poll(rid)
                    polls += 1
                    got.extend(toks)
                    if reason is not None:
                        break
                assert got == _reference(params, prompts[0], 6)
                assert reason == "budget"
                assert polls >= 2                 # chunked, not one lump
                st = c.stats()
                assert st["slots"] == 2
                assert st["queue_depth"] == 0
        finally:
            srv.stop(drain=True)

    def test_cancel_over_the_wire(self, params):
        prompts = _prompts(6, (4, 4))
        srv = ServingServer(_batcher(params, batch=1, chunk=2),
                            registry=M.MetricsRegistry())
        port = srv.start()
        try:
            with StreamingClient("127.0.0.1", port) as c:
                rid = c.submit(prompts[0], 25)
                ev = c.next_event(rid, timeout=60)
                assert ev[0] == "tokens"
                c.cancel(rid)
                c.cancel(rid)                     # idempotent on the wire
                toks = list(ev[1])
                while True:
                    ev = c.next_event(rid, timeout=60)
                    if ev[0] == "retired":
                        assert ev[1] == "cancelled"
                        break
                    assert ev[0] == "tokens"
                    toks.extend(ev[1])
                # a cancelled stream is a PREFIX of the full answer
                ref = _reference(params, prompts[0], 25)
                assert toks == ref[:len(toks)]
                assert len(toks) < 25
                # the freed slot serves the next request completely
                rid2 = c.submit(prompts[1], 6)
                toks2, reason = c.result(rid2)
                assert toks2 == _reference(params, prompts[1], 6)
        finally:
            srv.stop(drain=True)

    def test_graceful_drain(self, params):
        """stop(drain=True) finishes in-flight requests — the client
        still receives every token and the RETIRED frame."""
        prompts = _prompts(7, (4,))
        srv = ServingServer(_batcher(params, batch=1, chunk=2),
                            registry=M.MetricsRegistry())
        port = srv.start()
        c = StreamingClient("127.0.0.1", port)
        try:
            rid = c.submit(prompts[0], 12)
            ev = c.next_event(rid, timeout=60)
            assert ev[0] == "tokens"
            stopper = threading.Thread(target=srv.stop,
                                       kwargs={"drain": True})
            stopper.start()
            toks = list(ev[1])
            while True:
                ev = c.next_event(rid, timeout=60)
                if ev[0] == "retired":
                    break
                toks.extend(ev[1])
            assert toks == _reference(params, prompts[0], 12)
            stopper.join(timeout=60)
            assert not stopper.is_alive()
        finally:
            c.close()


class TestProtocolRobustness:
    """Satellite contract: malformed/truncated frames never kill the
    server; disconnects free slots; errors are scoped correctly."""

    @pytest.fixture()
    def server(self, params):
        srv = ServingServer(_batcher(params), registry=M.MetricsRegistry())
        srv.start()
        yield srv
        srv.stop()

    def _assert_still_serving(self, params, port):
        prompts = _prompts(9, (4,))
        with StreamingClient("127.0.0.1", port) as c:
            toks, reason = c.result(c.submit(prompts[0], 5))
            assert toks == _reference(params, prompts[0], 5)

    def test_garbage_magic_closed(self, params, server):
        s = socket.create_connection(("127.0.0.1", server.port))
        s.sendall(b"GET / HTTP/1.1\r\n\r\n")
        assert s.recv(4096) == b""                # server closed it
        s.close()
        self._assert_still_serving(params, server.port)

    def test_implausible_frame_is_connection_scoped(self, params, server):
        s = socket.create_connection(("127.0.0.1", server.port))
        s.sendall(P.MAGIC)
        assert P.recv_frame(s)[0] == P.HELLO
        s.sendall(struct.pack("<I", P.MAX_FRAME_BYTES + 5))
        frame = P.recv_frame(s)                   # ERROR rid=0, then EOF
        assert frame is not None and frame[0] == P.ERROR and frame[1] == 0
        assert "implausible" in P.unpack_json(frame[2])["message"]
        assert P.recv_frame(s) is None
        s.close()
        self._assert_still_serving(params, server.port)

    def test_truncated_frame_never_kills_server(self, params, server):
        s = socket.create_connection(("127.0.0.1", server.port))
        s.sendall(P.MAGIC)
        assert P.recv_frame(s)[0] == P.HELLO
        s.sendall(struct.pack("<I", 64) + b"\x01partial")
        s.close()                                 # die mid-frame
        self._assert_still_serving(params, server.port)

    def test_unknown_frame_type_is_connection_scoped(self, params,
                                                     server):
        s = socket.create_connection(("127.0.0.1", server.port))
        s.sendall(P.MAGIC)
        assert P.recv_frame(s)[0] == P.HELLO
        P.send_frame(s, 250, 1)
        frame = P.recv_frame(s)
        assert frame[0] == P.ERROR and frame[1] == 0
        assert P.recv_frame(s) is None
        s.close()
        self._assert_still_serving(params, server.port)

    def test_malformed_admit_payload_is_connection_scoped(self, params,
                                                          server):
        s = socket.create_connection(("127.0.0.1", server.port))
        s.sendall(P.MAGIC)
        assert P.recv_frame(s)[0] == P.HELLO
        P.send_frame(s, P.ADMIT, 1, b"\xff\xfenot json")
        frame = P.recv_frame(s)
        assert frame[0] == P.ERROR and frame[1] == 0
        s.close()
        self._assert_still_serving(params, server.port)

    def test_unservable_admit_is_request_scoped(self, params, server):
        """A too-long prompt costs an ERROR for that rid only — the
        connection keeps working."""
        with StreamingClient("127.0.0.1", server.port) as c:
            rid = c.submit([1] * 40, 8)           # exceeds max_len 32
            ev = c.next_event(rid, timeout=60)
            assert ev[0] == "error" and "exceeds max_len" in ev[1]
            prompts = _prompts(10, (4,))
            toks, _ = c.result(c.submit(prompts[0], 5))
            assert toks == _reference(params, prompts[0], 5)

    def test_duplicate_rid_is_request_scoped(self, params, server):
        """A duplicate ADMIT rid earns an ERROR for that rid while the
        original stream keeps delivering — and the reply is sent after
        the session lock is dropped (TL001), so a slow duplicate-sender
        can never stall admission for everyone else."""
        with StreamingClient("127.0.0.1", server.port) as c:
            prompt = _prompts(14, (4,))[0]
            c.submit(prompt, 6, rid=777)
            c.submit(prompt, 6, rid=777)          # duplicate, same rid
            saw_error, saw_retired = False, False
            deadline = time.time() + 60
            while not (saw_error and saw_retired) and time.time() < deadline:
                ev = c.next_event(777, timeout=60)
                if ev[0] == "error":
                    assert "already active" in ev[1]
                    saw_error = True
                elif ev[0] == "retired":
                    saw_retired = True            # original stream intact
            assert saw_error and saw_retired
            # connection-scoped state is clean: fresh rids still serve
            toks, _ = c.result(c.submit(prompt, 5))
            assert toks == _reference(params, prompt, 5)

    def test_disconnect_mid_stream_frees_slots(self, params, server):
        """A client that vanishes mid-stream must not leak its cache
        slots: with batch=2 fully occupied by the vanished client, a
        NEW client's requests still complete."""
        c1 = StreamingClient("127.0.0.1", server.port)
        r1 = c1.submit(_prompts(11, (4,))[0], 25)
        r2 = c1.submit(_prompts(12, (4,))[0], 25)
        assert c1.next_event(r1, timeout=60)[0] == "tokens"
        c1.close()                                # both slots were busy
        self._assert_still_serving(params, server.port)
        # engine-side: the cancelled occupants were swept
        t0 = time.time()
        while time.time() - t0 < 30:
            st = server.engine.stats()
            if st["active"] == 0 and st["queue_depth"] == 0:
                break
            time.sleep(0.01)
        assert st["active"] == 0, st


class TestRouter:
    def _replicas(self, params, n=2, **kw):
        servers = [ServingServer(_batcher(params, **kw),
                                 registry=M.MetricsRegistry())
                   for _ in range(n)]
        ports = [s.start() for s in servers]
        return servers, [f"127.0.0.1:{p}" for p in ports]

    def test_sessions_spread_by_queue_depth(self, params):
        """Enough concurrent sessions land on BOTH replicas (placement
        by reported queue depth + local assignment), and every output
        matches the solo reference."""
        servers, addrs = self._replicas(params)
        router = ServingRouter(addrs, registry=M.MetricsRegistry())
        rport = router.start()
        prompts = _prompts(13, (5, 3, 7, 4, 6, 3))
        try:
            with StreamingClient("127.0.0.1", rport) as c:
                assert c.hello["router"] is True
                rids = [c.submit(p, 6) for p in prompts]
                outs = [c.result(r) for r in rids]
            for i, (toks, reason) in enumerate(outs):
                assert toks == _reference(params, prompts[i], 6), i
            placed = router.stats()["replicas"]
            placed_counts = [servers[i].engine.b.steps_executed
                             for i in range(2)]
            assert all(s > 0 for s in placed_counts), (
                f"placement did not spread: {placed}")
        finally:
            router.stop()
            for s in servers:
                s.stop()

    def test_placement_prefers_less_loaded_replica(self, params):
        """With replica A pre-loaded (its queue depth reported via
        STATS), new router sessions land on B."""
        servers, addrs = self._replicas(params, chunk=2)
        router = ServingRouter(addrs, health_interval_s=0.1,
                               registry=M.MetricsRegistry())
        rport = router.start()
        try:
            # saturate replica A directly: 2 slots busy + 2 queued
            host_a, port_a = addrs[0].rsplit(":", 1)
            ca = StreamingClient(host_a, int(port_a))
            fillers = [ca.submit(p, 28)
                       for p in _prompts(14, (3, 3, 3, 3))]
            # let a health/stats cycle observe the load
            deadline = time.time() + 10
            while time.time() < deadline:
                load = router.stats()["replicas"][addrs[0]]
                if load["reported_load"] >= 3:
                    break
                time.sleep(0.02)
            assert load["reported_load"] >= 3, load
            with StreamingClient("127.0.0.1", rport) as c:
                prompts = _prompts(15, (4, 4))
                rids = [c.submit(p, 4) for p in prompts]
                for i, r in enumerate(rids):
                    toks, _ = c.result(r)
                    assert toks == _reference(params, prompts[i], 4)
            placed = router.stats()["replicas"]
            b_sessions = servers[1].engine.b.steps_executed
            assert b_sessions > 0, placed         # B actually served
            for f in fillers:
                ca.cancel(f)
            ca.close()
        finally:
            router.stop()
            for s in servers:
                s.stop()

    def test_replica_loss_drains_to_survivor_no_dup_no_drop(self, params):
        """THE router acceptance pin: kill a replica mid-stream; every
        session it carried completes on the survivor with exactly the
        solo-reference token sequence — the streamed prefix is trimmed
        into the re-admission, so nothing duplicates and nothing
        drops."""
        class SlowFetch(ContinuousBatcher):
            def _fetch(self, handle):
                time.sleep(0.05)          # keep streams mid-flight
                return super()._fetch(handle)

        servers = [ServingServer(SlowFetch(params, CFG, batch=2,
                                           max_len=64, chunk=2),
                                 registry=M.MetricsRegistry())
                   for _ in range(2)]
        addrs = [f"127.0.0.1:{s.start()}" for s in servers]
        reg = M.MetricsRegistry()
        router = ServingRouter(addrs, health_interval_s=0.2, registry=reg)
        rport = router.start()
        prompts = _prompts(16, (5, 5, 5, 5))
        budget = 24
        got = {}
        try:
            with StreamingClient("127.0.0.1", rport) as c:
                rids = [c.submit(p, budget) for p in prompts]
                got = {r: [] for r in rids}
                started = set()
                deadline = time.time() + 60
                while len(started) < len(rids) and time.time() < deadline:
                    for i, r in enumerate(rids):
                        if r in started:
                            continue
                        try:
                            ev = c.next_event(r, timeout=0.05)
                        except queue_mod.Empty:
                            continue
                        assert ev[0] == "tokens", ev
                        got[r].extend(ev[1])
                        started.add(r)
                assert len(started) == len(rids), "streams never started"
                pre = router.stats()["replicas"]
                assert all(v["assigned"] > 0 for v in pre.values()), pre
                servers[0].kill()                 # replica loss
                for i, r in enumerate(rids):
                    while True:
                        ev = c.next_event(r, timeout=60)
                        if ev[0] == "tokens":
                            got[r].extend(ev[1])
                        elif ev[0] == "retired":
                            break
                        else:
                            raise AssertionError(ev)
                for i, r in enumerate(rids):
                    assert got[r] == _reference(params, prompts[i],
                                                budget), i
            assert reg.counter("tony_router_failovers_total").value >= 1
            assert reg.gauge("tony_router_replica_up",
                             replica=addrs[0]).value == 0
            assert reg.gauge("tony_router_replica_up",
                             replica=addrs[1]).value == 1
        finally:
            router.stop()
            for s in servers:
                s.stop()


class TestFleetOperations:
    """Planned drain + rolling upgrade on REAL serving replicas: the
    live-operability acceptance pins. Migration re-prefills on a
    survivor with the streamed prefix folded in and the session's rng
    stream/offset pinned, so the full token sequence — greedy AND
    sampled — must equal the solo reference exactly."""

    def _slow_servers(self, params, n=2, weights_version=None,
                      fetch_s=0.05, **kw):
        class SlowFetch(ContinuousBatcher):
            def _fetch(self, handle):
                time.sleep(fetch_s)       # keep streams mid-flight
                return super()._fetch(handle)

        kw.setdefault("batch", 2)
        kw.setdefault("max_len", 64)
        kw.setdefault("chunk", 2)
        servers = [ServingServer(SlowFetch(params, CFG, **kw),
                                 registry=M.MetricsRegistry(),
                                 weights_version=weights_version)
                   for _ in range(n)]
        return servers, [f"127.0.0.1:{s.start()}" for s in servers]

    def _start_streams(self, c, prompts, budget):
        """Submit and block until every stream has produced at least
        one token (so a drain migrates genuinely mid-flight)."""
        rids = [c.submit(p, budget) for p in prompts]
        got = {r: [] for r in rids}
        started = set()
        deadline = time.time() + 60
        while len(started) < len(rids) and time.time() < deadline:
            for r in rids:
                if r in started:
                    continue
                try:
                    ev = c.next_event(r, timeout=0.05)
                except queue_mod.Empty:
                    continue
                assert ev[0] == "tokens", ev
                got[r].extend(ev[1])
                started.add(r)
        assert len(started) == len(rids), "streams never started"
        return rids, got

    def _collect(self, c, rids, got):
        for r in rids:
            while True:
                ev = c.next_event(r, timeout=60)
                if ev[0] == "tokens":
                    got[r].extend(ev[1])
                elif ev[0] == "retired":
                    break
                else:
                    raise AssertionError(ev)

    def test_planned_drain_zero_dup_drop_greedy(self, params):
        """Drain a replica carrying live greedy streams: every session
        completes with exactly the solo-reference tokens, the drained
        replica ends fenced and empty, and the migration counters
        move."""
        # batch=4: the survivor has idle slots, so migrations ACK
        # while the old placement still streams (the interesting path)
        servers, addrs = self._slow_servers(params, batch=4)
        reg = M.MetricsRegistry()
        router = ServingRouter(addrs, health_interval_s=0.2,
                               registry=reg)
        rport = router.start()
        prompts = _prompts(31, (5, 5, 5, 5))
        budget = 24
        try:
            with StreamingClient("127.0.0.1", rport) as c:
                rids, got = self._start_streams(c, prompts, budget)
                pre = router.stats()["replicas"]
                assert all(v["assigned"] > 0 for v in pre.values()), pre
                victim = max(pre, key=lambda a: pre[a]["assigned"])
                res = c.drain_replica(victim)
                assert res.get("drained"), res
                assert res["migrated"] >= 1, res
                self._collect(c, rids, got)
                for i, r in enumerate(rids):
                    assert got[r] == _reference(params, prompts[i],
                                                budget), i
                post = router.stats()["replicas"]
                assert post[victim]["draining"]
                assert post[victim]["assigned"] == 0
            # every drain-initiated migration either ACKs (counted) or
            # the old placement legitimately finishes first — at least
            # one must take the ACK path with idle survivor slots
            migs = reg.counter("tony_router_migrations_total").value
            assert 1 <= migs <= res["migrated"], (migs, res)
            assert reg.counter("tony_router_drains_total").value == 1
            # drain is planned, not failover
            assert reg.counter("tony_router_failovers_total").value == 0
        finally:
            router.stop()
            for s in servers:
                s.stop()

    def test_planned_drain_zero_dup_drop_sampled(self, params):
        """The sampled twin: per-session rng stream + offset pinning
        makes the migrated continuation bit-identical to the
        uninterrupted sampled run."""
        kw = dict(batch=2, max_len=64, chunk=2, seed=7,
                  temperature=0.8, top_k=20, top_p=0.9)
        prompts = _prompts(32, (5, 4, 6, 5))
        budget = 20
        ref = ContinuousBatcher(params, CFG, **kw).serve(prompts, budget)
        servers, addrs = self._slow_servers(params, **kw)
        router = ServingRouter(addrs, health_interval_s=0.2,
                               registry=M.MetricsRegistry())
        rport = router.start()
        try:
            with StreamingClient("127.0.0.1", rport) as c:
                rids, got = self._start_streams(c, prompts, budget)
                pre = router.stats()["replicas"]
                victim = max(pre, key=lambda a: pre[a]["assigned"])
                res = c.drain_replica(victim)
                assert res.get("drained"), res
                self._collect(c, rids, got)
                for i, r in enumerate(rids):
                    assert got[r] == ref[i], \
                        f"stream {i}: sampled dup/drop across migration"
        finally:
            router.stop()
            for s in servers:
                s.stop()

    def test_rolling_upgrade_mid_stream_continuity(self, params):
        """Upgrade a 2-replica fleet mid-stream: stand up the v2 tier,
        drain and retire v1 replica by replica. Every in-flight
        session keeps exact token continuity, the fleet ends all-v2,
        and a fresh session lands on the new tier."""
        from tony_tpu.serving.fleet import CapacityProvider, \
            FleetController

        old_servers, old_addrs = self._slow_servers(
            params, weights_version="v1")
        new_servers, new_addrs = self._slow_servers(
            params, weights_version="v2")
        by_addr = dict(zip(old_addrs + new_addrs,
                           old_servers + new_servers))

        class StopProvider(CapacityProvider):
            released = []

            def grow(self, n):
                raise AssertionError("upgrade must not grow")

            def release(self, addrs):
                for a in addrs:
                    self.released.append(a)
                    by_addr[a].stop()

        reg = M.MetricsRegistry()
        router = ServingRouter(old_addrs, health_interval_s=0.2,
                               registry=reg)
        rport = router.start()
        prompts = _prompts(33, (5, 5, 4, 6))
        budget = 24
        try:
            ctrl = FleetController(router, StopProvider(), registry=reg)
            with StreamingClient("127.0.0.1", rport) as c:
                rids, got = self._start_streams(c, prompts, budget)
                results = ctrl.rolling_upgrade(new_addrs)
                assert set(results) == set(old_addrs)
                assert all(r.get("drained") for r in results.values()), \
                    results
                self._collect(c, rids, got)
                for i, r in enumerate(rids):
                    assert got[r] == _reference(params, prompts[i],
                                                budget), i
                post = router.stats()["replicas"]
                assert set(post) == set(new_addrs), post
                assert all(v["weights_version"] == "v2"
                           for v in post.values()), post
                assert sorted(StopProvider.released) == sorted(old_addrs)
                # a fresh session serves on the upgraded tier
                p = _prompts(34, (5,))[0]
                rid = c.submit(p, 6)
                toks, reason = c.result(rid)
                assert toks == _reference(params, p, 6)
            assert reg.counter("tony_fleet_upgrades_total").value == 1
        finally:
            router.stop()
            for s in old_servers + new_servers:
                s.stop()


class TestStreamingBenchArm:
    def test_stream_vs_request_response_pins(self):
        """The tentpole acceptance, deterministically: at a 50 ms
        injected round trip the streamed wall sits within 1.15x of the
        zero-delay wall (the round trip is paid once) while the
        request/response wire pays it per chunk + per admission —
        stream-vs-rr >= 2. The plug keeps the streamed sync schedule
        identical across runs (asserted)."""
        import bench

        res = bench._streaming_arm()
        assert res["serving_stream_syncs"] == \
            res["serving_stream_syncs_nodelay"], res
        assert res["serving_stream_vs_nodelay"] <= 1.15, res
        assert res["serving_stream_vs_rr_wall"] >= 2.0, res
        # rr degraded by >= exchanges x RT over ITS compute floor
        floor = (res["serving_stream_wall_nodelay_s"]
                 - 0.0)                           # same chunk schedule
        degraded = res["serving_rr_wall_s"] - floor
        assert degraded >= (0.8 * res["serving_rr_round_trips"]
                            * res["serving_stream_round_trip_s"]), res
        assert res["serving_stream_ttft_s"] > 0, res


@pytest.mark.slow
class TestStreamingBenchRealistic:
    def test_realistic_compute_still_streams_past_rr(self):
        """No injected fetch floor — real (tiny-model) chunk compute
        only, so the 50 ms round trip dominates: streaming must beat
        the per-chunk wire by well over 2x."""
        import bench

        res = bench._streaming_arm(fetch_floor_s=0.0, budget=96)
        assert res["serving_stream_vs_rr_wall"] >= 2.0, res
