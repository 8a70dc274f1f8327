"""Streaming serving server: one persistent connection per client, one
:class:`~tony_tpu.models.serve.ServeEngine` per server.

The pre-streaming serving path paid a client↔server round trip per
chunk and per admission (request/response, serialized with compute).
Here the engine runs in one thread, each connection gets one reader
thread feeding admissions/cancels straight into the engine's live
queue, and the engine's delta callbacks push TOKENS frames the moment a
chunk is consumed — transport overlaps device compute end-to-end, and
one connection multiplexes any number of in-flight requests.

Robustness contract (test-enforced):

- a malformed or truncated frame is CONNECTION-scoped: the offender
  gets a best-effort ``ERROR`` (rid 0) and a clean close; the server
  and every other connection keep serving;
- an un-servable ADMIT (bad budget, prompt too long, duplicate rid) is
  REQUEST-scoped: ``ERROR`` with that rid, connection stays up;
- a client disconnect cancels all its in-flight requests — their cache
  slots free at the next consumed chunk and readmit from the queue;
- ``CANCEL`` racing retirement is idempotent (engine contract).

``stop(drain=True)`` is the graceful path: no new connections or
admissions, in-flight requests finish and stream out, then the engine
thread exits. ``kill()`` severs client connections first (peers see
EOF immediately) and aborts the engine — the router's replica-loss
drill.
"""

from __future__ import annotations

import itertools
import logging
import socket
import threading

from tony_tpu.runtime import metrics as metrics_mod
from tony_tpu.serving import protocol as P
from tony_tpu.serving.prefix import PrefixHost, fingerprint
from tony_tpu.serving.weightstore import WeightHost, pack_weights, \
    tree_digest

log = logging.getLogger(__name__)


class FrameConn:
    """One accepted connection: socket + serialized writes. Engine
    callbacks, poll responses, and error replies may send from
    different threads — ``send`` takes the per-connection lock and
    reports (rather than raises) transport failure."""

    def __init__(self, conn_id: int, sock: socket.socket, addr) -> None:
        self.id = conn_id
        self.sock = sock
        self.addr = addr
        self._send_lock = threading.Lock()
        self.alive = True

    def send(self, ftype: int, rid: int, payload: bytes = b"") -> bool:
        return self.send_many([(ftype, rid, payload)])

    def send_many(self, frames) -> bool:
        """Write several frames in ONE sendall — a retiring request's
        final TOKENS and its RETIRED frame share a kernel write, so a
        process killed between them cannot deliver one without the
        other (the router's failover reads an unfinished stream off
        exactly that gap)."""
        buf = b"".join(P.encode_frame(t, r, p) for t, r, p in frames)
        with self._send_lock:
            if not self.alive:
                return False
            try:
                self.sock.sendall(buf)
                return True
            except OSError:
                self.alive = False
                return False

    def close(self) -> None:
        self.alive = False
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


class FrameServerBase:
    """Accept loop + per-connection frame reader for the TONYS1
    protocol. Subclasses implement ``_hello_payload()``,
    ``_handle_frame(conn, ftype, rid, payload)`` (raise
    :class:`~tony_tpu.serving.protocol.ProtocolError` for
    connection-scoped violations), and ``_on_conn_closed(conn)``."""

    def __init__(self, bind_host: str = "127.0.0.1", port: int = 0) -> None:
        self.bind_host = bind_host
        self.port = port
        self._listener: socket.socket | None = None
        self._accept_thread: threading.Thread | None = None
        self._conns: dict[int, FrameConn] = {}
        self._conn_ids = itertools.count(1)
        self._conns_lock = threading.Lock()
        self._stopping = threading.Event()

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> int:
        server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        server.bind((self.bind_host, self.port))
        server.listen(64)
        self.port = server.getsockname()[1]
        self._listener = server
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="tony-serve-accept", daemon=True)
        self._accept_thread.start()
        return self.port

    def _close_listener(self) -> None:
        if self._listener is not None:
            try:
                self._listener.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                self._listener.close()
            except OSError:
                pass

    def _close_conns(self) -> None:
        with self._conns_lock:
            conns = list(self._conns.values())
        for conn in conns:
            conn.close()

    # -- accept / read ------------------------------------------------------
    def _accept_loop(self) -> None:
        assert self._listener is not None
        while not self._stopping.is_set():
            try:
                sock, addr = self._listener.accept()
            except OSError:
                break                       # listener closed by stop()
            P.set_nodelay(sock)
            conn = FrameConn(next(self._conn_ids), sock, addr)
            with self._conns_lock:
                self._conns[conn.id] = conn
            threading.Thread(target=self._serve_conn, args=(conn,),
                             name=f"tony-serve-conn-{conn.id}",
                             daemon=True).start()

    def _serve_conn(self, conn: FrameConn) -> None:
        try:
            if not P.read_magic(conn.sock):
                log.warning("serving: %s sent no TONYS1 magic; closing",
                            conn.addr)
                return
            conn.send(P.HELLO, 0, P.pack_json(self._hello_payload()))
            while not self._stopping.is_set():
                frame = P.recv_frame(conn.sock)
                if frame is None:
                    break                   # clean disconnect
                self._handle_frame(conn, *frame)
        except P.ProtocolError as e:
            # connection-scoped: report, close THIS connection, keep
            # serving everyone else — and leave a postmortem artifact
            # scoped to the OFFENDING connection (the flight recorder's
            # final entries name it; healthy connections dump nothing)
            log.warning("serving: protocol error from %s: %s",
                        conn.addr, e)
            from tony_tpu.runtime import tracing
            flight = tracing.get_flight()
            flight.record("protocol_error", conn=conn.id,
                          addr=str(conn.addr), error=str(e)[:500])
            flight.dump("protocol_error", conn=conn.id,
                        addr=str(conn.addr))
            conn.send(P.ERROR, 0, P.pack_json({"message": str(e)}))
        except OSError:
            pass                            # connection reset under us
        finally:
            conn.close()
            with self._conns_lock:
                self._conns.pop(conn.id, None)
            self._on_conn_closed(conn)

    # -- subclass surface ---------------------------------------------------
    def _hello_payload(self) -> dict:
        raise NotImplementedError

    def _handle_frame(self, conn: FrameConn, ftype: int, rid: int,
                      payload: bytes) -> None:
        raise NotImplementedError

    def _on_conn_closed(self, conn: FrameConn) -> None:
        raise NotImplementedError


class _Session:
    """Server-side request state. ``stream=True`` pushes deltas as they
    land; ``stream=False`` buffers them for long-POLLs (the
    request/response contrast the streaming arm is measured against)."""

    __slots__ = ("conn", "rid", "stream", "buffer", "retired",
                 "poll_pending")

    def __init__(self, conn: FrameConn, rid: int, stream: bool) -> None:
        self.conn = conn
        self.rid = rid
        self.stream = stream
        self.buffer: list[int] = []
        self.retired: tuple[str, int] | None = None
        self.poll_pending = False


class ServingServer(WeightHost, PrefixHost, FrameServerBase):
    """Drive a batcher's :class:`~tony_tpu.models.serve.ServeEngine`
    behind the TONYS1 streaming protocol.

    Usage::

        server = ServingServer(batcher, port=0)
        port = server.start()          # engine + accept threads
        ...
        server.stop(drain=True)        # finish in-flight, then exit

    PREFIX-AWARE serving (docs/serving.md §Prefix-aware routing): the
    server is a :class:`~tony_tpu.serving.prefix.PrefixHost` — its
    HELLO and STATS advertise the batcher's resident prefix templates
    (and the template lane's ``prefix_port``), ``PREFIX`` frames carry
    install/publish/list ops, and a peer's published template lands
    through the lane into the batcher's store with zero prefill
    forwards. ADMITs naming (or auto-matching) a resident prefix run
    only their suffix through the model.
    """

    def __init__(self, batcher, bind_host: str = "127.0.0.1",
                 port: int = 0, registry=None,
                 weights_version: str | None = None,
                 weights_digest: str | None = None,
                 class_floors: dict | None = None,
                 max_queue_depth: int = 128,
                 busy_retry_ms: int = 250,
                 latency_buckets=None) -> None:
        super().__init__(bind_host, port)
        from tony_tpu.models.serve import ServeEngine
        self.batcher = batcher
        #: the model-weights generation this replica serves, advertised
        #: in HELLO and STATS — what the router's version-pinned
        #: placement (rolling upgrades) keys on. None = unversioned.
        self.weights_version = weights_version
        #: the content digest of the served weight tree (computed at
        #: start() when not given) — the version-pinning fallback for
        #: unversioned fleets, and the name peers pull this replica's
        #: artifact by (warm scale-up).
        self.weights_digest = weights_digest
        self._lock = threading.Lock()
        self._sessions: dict[tuple[int, int], _Session] = {}
        self.engine = ServeEngine(batcher, on_delta=self._on_delta,
                                  on_retired=self._on_retired,
                                  registry=registry,
                                  class_floors=class_floors,
                                  max_queue_depth=max_queue_depth,
                                  busy_retry_ms=busy_retry_ms,
                                  latency_buckets=latency_buckets)
        self._engine_thread: threading.Thread | None = None
        reg = registry or metrics_mod.get_default()
        self._init_prefix_host(reg)
        # the weights lane shares the prefix hub's port (blobs are
        # kind-tagged: neither lane can misread the other's); the
        # exporter lazily packs the live params the first time a peer
        # (or the fleet) asks to seed from this replica
        self._init_weight_host(reg, exporter=self._export_weights_blob,
                               hub=self._prefix_hub)

    # -- resident prefix templates (PrefixHost hooks) -----------------------
    def install_prefix(self, tokens, prefix_id: str | None = None):
        """Compute ``tokens``' K/V template locally and make it
        resident; returns the prefix id (content fingerprint unless
        given), or None when the batcher degraded prefix-blind (ring
        layout)."""
        pid = prefix_id or fingerprint(tokens)
        return pid if self.batcher.install_prefix(pid, tokens) else None

    def install_prefix_template(self, meta, bufs) -> str:
        return self.batcher.install_prefix_template(meta, bufs)

    def resident_prefixes(self) -> list:
        return self.batcher.resident_prefixes()

    def _prefix_blob(self, prefix_id: str) -> bytes:
        return self.batcher.export_prefix_blob(prefix_id)

    # -- the seedable weight artifact (WeightHost exporter) -----------------
    def _export_weights_blob(self) -> bytes:
        return pack_weights(self.batcher.params,
                            version=self.weights_version)

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> int:
        if self.weights_digest is None:
            try:
                self.weights_digest = tree_digest(self.batcher.params)
            except Exception as e:          # noqa: BLE001 — advisory
                log.warning("weights digest not computed: %s", e)
        self._engine_thread = threading.Thread(
            target=self.engine.run, name="tony-serve-engine", daemon=True)
        self._engine_thread.start()
        self._start_prefix_host()
        self._start_weight_host()
        port = super().start()
        log.info("serving on %s:%s (%d slots; prefix lane on :%s)",
                 self.bind_host, port, self.batcher.batch,
                 self.prefix_port)
        return port

    def stop(self, drain: bool = False,
             drain_timeout_s: float = 600.0) -> None:
        """Stop serving. ``drain=True`` finishes every accepted request
        (clients keep receiving deltas — and may keep CANCELing /
        POLLing / STATSing while the drain runs; only ``_stopping`` is
        deferred, because setting it would make a connection's next
        frame exit its reader loop and cancel that client's in-flight
        streams mid-drain); ``drain=False`` aborts — outstanding
        requests retire as ``"stopped"``. A drain that outlives
        ``drain_timeout_s`` is escalated to an abort, LOUDLY — a silent
        degradation would sever clients the caller believes drained."""
        self._close_listener()              # no new connections
        if drain:
            self.engine.drain()
        else:
            self._stopping.set()
            self.engine.stop()
        if self._engine_thread is not None:
            self._engine_thread.join(
                timeout=drain_timeout_s if drain else 60)
            if self._engine_thread.is_alive():
                log.warning(
                    "serving: engine did not %s within %.0fs; aborting "
                    "outstanding requests",
                    "drain" if drain else "stop",
                    drain_timeout_s if drain else 60)
                self.engine.stop()
                self._engine_thread.join(timeout=60)
        self._stopping.set()
        self._stop_prefix_host()
        self._stop_weight_host()
        self._close_conns()
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5)

    def kill(self) -> None:
        """Abrupt replica loss: sever client connections FIRST (peers
        see EOF immediately — what a crashed host looks like), then
        abort the engine."""
        self._stopping.set()
        self._close_listener()
        self._close_conns()
        self._stop_prefix_host()
        self._stop_weight_host()
        self.engine.stop()
        if self._engine_thread is not None:
            self._engine_thread.join(timeout=60)

    # -- frame handling (reader threads) ------------------------------------
    def _hello_payload(self) -> dict:
        # "role" lets a disaggregation-aware router sanity-check what
        # it connected to (a colocated engine serves prompts end-to-end);
        # "prefixes"/"ring"/"prefix_port" seed residency-aware routing
        return {"v": 1, "slots": self.batcher.batch, "role": "engine",
                "prefixes": self.batcher.resident_prefixes(),
                "ring": self.batcher._ring,
                "prefix_port": self.prefix_port,
                "weights_version": self.weights_version,
                "weights_digest": self.weights_digest,
                "weight_port": self.weight_port,
                "weights_resident": self.weight_store.resident_digests()}

    def _handle_frame(self, conn: FrameConn, ftype: int, rid: int,
                      payload: bytes) -> None:
        if ftype == P.ADMIT:
            self._admit(conn, rid, payload)
        elif ftype == P.CANCEL:
            self.engine.cancel((conn.id, rid))
        elif ftype == P.POLL:
            self._poll(conn, rid)
        elif ftype == P.STATS:
            conn.send(P.STATS, 0, P.pack_json(dict(
                self.engine.stats(),
                prefixes=self.batcher.resident_prefixes(),
                ring=self.batcher._ring,
                weights_version=self.weights_version,
                weights_digest=self.weights_digest,
                weights_resident=self.weight_store.resident_digests())))
        elif ftype == P.PREFIX:
            self._handle_prefix_frame(conn, rid, payload)
        elif ftype == P.WEIGHTS:
            self._handle_weights_frame(conn, rid, payload)
        else:
            raise P.ProtocolError(
                f"unexpected frame type {P.FRAME_NAMES.get(ftype, ftype)}")

    def _admit(self, conn: FrameConn, rid: int, payload: bytes) -> None:
        # structural violations are connection-scoped (raise), an
        # un-servable request is request-scoped (ERROR with its rid)
        prompt, max_new, stream = P.parse_admit(payload)
        trace_ctx = P.parse_trace_ctx(payload)
        prefix_id = P.parse_prefix_id(payload)
        rng = P.parse_rng(payload)
        if rid == 0:
            raise P.ProtocolError("ADMIT rid must be nonzero")
        try:
            # absent = "standard" (old wires unchanged); an UNKNOWN
            # class is a request-scoped error — the client asked for a
            # tier that does not exist and must hear "no", not silently
            # serve at a different one
            cls = P.parse_class(payload)
        except ValueError as e:
            conn.send(P.ERROR, rid, P.pack_json({"message": str(e)}))
            return
        key = (conn.id, rid)
        # the duplicate-rid reply is sent AFTER the lock is dropped: a
        # frame send can block on a slow client socket, and this lock
        # serializes admission/poll for every connection (TL001)
        with self._lock:
            duplicate = key in self._sessions
            if not duplicate:
                self._sessions[key] = _Session(conn, rid, stream)
        if duplicate:
            conn.send(P.ERROR, rid, P.pack_json(
                {"message": f"request id {rid} is already active"}))
            return
        # local import: models.serve pulls in jax, and this module must
        # stay importable without it (router/simfleet/daemon only want
        # FrameConn); by the time a request is admitted the engine --
        # and therefore jax -- is already loaded
        from tony_tpu.models.serve import EngineBusy
        try:
            self.engine.submit(key, prompt, max_new, trace_ctx=trace_ctx,
                               prefix_id=prefix_id, rng=rng,
                               request_class=cls)
        except EngineBusy as e:
            # the explicit shed: terminal for this rid, a statement
            # about LOAD — the client re-admits after the hint
            with self._lock:
                self._sessions.pop(key, None)
            conn.send(P.BUSY, rid, P.pack_json(
                {"retry_after_ms": e.retry_after_ms}))
        except (ValueError, RuntimeError) as e:
            with self._lock:
                self._sessions.pop(key, None)
            conn.send(P.ERROR, rid, P.pack_json({"message": str(e)}))

    def _poll(self, conn: FrameConn, rid: int) -> None:
        key = (conn.id, rid)
        reply = None
        with self._lock:
            sess = self._sessions.get(key)
            if sess is None:
                reply = (P.ERROR, P.pack_json(
                    {"message": f"unknown request id {rid}"}))
            elif sess.buffer:
                toks, sess.buffer = sess.buffer, []
                reply = (P.TOKENS, P.pack_tokens(toks))
            elif sess.retired is not None:
                reason, n = sess.retired
                del self._sessions[key]
                reply = (P.RETIRED,
                         P.pack_json({"reason": reason, "tokens": n}))
            else:
                sess.poll_pending = True    # answered when data lands
        if reply is not None:
            conn.send(reply[0], rid, reply[1])

    def _on_conn_closed(self, conn: FrameConn) -> None:
        """A disconnected client's requests are cancelled — their slots
        free at the next consumed chunk and readmit from the queue."""
        with self._lock:
            doomed = [key for key, s in self._sessions.items()
                      if s.conn is conn]
            for key in doomed:
                del self._sessions[key]
        for key in doomed:
            self.engine.cancel(key)

    # -- engine callbacks (engine thread; cancels: any thread) --------------
    def _on_delta(self, key, toks) -> None:
        reply = None
        with self._lock:
            sess = self._sessions.get(key)
            if sess is None:
                return                      # late delta for a dead session
            if sess.stream:
                reply = (sess.conn, P.TOKENS, sess.rid,
                         P.pack_tokens(toks))
            else:
                sess.buffer.extend(int(t) for t in toks)
                if sess.poll_pending:
                    sess.poll_pending = False
                    buf, sess.buffer = sess.buffer, []
                    reply = (sess.conn, P.TOKENS, sess.rid,
                             P.pack_tokens(buf))
        if reply is not None and not reply[0].send(*reply[1:]):
            self._drop_dead_conn(reply[0])

    def _on_retired(self, key, reason: str, n_tokens: int,
                    final_tokens) -> None:
        conn = None
        frames: list = []
        with self._lock:
            sess = self._sessions.get(key)
            if sess is None:
                return
            conn = sess.conn
            body = P.pack_json({"reason": reason, "tokens": n_tokens})
            if sess.stream:
                del self._sessions[key]
                # the final delta and the retirement go out in ONE
                # write (see FrameConn.send_many)
                if final_tokens:
                    frames.append((P.TOKENS, sess.rid,
                                   P.pack_tokens(final_tokens)))
                frames.append((P.RETIRED, sess.rid, body))
            else:
                sess.buffer.extend(int(t) for t in final_tokens)
                sess.retired = (reason, n_tokens)
                if sess.poll_pending:
                    sess.poll_pending = False
                    if sess.buffer:
                        buf, sess.buffer = sess.buffer, []
                        frames.append((P.TOKENS, sess.rid,
                                       P.pack_tokens(buf)))
                    else:
                        del self._sessions[key]
                        frames.append((P.RETIRED, sess.rid, body))
        if frames and not conn.send_many(frames):
            self._drop_dead_conn(conn)

    def _drop_dead_conn(self, conn: FrameConn) -> None:
        """A send failed mid-stream: the peer is gone. Close the socket
        so its reader thread unblocks and runs the disconnect cleanup
        (cancel + slot free)."""
        conn.close()
