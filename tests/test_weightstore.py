"""Warm scale-up plane: content-addressed weight artifacts, the
chunked byte-blob lane, peer-to-peer pull against a live server, the
self-organizing fan-out, and the bench pins.

The load-bearing guarantees pinned here:

- the digest is a pure function of tree CONTENT (deterministic across
  processes; any flipped byte, renamed path, or dtype change moves it);
- a landing recomputes the digest and REFUSES mismatches — corruption
  is an error, never silently served weights;
- a ship-warmed replica's tokens are bit-identical to a
  storage-loaded one's, greedy AND sampled;
- a blob survives the channel's reconnect-with-seq-resume mid-transfer
  with zero duplicated and zero dropped bytes;
- warm fan-out reaches N replicas in O(log N) waves and a crashed
  seeder degrades to a storage load, never a wedged fleet.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp                                     # noqa: E402

from tony_tpu.channels.channel import (BLOB_CHUNK_MAGIC, ChannelError,
                                       ChannelHub, ChannelSender,
                                       _blob_frame)          # noqa: E402
from tony_tpu.models import transformer as T                 # noqa: E402
from tony_tpu.models.serve import ContinuousBatcher          # noqa: E402
from tony_tpu.runtime.metrics import MetricsRegistry         # noqa: E402
from tony_tpu.serving import blobcodec                       # noqa: E402
from tony_tpu.serving.protocol import ProtocolError          # noqa: E402
from tony_tpu.serving.server import ServingServer            # noqa: E402
from tony_tpu.serving.weightstore import (                   # noqa: E402
    WEIGHT_CHANNEL, WeightStore, dir_digest, flatten_tree,
    install_compile_cache, pack_compile_cache, pack_weights, peek_weights_meta,
    pull_weights, tree_digest, unflatten_tree, unpack_weights, warm_fanout,
    weights_rpc)

CFG = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def _tree(seed=3):
    rng = np.random.RandomState(seed)
    return {"block": {"w": rng.randn(8, 16).astype(np.float32),
                      "b": rng.randn(16).astype(np.float32)},
            "head": [rng.randn(4).astype(np.float32),
                     rng.randint(0, 99, size=7).astype(np.int32)]}


# ---------------------------------------------------------------------------
# The content address
# ---------------------------------------------------------------------------
class TestTreeDigest:
    def test_flatten_round_trip(self):
        tree = _tree()
        flat = flatten_tree(tree)
        assert sorted(flat) == ["block/b", "block/w", "head/#0", "head/#1"]
        back = unflatten_tree(flat)
        assert isinstance(back["head"], list)
        np.testing.assert_array_equal(back["block"]["w"],
                                      tree["block"]["w"])
        np.testing.assert_array_equal(back["head"][1], tree["head"][1])

    def test_digest_is_content_only(self):
        d = tree_digest(_tree())
        assert len(d) == 64
        # dict order is irrelevant; an identically-valued rebuild agrees
        assert tree_digest(_tree()) == d
        # flat and nested forms agree (the wire ships flat)
        assert tree_digest(flatten_tree(_tree())) == d

    def test_digest_moves_on_any_change(self):
        base = tree_digest(_tree())
        flipped = _tree()
        flipped["block"]["w"][3, 7] += 1e-3
        assert tree_digest(flipped) != base
        renamed = _tree()
        renamed["block2"] = renamed.pop("block")
        assert tree_digest(renamed) != base
        recast = _tree()
        recast["block"]["b"] = recast["block"]["b"].astype(np.float64)
        assert tree_digest(recast) != base

    def test_digest_deterministic_across_processes(self):
        """The whole point of content addressing: two replicas that
        never spoke compute the SAME address for the same weights."""
        prog = (
            "import numpy as np, json, sys\n"
            "from tony_tpu.serving.weightstore import tree_digest\n"
            "rng = np.random.RandomState(3)\n"
            "tree = {'block': {'w': rng.randn(8, 16).astype(np.float32),"
            " 'b': rng.randn(16).astype(np.float32)},"
            " 'head': [rng.randn(4).astype(np.float32),"
            " rng.randint(0, 99, size=7).astype(np.int32)]}\n"
            "print(json.dumps(tree_digest(tree)))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, timeout=120,
                             cwd=os.path.join(os.path.dirname(__file__),
                                              os.pardir))
        assert out.returncode == 0, out.stderr
        assert json.loads(out.stdout.strip()) == tree_digest(_tree())


# ---------------------------------------------------------------------------
# The artifact: pack / land / refuse
# ---------------------------------------------------------------------------
class TestWeightArtifact:
    def test_round_trip_bit_identical(self):
        tree = _tree()
        blob = pack_weights(tree, version="v1")
        meta = peek_weights_meta(blob)
        assert meta["part"] == "weights" and meta["version"] == "v1"
        assert meta["digest"] == tree_digest(tree)
        landed_meta, landed = unpack_weights(blob)
        assert landed_meta["digest"] == meta["digest"]
        for path, a in flatten_tree(tree).items():
            b = flatten_tree(landed)[path]
            assert a.dtype == b.dtype
            assert a.tobytes() == b.tobytes()   # BIT identical

    def test_flipped_byte_refused(self):
        blob = bytearray(pack_weights(_tree()))
        blob[-10] ^= 0x40                       # one bit, deep in payload
        with pytest.raises(ProtocolError, match="REFUSED"):
            unpack_weights(bytes(blob))

    def test_quantized_ship_dequantizes_to_exact_shipped_version(self):
        """The quantize-on-wire guard: the digest names the AS-SERVED
        (dequantized) tree on both ends, so what lands is bit-identical
        to what the packer would itself serve — or the transfer is
        refused. A quantized artifact is its own version: distinct
        digest from the full-precision artifact."""
        rng = np.random.RandomState(5)
        tree = {"w": rng.randn(64, 64).astype(np.float32),
                "ids": rng.randint(0, 99, size=16).astype(np.int32)}
        q = pack_weights(tree, version="v1", quantize=True)
        full = pack_weights(tree, version="v1")
        assert len(q) < len(full) / 2           # int8 on the wire
        qmeta = peek_weights_meta(q)
        assert qmeta["quantized"] and qmeta["digest"] != \
            peek_weights_meta(full)["digest"]
        meta, landed = unpack_weights(q)        # digest gate passed
        assert tree_digest(landed) == meta["digest"]
        # landing the same quantized artifact twice is bit-stable
        _, landed2 = unpack_weights(q)
        for path, a in flatten_tree(landed).items():
            assert a.tobytes() == flatten_tree(landed2)[path].tobytes()

    def test_quantized_tamper_refused(self):
        blob = bytearray(pack_weights(_tree(), quantize=True))
        blob[-5] ^= 0x01
        with pytest.raises(ProtocolError, match="REFUSED"):
            unpack_weights(bytes(blob))

    def test_store_put_get_verifies(self):
        reg = MetricsRegistry()
        store = WeightStore(reg)
        blob = pack_weights(_tree())
        digest = store.put(blob)
        assert store.get(digest) == blob
        assert store.digests() == [digest]
        assert reg.counter("tony_weight_installs_total").value == 1
        bad = bytearray(blob)
        bad[-3] ^= 0x10
        with pytest.raises(ProtocolError, match="REFUSED"):
            store.put(bytes(bad))


# ---------------------------------------------------------------------------
# One codec, three lanes: adversarial blobs re-pinned for every kind
# ---------------------------------------------------------------------------
class TestBlobCodecKinds:
    def _mk(self, codec):
        return codec.pack({"x": 1}, {"a": np.arange(6, dtype=np.float32)})

    @pytest.mark.parametrize("codec", [blobcodec.KV_ROW,
                                       blobcodec.PREFIX_TEMPLATE,
                                       blobcodec.WEIGHTS],
                             ids=lambda c: c.kind)
    def test_truncated_rejected(self, codec):
        blob = self._mk(codec)
        with pytest.raises(ProtocolError, match="truncated"):
            codec.unpack(blob[:len(blob) - 4])

    @pytest.mark.parametrize("codec", [blobcodec.KV_ROW,
                                       blobcodec.PREFIX_TEMPLATE,
                                       blobcodec.WEIGHTS],
                             ids=lambda c: c.kind)
    def test_trailing_garbage_rejected(self, codec):
        with pytest.raises(ProtocolError, match="trailing"):
            codec.unpack(self._mk(codec) + b"xx")

    @pytest.mark.parametrize("packer,lane", [
        (blobcodec.WEIGHTS, blobcodec.KV_ROW),
        (blobcodec.KV_ROW, blobcodec.PREFIX_TEMPLATE),
        (blobcodec.PREFIX_TEMPLATE, blobcodec.WEIGHTS),
    ], ids=["weights-on-kv", "kv-on-template", "template-on-weights"])
    def test_mistagged_kind_rejected_on_every_lane(self, packer, lane):
        """A kv row can never land as weights (and every other
        pairing): the kind tag gates AFTER structural parse, so the
        error names the actual kind."""
        blob = self._mk(packer)
        with pytest.raises(ProtocolError,
                           match=f"does not belong on the {lane.kind!r}"):
            lane.unpack(blob)

    def test_untagged_legacy_meta_only_lands_on_kv_lane(self):
        legacy = blobcodec.pack_blob(
            {"x": 1}, {"a": np.arange(3, dtype=np.float32)})
        meta, bufs = blobcodec.KV_ROW.unpack(legacy)   # allow_untagged
        assert meta["x"] == 1 and "a" in bufs
        with pytest.raises(ProtocolError, match="does not belong"):
            blobcodec.WEIGHTS.unpack(legacy)

    def test_weights_blob_on_template_lane_keeps_template_error(self):
        """The pre-existing prefix pin survives the shared codec: a
        non-template blob on the template lane still reads 'not a
        prefix template'."""
        from tony_tpu.serving.kvship import unpack_template
        with pytest.raises(ProtocolError, match="not a prefix"):
            unpack_template(pack_weights(_tree()))


# ---------------------------------------------------------------------------
# The chunked resumable byte-blob lane
# ---------------------------------------------------------------------------
class TestChunkedBlobLane:
    def _hub(self):
        reg = MetricsRegistry()
        hub = ChannelHub(capacity=8, registry=reg)
        port = hub.start()
        return hub, port, reg

    def test_large_blob_chunks_and_lands_identical(self):
        hub, port, reg = self._hub()
        recv = hub.receiver("w")
        blob = np.random.RandomState(0).bytes(1 << 20)
        landed = {}

        def consume():
            landed["blob"] = recv.recv_bytes(timeout=30)

        t = threading.Thread(target=consume, daemon=True)
        try:
            s = ChannelSender(f"127.0.0.1:{port}", "w", window=8,
                              registry=reg)
            t.start()
            s.send_bytes(blob, sync=True, timeout=30,
                         chunk_bytes=64 * 1024)
            t.join(timeout=30)
            assert landed.get("blob") == blob
            s.close()
        finally:
            hub.stop()

    def test_magic_collision_escaped(self):
        """A payload that happens to START with the chunk magic must
        not be parsed as a manifest."""
        hub, port, reg = self._hub()
        recv = hub.receiver("w")
        blob = BLOB_CHUNK_MAGIC + b"i am not a manifest"
        try:
            s = ChannelSender(f"127.0.0.1:{port}", "w", window=4,
                              registry=reg)
            s.send_bytes(blob, sync=True, timeout=30)
            assert recv.recv_bytes(timeout=30) == blob
            s.close()
        finally:
            hub.stop()

    def test_short_poll_timeout_never_aborts_mid_blob(self):
        """The install-loop regression: a consumer polling with a
        250 ms timeout must land a blob whose chunks arrive SLOWER
        than that — the caller's timeout bounds only the wait for the
        blob to start; each chunk gets its own generous deadline."""
        hub, port, reg = self._hub()
        recv = hub.receiver("w")
        payloads = [b"a" * 100, b"b" * 100, b"c" * 77]
        blob_id = "feedfeedfeedfeed"
        landed = {}
        done = threading.Event()

        def consume():
            # the install-loop shape: short idle polls, forever
            while not done.is_set():
                try:
                    landed["blob"] = recv.recv_bytes(timeout=0.25)
                    return
                except ChannelError:
                    continue

        def trickle():
            s = ChannelSender(f"127.0.0.1:{port}", "w", window=8,
                              registry=reg)
            try:
                s.send(np.frombuffer(_blob_frame(
                    {"v": 2, "kind": "manifest", "chunks": 3,
                     "total": 277, "blob": blob_id}), np.uint8),
                    sync=True, timeout=30)
                for i, p in enumerate(payloads):
                    time.sleep(0.4)         # slower than the 0.25 poll
                    s.send(np.frombuffer(_blob_frame(
                        {"v": 2, "kind": "chunk", "blob": blob_id,
                         "i": i}, p), np.uint8), sync=True, timeout=30)
            finally:
                s.close(drain=False)

        ct = threading.Thread(target=consume, daemon=True)
        st = threading.Thread(target=trickle, daemon=True)
        try:
            ct.start()
            st.start()
            st.join(timeout=30)
            ct.join(timeout=30)
            done.set()
            assert landed.get("blob") == b"".join(payloads)
        finally:
            done.set()
            hub.stop()

    def test_aborted_reassembly_resyncs_discarding_stale_chunks(self):
        """A reassembly aborted mid-blob (dead seeder) leaves the
        already-queued stragglers on the lane; the NEXT recv_bytes
        identifies them by blob id and discards them instead of
        misparsing them as standalone blobs — the lane re-synchronizes
        and a fresh ship lands intact."""
        hub, port, reg = self._hub()
        recv = hub.receiver("w")
        stale_id = "deaddeaddeaddead"
        fresh = np.random.RandomState(7).bytes(300 * 1024)
        try:
            s = ChannelSender(f"127.0.0.1:{port}", "w", window=8,
                              registry=reg)
            # manifest promising 3 chunks, only one delivered: the
            # committed reassembly times out on chunk 1
            s.send(np.frombuffer(_blob_frame(
                {"v": 2, "kind": "manifest", "chunks": 3,
                 "total": 300, "blob": stale_id}), np.uint8),
                sync=True, timeout=30)
            s.send(np.frombuffer(_blob_frame(
                {"v": 2, "kind": "chunk", "blob": stale_id, "i": 0},
                b"x" * 100), np.uint8), sync=True, timeout=30)
            with pytest.raises(ChannelError):
                recv.recv_bytes(timeout=5, chunk_timeout=0.2)
            # the dead blob's stragglers arrive late, then a fresh blob
            s.send(np.frombuffer(_blob_frame(
                {"v": 2, "kind": "chunk", "blob": stale_id, "i": 1},
                b"y" * 100), np.uint8), sync=True, timeout=30)
            s.send(np.frombuffer(_blob_frame(
                {"v": 2, "kind": "chunk", "blob": stale_id, "i": 2},
                b"z" * 100), np.uint8), sync=True, timeout=30)
            s.send_bytes(fresh, sync=True, timeout=30,
                         chunk_bytes=64 * 1024)
            assert recv.recv_bytes(timeout=30) == fresh
            s.close(drain=False)
        finally:
            hub.stop()

    def test_new_manifest_mid_blob_restarts_reassembly(self):
        """A sender that gave up and re-shipped: a fresh manifest
        arriving mid-reassembly restarts on the new blob instead of
        erroring (or worse, splicing two blobs together)."""
        hub, port, reg = self._hub()
        recv = hub.receiver("w")
        fresh = np.random.RandomState(9).bytes(200 * 1024)
        try:
            s = ChannelSender(f"127.0.0.1:{port}", "w", window=8,
                              registry=reg)
            s.send(np.frombuffer(_blob_frame(
                {"v": 2, "kind": "manifest", "chunks": 2,
                 "total": 200, "blob": "0011223344556677"}), np.uint8),
                sync=True, timeout=30)
            s.send(np.frombuffer(_blob_frame(
                {"v": 2, "kind": "chunk", "blob": "0011223344556677",
                 "i": 0}, b"q" * 100), np.uint8), sync=True, timeout=30)
            s.send_bytes(fresh, sync=True, timeout=30,
                         chunk_bytes=64 * 1024)
            assert recv.recv_bytes(timeout=30) == fresh
            s.close(drain=False)
        finally:
            hub.stop()

    def test_disconnect_mid_blob_resumes_zero_dup_zero_drop(self):
        """Sever the socket repeatedly DURING a chunked transfer: the
        sender reconnects and resumes at the receiver's seq, and the
        landed bytes equal the shipped bytes exactly — a 30 GB ship
        that drops at 29 GB re-sends chunks, not the blob."""
        hub, port, reg = self._hub()
        recv = hub.receiver("w")
        # 24 chunks + manifest > hub capacity (8) + window (2): with no
        # consumer draining, the sender is GUARANTEED blocked mid-blob
        # when the severs land
        blob = np.random.RandomState(1).bytes(768 * 1024)
        landed = {}
        sent = {}

        def send():
            s = ChannelSender(f"127.0.0.1:{port}", "w", window=2,
                              registry=reg)
            try:
                s.send_bytes(blob, sync=True, timeout=60,
                             chunk_bytes=32 * 1024)
                sent["ok"] = True
            finally:
                s.close(drain=False)

        def consume():
            landed["blob"] = recv.recv_bytes(timeout=60)

        st = threading.Thread(target=send, daemon=True)
        try:
            st.start()
            time.sleep(0.2)                     # sender now wedged mid-blob
            assert st.is_alive()
            hub.disconnect_all()
            time.sleep(0.05)
            hub.disconnect_all()
            ct = threading.Thread(target=consume, daemon=True)
            ct.start()
            st.join(timeout=60)
            ct.join(timeout=60)
            assert sent.get("ok") and landed.get("blob") == blob
            assert reg.counter("tony_channel_reconnects_total",
                               channel="w").value >= 1
        finally:
            hub.stop()


# ---------------------------------------------------------------------------
# Self-organizing fan-out
# ---------------------------------------------------------------------------
class TestWarmFanout:
    def test_log2_waves_from_one_seed(self):
        shipped = []
        res = warm_fanout([f"t{i}" for i in range(8)],
                          lambda src, dst: shipped.append((src, dst)),
                          seeders=["seed"])
        assert not res["failed"] and not res["fallback"]
        assert len(res["warmed"]) == 8 and res["ships"] == 8
        # 1 -> 2 -> 4 -> 8 seeders: ceil(log2(8+1)) = 4 waves, not 8
        assert res["waves"] == 4

    def test_cold_start_mints_seed_then_fans_out(self):
        loads = []
        res = warm_fanout([f"t{i}" for i in range(8)],
                          lambda src, dst: None,
                          fallback=loads.append)
        assert loads == ["t0"]                  # ONE storage load
        assert res["waves"] == 4 and res["ships"] == 7
        assert res["fallback"] == ["t0"] and len(res["warmed"]) == 7

    def test_crashed_seeder_condemned_target_retries(self):
        calls = []

        def ship(src, dst):
            calls.append((src, dst))
            if src == "dead":
                raise RuntimeError("seeder crashed mid-ship")

        loads = []
        res = warm_fanout(["t0", "t1"], ship, seeders=["dead"],
                          fallback=loads.append)
        assert not res["failed"]
        assert loads == ["t0"]                  # fallback minted a seed
        assert ("dead", "t0") in calls          # the failed attempt
        assert sorted(res["warmed"] + res["fallback"]) == ["t0", "t1"]

    def test_no_fallback_reports_failed_without_wedging(self):
        res = warm_fanout(["t0", "t1"],
                          lambda s, d: (_ for _ in ()).throw(
                              RuntimeError("boom")),
                          seeders=["dead"])
        assert res["failed"] == ["t0", "t1"] and not res["warmed"]

    def test_failing_fallback_reports_failed_never_raises(self):
        """The chaos case the fleet controller ships: a storage load
        that ITSELF fails moves its target to ``failed`` (for the
        controller's release path) and the wave loop keeps warming —
        it never propagates out of _scale_up / rolling_upgrade."""
        attempts = []

        def fallback(dst):
            attempts.append(dst)
            if len(attempts) == 1:
                raise OSError("storage load failed")

        res = warm_fanout(["t0", "t1", "t2"], lambda src, dst: None,
                          fallback=fallback)
        assert res["failed"] == ["t0"]          # the failed load's target
        assert res["fallback"] == ["t1"]        # retry minted a seeder
        assert res["warmed"] == ["t2"]          # and fan-out resumed
        assert attempts == ["t0", "t1"]

    def test_fallback_always_failing_terminates(self):
        def fallback(dst):
            raise OSError("storage down")

        res = warm_fanout(["t0", "t1"], lambda src, dst: None,
                          fallback=fallback)
        assert res["failed"] == ["t0", "t1"]
        assert not res["warmed"] and not res["fallback"]


# ---------------------------------------------------------------------------
# Live server: advertise, pull, bit-identical serving
# ---------------------------------------------------------------------------
class TestLiveServerWarmBoot:
    def _prompts(self, seed, sizes):
        rng = np.random.RandomState(seed)
        return [[int(t) for t in rng.randint(0, CFG.vocab_size, size=n)]
                for n in sizes]

    def test_hello_advertises_and_pull_lands_verified(self, params):
        srv = ServingServer(
            ContinuousBatcher(params, CFG, batch=2, max_len=32, chunk=3),
            registry=MetricsRegistry())
        port = srv.start()
        addr = f"127.0.0.1:{port}"
        try:
            digest = srv.weights_digest
            assert isinstance(digest, str) and len(digest) == 64
            assert digest == tree_digest(params)
            listed = weights_rpc(addr, {"op": "list"})
            assert listed["ok"]
            hello = listed["_hello"]
            assert hello["weights_digest"] == digest
            assert digest in listed["resident"]
            meta, tree = pull_weights(addr, timeout_s=60)
            assert meta["digest"] == digest
            assert tree_digest(tree) == digest
        finally:
            srv.stop()

    def test_unknown_digest_fails_request_not_replica(self, params):
        srv = ServingServer(
            ContinuousBatcher(params, CFG, batch=2, max_len=32, chunk=3),
            registry=MetricsRegistry())
        port = srv.start()
        addr = f"127.0.0.1:{port}"
        try:
            res = weights_rpc(addr, {"op": "publish", "digest": "0" * 64,
                                     "target": "127.0.0.1:1"})
            assert not res["ok"]
            # the replica survived the bad request
            assert weights_rpc(addr, {"op": "list"})["ok"]
        finally:
            srv.stop()

    def test_ship_warmed_tokens_bit_identical_greedy_and_sampled(
            self, params):
        """THE acceptance gate: a replica serving pulled (ship-warmed)
        weights emits exactly the tokens a storage-loaded replica
        does, greedy AND sampled."""
        srv = ServingServer(
            ContinuousBatcher(params, CFG, batch=2, max_len=32, chunk=3),
            registry=MetricsRegistry())
        port = srv.start()
        try:
            _, pulled = pull_weights(f"127.0.0.1:{port}", timeout_s=60)
        finally:
            srv.stop()
        prompts = self._prompts(11, [4, 6, 3])
        for kw in ({},                               # greedy
                   {"temperature": 0.9, "top_k": 12, "top_p": 0.95,
                    "seed": 11}):                    # sampled
            want = ContinuousBatcher(params, CFG, batch=2, max_len=32,
                                     chunk=3, **kw).serve(prompts, 6)
            got = ContinuousBatcher(pulled, CFG, batch=2, max_len=32,
                                    chunk=3, **kw).serve(prompts, 6)
            assert got == want, kw


# ---------------------------------------------------------------------------
# Lazy export: HELLO/STATS never pay (or pin) the params pack
# ---------------------------------------------------------------------------
class TestLazyExport:
    def test_resident_view_never_triggers_export(self):
        """The first client HELLO must not synchronously pack a
        multi-GB host copy of the params: resident_digests() (what
        HELLO/STATS advertise) never runs the exporter; digests()
        (the seed-intent list/publish path) runs it exactly once."""
        calls = []

        def exporter():
            calls.append(1)
            return pack_weights(_tree())

        store = WeightStore(MetricsRegistry(), exporter=exporter)
        assert store.resident_digests() == []
        assert store.resident_digests() == []
        assert not calls                        # advertising is free
        d = tree_digest(_tree())
        assert store.digests() == [d]           # seed intent: exports
        assert len(calls) == 1
        assert store.digests() == [d]           # ... exactly once
        assert len(calls) == 1
        assert store.resident_digests() == [d]

    def test_live_hello_and_resident_op_do_not_export(self, params):
        """End-to-end: a fresh server's HELLO advertises an EMPTY
        resident list (plus its precomputed weights_digest — the
        seedability signal); the 'resident' op stays non-exporting;
        the 'list' op is the moment the export runs."""
        srv = ServingServer(
            ContinuousBatcher(params, CFG, batch=2, max_len=32, chunk=3),
            registry=MetricsRegistry())
        port = srv.start()
        addr = f"127.0.0.1:{port}"
        try:
            digest = srv.weights_digest
            res = weights_rpc(addr, {"op": "resident"})
            assert res["ok"] and res["resident"] == []
            assert res["_hello"]["weights_resident"] == []
            assert res["_hello"]["weights_digest"] == digest
            listed = weights_rpc(addr, {"op": "list"})
            assert digest in listed["resident"]
            res2 = weights_rpc(addr, {"op": "resident"})
            assert digest in res2["resident"]
            assert digest in res2["_hello"]["weights_resident"]
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# The advertised pull-back address (cross-host warm boot)
# ---------------------------------------------------------------------------
class TestPullAdvertiseHost:
    def test_reachable_host_toward_loopback_is_loopback(self):
        from tony_tpu.serving.weightstore import _reachable_host
        assert _reachable_host("127.0.0.1:9") == "127.0.0.1"

    def test_reachable_host_falls_back_on_unresolvable_peer(self):
        from tony_tpu.serving.weightstore import _reachable_host
        assert _reachable_host("host.invalid:1",
                               default="203.0.113.1") == "203.0.113.1"

    @pytest.mark.parametrize("advertise,expect", [
        (None, "192.0.2.55"),           # derived from the seeder route
        ("203.0.113.7", "203.0.113.7"),  # explicit override wins
    ], ids=["derived", "explicit"])
    def test_pull_advertises_reachable_target(self, monkeypatch,
                                              advertise, expect):
        """The cross-host regression: pull_weights must advertise an
        address the SEEDER can reach — never a hard-coded loopback
        that would have a remote seeder ship the artifact to itself."""
        from tony_tpu.serving import weightstore as ws
        blob = pack_weights(_tree())
        digest = peek_weights_meta(blob)["digest"]
        captured = {}
        probed = []

        def fake_reachable(peer, default="127.0.0.1"):
            probed.append(peer)
            return "192.0.2.55"

        monkeypatch.setattr(ws, "_reachable_host", fake_reachable)

        def fake_rpc(addr, body, timeout_s=30.0):
            if body["op"] == "list":
                return {"ok": True, "resident": [digest], "_hello": {}}
            assert body["op"] == "publish"
            captured["target"] = body["target"]
            host, port = body["target"].rsplit(":", 1)

            def ship():
                s = ChannelSender(f"127.0.0.1:{port}", WEIGHT_CHANNEL,
                                  registry=MetricsRegistry())
                try:
                    s.send_bytes(blob, sync=True, timeout=30)
                finally:
                    s.close(drain=False)

            threading.Thread(target=ship, daemon=True).start()
            return {"ok": True, "digest": digest, "_hello": {}}

        monkeypatch.setattr(ws, "weights_rpc", fake_rpc)
        meta, tree = pull_weights("198.51.100.2:4242", timeout_s=30,
                                  advertise_host=advertise)
        assert meta["digest"] == digest
        assert tree_digest(tree) == digest
        assert captured["target"].rsplit(":", 1)[0] == expect
        # the route probe names the seeder; an explicit host skips it
        assert probed == ([] if advertise else ["198.51.100.2:4242"])


# ---------------------------------------------------------------------------
# Compiled-program artifacts
# ---------------------------------------------------------------------------
class TestCompileCache:
    def _seed_dir(self, tmp_path):
        src = tmp_path / "cache"
        (src / "sub").mkdir(parents=True)
        (src / "a.bin").write_bytes(b"\x01\x02xla")
        (src / "sub" / "b.bin").write_bytes(b"\x03" * 100)
        return str(src)

    def test_pack_install_round_trip(self, tmp_path):
        src = self._seed_dir(tmp_path)
        blob = pack_compile_cache(src, version="v1")
        dst = str(tmp_path / "landed")
        meta = install_compile_cache(blob, dst)
        assert meta["digest"] == dir_digest(src) == dir_digest(dst)
        assert open(os.path.join(dst, "sub", "b.bin"), "rb").read() \
            == b"\x03" * 100

    def test_flipped_byte_refused(self, tmp_path):
        """A corrupt transfer raises instead of being trusted as a
        trace cache (the landing is verified AFTER the write; nothing
        already resident is deleted)."""
        blob = bytearray(pack_compile_cache(self._seed_dir(tmp_path)))
        blob[-7] ^= 0x20
        with pytest.raises(ProtocolError, match="landed dirty"):
            install_compile_cache(bytes(blob), str(tmp_path / "landed"))

    def test_corrupt_blob_refused_at_put(self, tmp_path):
        """put() digest-verifies compile-cache artifacts too: a
        corrupt blob can never land resident (counted as an install)
        and be re-seeded peer-to-peer — corruption is caught at the
        store, not later at every target's install."""
        reg = MetricsRegistry()
        store = WeightStore(reg)
        good = pack_compile_cache(self._seed_dir(tmp_path))
        bad = bytearray(good)
        bad[-7] ^= 0x20
        with pytest.raises(ProtocolError, match="REFUSED"):
            store.put(bytes(bad))
        assert store.resident_digests() == []
        assert reg.counter("tony_weight_installs_total").value == 0
        digest = store.put(good)
        assert store.get(digest) == good        # a compile-cache hit
        assert reg.counter("tony_compile_cache_hits_total").value == 1

    def test_one_cache_placed_from_outside(self, tmp_path, monkeypatch):
        """runtime/compile_cache.py is the tree's one writer of
        ``jax_compilation_cache_dir``: with JAX_COMPILATION_CACHE_DIR set
        it sets nothing (whoever launched the process placed the cache);
        otherwise the checkout's own ``.jax_cache`` — derived from the
        package, not from the working directory — or the dir a shipped
        artifact names."""
        import jax
        from tony_tpu.runtime import compile_cache
        from tony_tpu.serving.weightstore import attach_compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.chdir(tmp_path)
        try:
            monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "env"))
            assert compile_cache.enable() == str(tmp_path / "env")
            assert attach_compile_cache(str(tmp_path / "x")) \
                == str(tmp_path / "env")
            assert jax.config.jax_compilation_cache_dir == before
            monkeypatch.delenv(compile_cache.ENV_VAR)
            repo = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            assert compile_cache.enable() == os.path.join(repo,
                                                          ".jax_cache")
            assert jax.config.jax_compilation_cache_dir \
                == os.path.join(repo, ".jax_cache")
            assert attach_compile_cache(str(tmp_path / "x")) \
                == str(tmp_path / "x")
            assert jax.config.jax_compilation_cache_dir \
                == str(tmp_path / "x")
            assert attach_compile_cache("") == ""       # none configured
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert compile_cache.stats().startswith("compile cache: ")


# ---------------------------------------------------------------------------
# Bench pins
# ---------------------------------------------------------------------------
class TestBenchArm:
    def test_weight_ship_arm_pins(self):
        import bench
        out = bench._weight_ship_arm()
        # ship-warmed replica ready >= 2x faster than cold start
        assert out["serving_scaleup_warm_vs_cold"] >= 2, out
        # one seed load + O(log N) fan-out beats N serial loads
        assert out["serving_upgrade_wall_vs_serial_loads"] > 1, out
        assert out["serving_warm_waves"] == 4, out      # 1 + log2(8)
        assert out["serving_warm_storage_loads"] == 1, out
        assert out["serving_scaleup_to_first_token_s"] > 0
