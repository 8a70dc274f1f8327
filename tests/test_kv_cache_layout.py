"""One representation of the K/V cache for every configuration: heads and
head_dim stored merged ([L, B, rows, KV·hd]), the 5-D form kept on the wire.
Cached decode against the training forward over head geometry x read path x
write path; int8 and rolling caches; old-wire-shape packages and templates."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import transformer as T
from tony_tpu.models.decode import generate, init_kv_cache


class TestMergedCacheEquivalence:
    """The cache stores heads and head_dim merged ([L, B, rows, KV·hd],
    ``init_kv_cache``) for EVERY configuration: cached decode must equal
    the training forward token for token whatever the head geometry
    (MHA head_dim 96 — the lane-padding case the representation exists
    for — and GQA head_dim 128), the read path (dense, blockwise) and
    the write path (contiguous slice, per-row scatter, bounded window);
    and what crosses a process boundary keeps the old 5-D wire form."""

    GEOMETRY = {
        # heads x head_dim: 2 x 96 (MHA), 4 query / 2 KV heads x 128
        "mha96": dict(d_model=192, n_heads=2, n_kv_heads=2),
        "gqa128": dict(d_model=512, n_heads=4, n_kv_heads=2),
    }
    STEPS = 4

    @classmethod
    def _cfg(cls, geometry, **extra):
        return T.TransformerConfig(
            vocab_size=64, n_layers=2, d_ff=128, max_seq=1024,
            dtype=jnp.float32, remat=False, **cls.GEOMETRY[geometry],
            **extra)

    @staticmethod
    def _forward_greedy(p, cfg, prompts, steps):
        """Reference: the training forward re-run per token on the
        batch right-padded to ONE width (causal, so each row's last REAL
        position is independent of its padding; one compile). Returns
        (tokens [B, steps], logits [B, steps, V])."""
        forward = jax.jit(T.forward, static_argnames=("cfg",))
        seqs = [list(r) for r in prompts]
        toks, lgs = [], []
        for _ in range(steps):
            batch = np.zeros((len(seqs), 16), np.int32)
            for i, s in enumerate(seqs):
                batch[i, :len(s)] = s
            logits, _ = forward(p, jnp.asarray(batch), cfg=cfg)
            last = logits[jnp.arange(len(seqs)),
                          jnp.asarray([len(s) - 1 for s in seqs])]
            nxt = np.asarray(jnp.argmax(last, axis=-1))
            for s, t in zip(seqs, nxt):
                s.append(int(t))
            toks.append(nxt)
            lgs.append(np.asarray(last))
        return np.stack(toks, 1), np.stack(lgs, 1)

    @classmethod
    def _cached_greedy(cls, p, cfg, prompts, max_len, write, steps):
        """Greedy decode off the cache. ``write``: "scalar" (uniform
        prompts, scalar frontier: contiguous-slice writes), "per_row"
        (bucketed prefill, per-row frontiers: the unique scatter) or
        "window" (per-row frontiers, bounded-window write)."""
        from tony_tpu.models import decode as D
        step = jax.jit(D.decode_step, static_argnames=("cfg", "window"))
        lens = [len(r) for r in prompts]
        if write == "scalar":
            assert len(set(lens)) == 1
            lg, cache = jax.jit(D.prefill, static_argnames=(
                "cfg", "max_len"))(p, jnp.asarray(prompts, jnp.int32),
                                   cfg=cfg, max_len=max_len)
        else:
            bucket = 8
            padded = np.zeros((len(prompts), bucket), np.int32)
            for i, r in enumerate(prompts):
                padded[i, :len(r)] = r
            lengths = jnp.asarray(lens, jnp.int32)
            lg, mini = jax.jit(D.prefill_rows, static_argnames=("cfg",))(
                p, jnp.asarray(padded), lengths, cfg=cfg)
            cache = D.init_kv_cache(cfg, len(prompts), max_len)
            cache = dict(cache, length=jnp.zeros((len(prompts),),
                                                 jnp.int32))
            cache = D.place_rows(cache, mini,
                                 jnp.arange(len(prompts)), lengths)
        window = 8 if write == "window" else None
        toks, lgs = [], []
        for _ in range(steps):
            lgs.append(np.asarray(lg))
            tok = jnp.argmax(lg, axis=-1)
            toks.append(np.asarray(tok))
            lg, cache = step(p, tok, cache, cache["length"], cfg=cfg,
                             window=window)
        assert cache["k"].ndim == 4          # merged, in every mode
        return np.stack(toks, 1), np.stack(lgs, 1)

    @pytest.mark.parametrize("write", ["scalar", "per_row", "window"])
    @pytest.mark.parametrize("max_len", [48, 640])       # dense, blockwise
    @pytest.mark.parametrize("geometry", sorted(GEOMETRY))
    def test_cached_decode_equals_forward(self, geometry, max_len, write):
        from tony_tpu.models import decode as D
        assert (max_len >= D._BLOCKWISE_MIN_LEN) == (max_len == 640)
        cfg = self._cfg(geometry)
        p = T.init_params(jax.random.PRNGKey(3), cfg)
        rs = np.random.RandomState(11)
        lens = (5, 5, 5) if write == "scalar" else (5, 3, 6)
        prompts = [list(rs.randint(0, cfg.vocab_size, size=n))
                   for n in lens]
        want_t, want_l = self._forward_greedy(p, cfg, prompts, self.STEPS)
        got_t, got_l = self._cached_greedy(p, cfg, prompts, max_len,
                                           write, self.STEPS)
        np.testing.assert_array_equal(got_t, want_t)
        np.testing.assert_allclose(got_l, want_l, rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("n_q", [1, 3])
    @pytest.mark.parametrize("geometry", sorted(GEOMETRY))
    def test_stored_row_contraction_equals_per_head_einsum(self, geometry,
                                                           n_q):
        """The read helpers contract the WHOLE stored row against a
        block-diagonal q (no head split, no transposition): the same
        numbers as the per-head einsums over the [B, S, KV, hd] view."""
        from tony_tpu.models import decode as D
        g = self.GEOMETRY[geometry]
        h, kv = g["n_heads"], g["n_kv_heads"]
        d = g["d_model"] // h
        ks = jax.random.split(jax.random.PRNGKey(8), 3)
        q = jax.random.normal(ks[0], (2, n_q, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (2, 40, kv, d), jnp.float32)
        v = jax.random.normal(ks[2], (2, 40, kv, d), jnp.float32)
        qg = q.reshape(2, n_q, kv, h // kv, d)
        want_s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k)
        got_s = D._head_scores(D._spread_queries(q, kv), D._kv_flat(k))
        assert got_s.shape == (2, kv, h // kv, n_q, 40)
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                                   rtol=1e-5, atol=1e-5)
        p = jax.nn.softmax(want_s, axis=-1)
        want_o = jnp.einsum("bkgqs,bskd->bkgqd", p, v)
        got_o = D._head_values(p, D._kv_flat(v))
        np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                                   rtol=1e-5, atol=1e-5)

    def test_int8_cache_merged_scales(self):
        """int8: the scale buffers follow the same rule ([L, B, rows,
        KV]); chunked == sequential through the merged writes, and the
        first greedy token equals the float cache's."""
        from tony_tpu.models import decode as D
        cfg = self._cfg("mha96", kv_cache_dtype="int8")
        p = T.init_params(jax.random.PRNGKey(3), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 6), 0,
                                    cfg.vocab_size)
        pre = jax.jit(D.prefill, static_argnames=("cfg", "max_len"))
        lg, cache = pre(p, prompt, cfg=cfg, max_len=640)
        assert cache["k"].shape == (2, 2, 640, 2 * 96)
        assert cache["k_scale"].shape == (2, 2, 640, 2)
        lg_f, _ = pre(p, prompt, cfg=self._cfg("mha96"), max_len=640)
        np.testing.assert_array_equal(np.asarray(jnp.argmax(lg, -1)),
                                      np.asarray(jnp.argmax(lg_f, -1)))
        toks = jax.random.randint(jax.random.PRNGKey(5), (2, 3), 0,
                                  cfg.vocab_size)
        lg_chunk, c1 = jax.jit(D.extend_step, static_argnames=("cfg",))(
            p, toks, cache, 6, cfg=cfg)
        step = jax.jit(D.decode_step, static_argnames=("cfg",))
        c2 = cache
        for i in range(3):
            lg_i, c2 = step(p, toks[:, i], c2, 6 + i, cfg=cfg)
            np.testing.assert_allclose(np.asarray(lg_chunk[:, i]),
                                       np.asarray(lg_i), rtol=2e-4,
                                       atol=2e-4)
        np.testing.assert_array_equal(np.asarray(c1["k"][0]),
                                      np.asarray(c2["k"][0]))

    def test_ring_cache_merged(self):
        """Rolling cache at head_dim 96: greedy generate past the
        capacity equals the windowed linear cache's, token for token."""
        lin = self._cfg("mha96", attn_window=12)
        ring = self._cfg("mha96", attn_window=12, kv_cache_capacity=16)
        p = T.init_params(jax.random.PRNGKey(3), lin)
        prompt = jax.random.randint(jax.random.PRNGKey(6), (2, 20), 0,
                                    lin.vocab_size)
        want = generate(p, prompt, lin, 14, jax.random.PRNGKey(0)).tokens
        got = generate(p, prompt, ring, 14, jax.random.PRNGKey(0)).tokens
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert init_kv_cache(ring, 2, 999)["k"].shape == (2, 2, 16, 192)

    @staticmethod
    def _old_wire(stored, cfg, row, width):
        """[L, 1, w, KV, hd]: what a replica of the 5-D representation
        shipped — written out here with numpy, not with the program's
        own ``kv_to_wire``."""
        return {n: np.asarray(a)[:, row:row + 1, :width].reshape(
                    a.shape[0], 1, width, cfg.kv_heads, -1)
                for n, a in stored.items()}

    @pytest.mark.parametrize("geometry", sorted(GEOMETRY))
    def test_old_wire_package_lands_and_decodes(self, geometry):
        """A ``KVPackage`` in the wire shape the 5-D cache shipped lands
        (``land_kv_rows``) and decodes to the tokens colocated serving
        gives the same prompt."""
        from tony_tpu.models import decode as D
        from tony_tpu.models.serve import (ContinuousBatcher, KVPackage,
                                           ServeEngine)
        cfg = self._cfg(geometry)
        p = T.init_params(jax.random.PRNGKey(3), cfg)
        prompt = [7, 3, 9, 1, 4]
        want = ContinuousBatcher(p, cfg, batch=2, max_len=32,
                                 chunk=2).serve([prompt], 6)[0]
        lg, mini = D.prefill(p, jnp.asarray([prompt], jnp.int32), cfg,
                             max_len=len(prompt))
        bufs = self._old_wire(D._kv_bufs(mini), cfg, 0, len(prompt))
        assert bufs["k"].shape == (2, 1, 5, cfg.kv_heads, cfg.head_dim)
        b = ContinuousBatcher(p, cfg, batch=2, max_len=32, chunk=2)
        got = []
        engine = ServeEngine(
            b, on_delta=lambda rid, toks: got.extend(toks),
            on_retired=lambda rid, reason, n, final: got.extend(final))
        engine.submit_prefilled(0, KVPackage(
            bufs, len(prompt), np.asarray(lg)[0],
            np.asarray(b._req_key(0), np.uint32)), 6)
        engine.drain()
        engine.run()
        assert b.prefill_forward_tokens == 0
        assert got == want
        # a package in the STORED shape is not a wire package
        flat = KVPackage(D.kv_from_wire(bufs), len(prompt),
                         np.asarray(lg)[0],
                         np.asarray(b._req_key(0), np.uint32))
        with pytest.raises(ValueError, match="wire layout"):
            b._validate_package(flat, 6)

    @pytest.mark.parametrize("geometry", sorted(GEOMETRY))
    def test_old_wire_template_installs_and_decodes(self, geometry):
        """A shipped prefix template in the old wire shape installs with
        zero prefix forwards, serves a continuation token-identically
        to prefix-blind serving, and is exported in that same shape."""
        from tony_tpu.models import decode as D
        from tony_tpu.models.serve import ContinuousBatcher
        from tony_tpu.serving import kvship
        cfg = self._cfg(geometry)
        p = T.init_params(jax.random.PRNGKey(3), cfg)
        prefix, suffix = [7, 3, 9, 1, 4, 8], [2, 5]
        want = ContinuousBatcher(p, cfg, batch=2, max_len=32,
                                 chunk=2).serve([prefix + suffix], 6)[0]
        _, mini = D.prefill(p, jnp.asarray([prefix], jnp.int32), cfg,
                            max_len=len(prefix))
        bufs = self._old_wire(D._kv_bufs(mini), cfg, 0, len(prefix))
        b = ContinuousBatcher(p, cfg, batch=2, max_len=32, chunk=2)
        meta = {"id": "sys", "tokens": prefix, "vocab": cfg.vocab_size}
        assert b.install_prefix_template(meta, bufs) == "sys"
        assert b._prefix_store["sys"].template["k"].ndim == 4
        assert b.serve([prefix + suffix], 6)[0] == want
        assert b.prefill_forward_tokens == len(suffix)
        _, shipped = kvship.unpack_template(b.export_prefix_blob("sys"))
        for n, a in bufs.items():
            assert shipped[n].shape == a.shape
            assert (shipped[n] == a).all(), n
        with pytest.raises(ValueError, match="wire layout"):
            b.install_prefix_template(dict(meta, id="flat"),
                                      D.kv_from_wire(bufs))
