"""KV-cache decode tests: greedy equivalence with the full forward,
sampling shapes, cache semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import transformer as T
from tony_tpu.models.decode import (decode_step, generate, init_kv_cache,
                                    prefill)

CFG = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def full_forward_greedy(params, prompt, steps, cfg=CFG):
    """Reference decode: re-run the full forward for every token."""
    tokens = prompt
    for _ in range(steps):
        logits, _ = T.forward(params, tokens, cfg)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    return tokens


class TestDecode:
    def test_prefill_matches_forward_last_logits(self, params):
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 7), 0,
                                    CFG.vocab_size)
        logits_full, _ = T.forward(params, prompt, CFG)
        logits_pre, cache = prefill(params, prompt, CFG, max_len=16)
        np.testing.assert_allclose(np.asarray(logits_pre),
                                   np.asarray(logits_full[:, -1]),
                                   rtol=2e-4, atol=2e-4)
        assert int(cache["length"]) == 7

    def test_decode_step_matches_full_forward(self, params):
        """A cached step must produce the same logits as re-running the
        whole sequence through the training forward."""
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 5), 0,
                                    CFG.vocab_size)
        _, cache = prefill(params, prompt, CFG, max_len=12)
        nxt = jnp.array([3, 7])
        logits_cached, cache = decode_step(params, nxt, cache,
                                           cache["length"], CFG)
        extended = jnp.concatenate([prompt, nxt[:, None]], axis=1)
        logits_full, _ = T.forward(params, extended, CFG)
        np.testing.assert_allclose(np.asarray(logits_cached),
                                   np.asarray(logits_full[:, -1]),
                                   rtol=2e-4, atol=2e-4)
        assert int(cache["length"]) == 6

    @pytest.mark.slow
    def test_greedy_generate_equals_full_forward_loop(self, params):
        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 4), 0,
                                    CFG.vocab_size)
        out = generate(params, prompt, CFG, max_new_tokens=6,
                       rng=jax.random.PRNGKey(0), temperature=0.0)
        expected = full_forward_greedy(params, prompt, 6)
        np.testing.assert_array_equal(np.asarray(out.tokens),
                                      np.asarray(expected))
        assert out.tokens.shape == (2, 10)
        assert out.logprobs.shape == (2, 6)
        assert bool((out.logprobs <= 0).all())

    def test_sampled_generate_shapes_and_validity(self, params):
        prompt = jax.random.randint(jax.random.PRNGKey(4), (3, 4), 0,
                                    CFG.vocab_size)
        out = generate(params, prompt, CFG, max_new_tokens=5,
                       rng=jax.random.PRNGKey(7), temperature=0.8, top_k=50)
        assert out.tokens.shape == (3, 9)
        gen = np.asarray(out.tokens[:, 4:])
        assert (gen >= 0).all() and (gen < CFG.vocab_size).all()
        # Different seeds give different samples (overwhelmingly likely).
        out2 = generate(params, prompt, CFG, max_new_tokens=5,
                        rng=jax.random.PRNGKey(8), temperature=0.8,
                        top_k=50)
        assert not np.array_equal(np.asarray(out.tokens),
                                  np.asarray(out2.tokens))

    def test_nucleus_sampling_respects_the_nucleus(self, params):
        """Every top-p sample lies inside the nucleus a numpy reference
        computes from the same logits (smallest prefix of the
        temperature-scaled distribution reaching p, crossing token
        kept); a tiny p degenerates to greedy argmax."""
        from tony_tpu.models.decode import _sample

        logits = jax.random.normal(jax.random.PRNGKey(3),
                                   (4, CFG.vocab_size)) * 3.0
        temp, p = 0.7, 0.6
        scaled = np.asarray(logits, np.float64) / temp
        exp = np.exp(scaled - scaled.max(axis=-1, keepdims=True))
        probs = exp / exp.sum(axis=-1, keepdims=True)
        nuclei = []
        for row in probs:
            order = np.argsort(-row)
            cum = np.cumsum(row[order])
            keep = (cum - row[order]) < p
            nuclei.append(set(order[keep].tolist()))
        for seed in range(20):
            tok, logp = _sample(logits, jax.random.PRNGKey(seed),
                                temperature=temp, top_k=0, top_p=p)
            for r in range(4):
                assert int(tok[r]) in nuclei[r], (seed, r)
            assert np.all(np.isfinite(np.asarray(logp)))
        # p -> 0 keeps only the argmax (position 0 is always kept)
        tok, _ = _sample(logits, jax.random.PRNGKey(0), temperature=temp,
                         top_k=0, top_p=1e-9)
        np.testing.assert_array_equal(
            np.asarray(tok), np.asarray(jnp.argmax(logits, axis=-1)))

    def test_nucleus_generate_end_to_end(self, params):
        """top_p threads through generate(): valid tokens, and a tiny
        nucleus reproduces greedy decoding despite temperature > 0."""
        prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 4), 0,
                                    CFG.vocab_size)
        out = generate(params, prompt, CFG, max_new_tokens=5,
                       rng=jax.random.PRNGKey(7), temperature=0.9,
                       top_p=0.8)
        gen = np.asarray(out.tokens[:, 4:])
        assert (gen >= 0).all() and (gen < CFG.vocab_size).all()
        greedy = generate(params, prompt, CFG, max_new_tokens=5,
                          rng=jax.random.PRNGKey(7), temperature=0.0)
        tiny = generate(params, prompt, CFG, max_new_tokens=5,
                        rng=jax.random.PRNGKey(7), temperature=0.9,
                        top_p=1e-9)
        np.testing.assert_array_equal(np.asarray(tiny.tokens),
                                      np.asarray(greedy.tokens))

    def test_cache_shapes(self):
        cache = init_kv_cache(CFG, batch=2, max_len=32)
        # heads and head_dim are stored merged (init_kv_cache)
        assert cache["k"].shape == (CFG.n_layers, 2, 32,
                                    CFG.n_heads * CFG.head_dim)
        assert cache["k"].dtype == CFG.dtype

    def test_flash_safe_len_boundaries(self):
        """The TPU flash kernels' alignment rule prefill pads to: free up
        to 256, 256-multiples to 1024, 1024-multiples beyond."""
        from tony_tpu.models.decode import _flash_safe_len

        assert [_flash_safe_len(s) for s in (1, 100, 256)] == [1, 100, 256]
        assert [_flash_safe_len(s) for s in (257, 300, 512, 1000)] == \
            [512, 512, 512, 1024]
        assert [_flash_safe_len(s) for s in (1024, 1025, 1056, 2048,
                                             2049)] == \
            [1024, 2048, 2048, 2048, 3072]

    def test_prefill_padding_preserves_outputs(self, params, monkeypatch):
        """The prompt-padding path (TPU flash alignment; forced here on
        CPU through the _pad_prompts seam): padded prefill produces the
        same logits, cache K/V, and greedy continuations as unpadded —
        causal masking keeps real positions independent of the padding
        and only real rows reach the cache."""
        import tony_tpu.models.decode as D

        prompt = jax.random.randint(jax.random.PRNGKey(12), (2, 300), 0,
                                    CFG.vocab_size)
        lg_ref, cache_ref = prefill(params, prompt, CFG, max_len=310)
        monkeypatch.setattr(D, "_pad_prompts", lambda: True)
        assert D._flash_safe_len(300) == 512        # genuinely pads
        lg_pad, cache_pad = prefill(params, prompt, CFG, max_len=310)
        np.testing.assert_allclose(np.asarray(lg_pad),
                                   np.asarray(lg_ref), rtol=2e-4,
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(cache_pad["k"]),
                                   np.asarray(cache_ref["k"]),
                                   rtol=2e-4, atol=2e-4)
        assert int(cache_pad["length"]) == 300
        # greedy continuation off the padded-prefill cache matches the
        # unpadded one (eager decode_step calls — no jit cache aliasing
        # between the patched and unpatched traces)
        ca, cb = cache_pad, cache_ref
        la, lb = lg_pad, lg_ref
        for _ in range(3):
            ta = jnp.argmax(la, axis=-1)
            tb = jnp.argmax(lb, axis=-1)
            np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))
            la, ca = decode_step(params, ta, ca, ca["length"], CFG)
            lb, cb = decode_step(params, tb, cb, cb["length"], CFG)

    @pytest.mark.slow
    def test_moe_greedy_generate_matches_full_forward(self):
        """MoE decode: cached generation equals the full-forward loop (high
        capacity factor so routing drops cannot differ between the S=1
        decode dispatch and the growing-S full forward)."""
        moe_cfg = CFG.scaled(num_experts=2, moe_capacity_factor=4.0)
        moe_params = T.init_params(jax.random.PRNGKey(5), moe_cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(6), (2, 4), 0,
                                    moe_cfg.vocab_size)
        out = generate(moe_params, prompt, moe_cfg, max_new_tokens=4,
                       rng=jax.random.PRNGKey(0), temperature=0.0)
        expected = full_forward_greedy(moe_params, prompt, 4, cfg=moe_cfg)
        np.testing.assert_array_equal(np.asarray(out.tokens),
                                      np.asarray(expected))

    def test_tp_sharded_decode_matches_unsharded(self, params):
        """Tensor-parallel serving: params sharded over tp (heads/mlp dims)
        decode token-identically via XLA sharding propagation."""
        from tony_tpu.parallel import make_mesh, shard_pytree
        prompt = jax.random.randint(jax.random.PRNGKey(9), (2, 8), 0,
                                    CFG.vocab_size)
        ref = generate(params, prompt, CFG, max_new_tokens=6,
                       rng=jax.random.PRNGKey(0), temperature=0.0)
        mesh = make_mesh({"tp": 4, "dp": 2})
        sharded = shard_pytree(params, T.logical_axes(CFG), mesh)
        with jax.set_mesh(mesh):
            out = generate(sharded, prompt, CFG, max_new_tokens=6,
                           rng=jax.random.PRNGKey(0), temperature=0.0)
        np.testing.assert_array_equal(np.asarray(ref.tokens),
                                      np.asarray(out.tokens))

    def test_extend_step_matches_sequential_decode(self, params):
        """A K-token chunked extend equals K sequential single steps."""
        prompt = jax.random.randint(jax.random.PRNGKey(10), (2, 5), 0,
                                    CFG.vocab_size)
        chunk = jax.random.randint(jax.random.PRNGKey(11), (2, 3), 0,
                                   CFG.vocab_size)
        from tony_tpu.models.decode import extend_step
        _, cache_a = prefill(params, prompt, CFG, max_len=12)
        logits_chunk, cache_a = extend_step(params, chunk, cache_a,
                                            cache_a["length"], CFG)
        _, cache_b = prefill(params, prompt, CFG, max_len=12)
        for i in range(3):
            logits_i, cache_b = decode_step(params, chunk[:, i], cache_b,
                                            cache_b["length"], CFG)
            np.testing.assert_allclose(np.asarray(logits_chunk[:, i]),
                                       np.asarray(logits_i),
                                       rtol=2e-4, atol=2e-4)
        assert int(cache_a["length"]) == int(cache_b["length"]) == 8

    @pytest.mark.slow
    @pytest.mark.parametrize("num_spec", [1, 3, 6])
    def test_speculative_equals_greedy(self, params, num_spec):
        """Speculative decoding with ANY draft model reproduces the target's
        greedy output exactly — here the draft IS the target (worst and best
        case acceptance paths both exercised across num_spec values)."""
        from tony_tpu.models.decode import speculative_generate
        prompt = jax.random.randint(jax.random.PRNGKey(12), (1, 5), 0,
                                    CFG.vocab_size)
        want = generate(params, prompt, CFG, max_new_tokens=9,
                        rng=jax.random.PRNGKey(0), temperature=0.0)
        got = speculative_generate(params, params, prompt, CFG, CFG,
                                   max_new_tokens=9,
                                   num_speculative=num_spec)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want.tokens))

    @pytest.mark.slow
    def test_speculative_with_distinct_draft(self, params):
        """A DIFFERENT (random) draft still yields the target's exact greedy
        output — only the speed, not the result, depends on the draft."""
        from tony_tpu.models.decode import speculative_generate
        draft_params = T.init_params(jax.random.PRNGKey(99), CFG)
        prompt = jax.random.randint(jax.random.PRNGKey(13), (1, 4), 0,
                                    CFG.vocab_size)
        want = generate(params, prompt, CFG, max_new_tokens=7,
                        rng=jax.random.PRNGKey(0), temperature=0.0)
        got = speculative_generate(params, draft_params, prompt, CFG, CFG,
                                   max_new_tokens=7, num_speculative=3)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want.tokens))

    @pytest.mark.slow
    @pytest.mark.parametrize("num_spec", [1, 3, 6])
    def test_speculative_device_equals_greedy(self, params, num_spec):
        """The DEVICE-side loop (one compiled while_loop program, no host
        round trips) is token-identical to the target's greedy generate —
        self-draft exercises the full-acceptance cache discipline."""
        from tony_tpu.models.decode import speculative_generate_device
        prompt = jax.random.randint(jax.random.PRNGKey(12), (1, 5), 0,
                                    CFG.vocab_size)
        want = generate(params, prompt, CFG, max_new_tokens=9,
                        rng=jax.random.PRNGKey(0), temperature=0.0)
        got = speculative_generate_device(params, params, prompt, CFG, CFG,
                                          max_new_tokens=9,
                                          num_speculative=num_spec)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want.tokens))

    @pytest.mark.slow
    def test_speculative_device_distinct_draft(self, params):
        """Rejections + corrections on device: a random draft still yields
        the target's exact greedy output (stale-entry overwrite path)."""
        from tony_tpu.models.decode import (speculative_generate,
                                            speculative_generate_device)
        draft_params = T.init_params(jax.random.PRNGKey(99), CFG)
        prompt = jax.random.randint(jax.random.PRNGKey(13), (1, 4), 0,
                                    CFG.vocab_size)
        want = generate(params, prompt, CFG, max_new_tokens=7,
                        rng=jax.random.PRNGKey(0), temperature=0.0)
        got = speculative_generate_device(params, draft_params, prompt,
                                          CFG, CFG, max_new_tokens=7,
                                          num_speculative=3)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want.tokens))
        host = speculative_generate(params, draft_params, prompt, CFG, CFG,
                                    max_new_tokens=7, num_speculative=3)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(host))


class TestBlockwiseCachedAttention:
    """Length-aware decode attention: caches >= _BLOCKWISE_MIN_LEN take a
    block-wise online-softmax path whose executed cost follows the live
    length, not the padded max_len. It must agree with the dense einsum."""

    def _rand(self, key, b, max_len, kv, h, d, n_q):
        ks = jax.random.split(jax.random.PRNGKey(key), 3)
        q = jax.random.normal(ks[0], (b, n_q, h, d), jnp.float32)
        k_cache = jax.random.normal(ks[1], (b, max_len, kv, d), jnp.float32)
        v_cache = jax.random.normal(ks[2], (b, max_len, kv, d), jnp.float32)
        return q, k_cache, v_cache

    @pytest.mark.parametrize("q_start,n_q", [(0, 1), (5, 1), (255, 1),
                                             (256, 1), (300, 4), (635, 4)])
    def test_matches_dense(self, q_start, n_q):
        from tony_tpu.models import decode as D
        # max_len=640 is NOT a block multiple: the last slice start clamps
        # and the >= i*block mask must discard the re-read rows
        q, k_cache, v_cache = self._rand(q_start, 2, 640, 4, 4, 16, n_q)
        if q_start + n_q > 640:
            pytest.skip("positions exceed cache")
        got = D._cached_attention_blockwise(
            q, {"k": D._kv_flat(k_cache)[None],
                "v": D._kv_flat(v_cache)[None]}, 0,
            jnp.asarray(q_start))
        b, nq, h, d = q.shape
        kv = k_cache.shape[2]
        group = h // kv
        q_pos = q_start + jnp.arange(nq)
        k_pos = jnp.arange(640)
        mask = k_pos[None, :] <= q_pos[:, None]
        qg = q.reshape(b, nq, kv, group, d)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache) * d ** -0.5
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        want = jnp.einsum("bkgqs,bskd->bqkgd", probs,
                          v_cache).reshape(b, nq, h, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_matches_dense(self):
        from tony_tpu.models import decode as D
        q, k_cache, v_cache = self._rand(7, 2, 768, 2, 8, 16, 3)  # group=4
        got = D._cached_attention_blockwise(
            q, {"k": D._kv_flat(k_cache)[None],
                "v": D._kv_flat(v_cache)[None]}, 0,
            jnp.asarray(500))
        b, nq, h, d = q.shape
        kv, group = 2, 4
        q_pos = 500 + jnp.arange(nq)
        mask = jnp.arange(768)[None, :] <= q_pos[:, None]
        qg = q.reshape(b, nq, kv, group, d)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache) * d ** -0.5
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        want = jnp.einsum("bkgqs,bskd->bqkgd", probs,
                          v_cache).reshape(b, nq, h, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_decode_step_long_cache_matches_full_forward(self, params):
        """End to end through the dispatch: a max_len >= 512 cache (block-
        wise path) still reproduces the training forward's logits."""
        prompt = jax.random.randint(jax.random.PRNGKey(30), (2, 5), 0,
                                    CFG.vocab_size)
        _, cache = prefill(params, prompt, CFG, max_len=600)
        nxt = jnp.array([3, 7])
        logits_cached, cache = decode_step(params, nxt, cache,
                                           cache["length"], CFG)
        extended = jnp.concatenate([prompt, nxt[:, None]], axis=1)
        logits_full, _ = T.forward(params, extended, CFG)
        np.testing.assert_allclose(np.asarray(logits_cached),
                                   np.asarray(logits_full[:, -1]),
                                   rtol=3e-4, atol=3e-4)

    @pytest.mark.slow
    def test_tp_sharded_long_cache_decode(self, params):
        """The fori_loop + dynamic_slice path must stay correct under tp
        sharding propagation (cache sharded on the KV-head axis)."""
        from tony_tpu.parallel import make_mesh, shard_pytree
        prompt = jax.random.randint(jax.random.PRNGKey(31), (2, 6), 0,
                                    CFG.vocab_size)
        _, cache_ref = prefill(params, prompt, CFG, max_len=600)
        nxt = jnp.array([1, 2])
        ref, _ = decode_step(params, nxt, cache_ref, cache_ref["length"],
                             CFG)
        mesh = make_mesh({"tp": 4, "dp": 2})
        sharded = shard_pytree(params, T.logical_axes(CFG), mesh)
        with jax.set_mesh(mesh):
            _, cache = prefill(sharded, prompt, CFG, max_len=600)
            got, _ = decode_step(sharded, nxt, cache, cache["length"], CFG)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=3e-4, atol=3e-4)


class TestGQA:
    """Grouped-query attention: n_kv_heads < n_heads."""
    GCFG = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False,
                                    n_kv_heads=2)    # 4 q heads, 2 kv heads

    def test_forward_equals_mha_with_repeated_kv_weights(self):
        """A GQA model must compute exactly what an MHA model with each
        K/V head repeated across its query group computes."""
        gparams = T.init_params(jax.random.PRNGKey(0), self.GCFG)
        mha_cfg = self.GCFG.scaled(n_kv_heads=None)
        rep = self.GCFG.n_heads // self.GCFG.kv_heads
        mparams = jax.tree.map(lambda x: x, gparams)
        mparams["blocks"] = dict(
            gparams["blocks"],
            wk=jnp.repeat(gparams["blocks"]["wk"], rep, axis=2),
            wv=jnp.repeat(gparams["blocks"]["wv"], rep, axis=2))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    self.GCFG.vocab_size)
        lg, _ = T.forward(gparams, tokens, self.GCFG)
        lm, _ = T.forward(mparams, tokens, mha_cfg)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lm),
                                   rtol=1e-5, atol=1e-5)

    def test_cache_stores_kv_heads_only(self):
        cache = init_kv_cache(self.GCFG, batch=2, max_len=32)
        assert cache["k"].shape == (self.GCFG.n_layers, 2, 32,
                                    2 * self.GCFG.head_dim)

    def test_greedy_generate_equals_full_forward(self):
        gparams = T.init_params(jax.random.PRNGKey(4), self.GCFG)
        prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 4), 0,
                                    self.GCFG.vocab_size)
        out = generate(gparams, prompt, self.GCFG, max_new_tokens=5,
                       rng=jax.random.PRNGKey(0), temperature=0.0)
        expected = full_forward_greedy(gparams, prompt, 5, cfg=self.GCFG)
        np.testing.assert_array_equal(np.asarray(out.tokens),
                                      np.asarray(expected))

    def test_indivisible_head_groups_rejected(self):
        # fails at CONSTRUCTION, not first trace
        with pytest.raises(ValueError, match="positive divisor"):
            T.PRESETS["tiny"].scaled(n_kv_heads=3)
        with pytest.raises(ValueError, match="positive divisor"):
            T.PRESETS["tiny"].scaled(n_kv_heads=0)
        with pytest.raises(ValueError, match="positive divisor"):
            T.PRESETS["tiny"].scaled(n_kv_heads=-2)

    def test_tp_sharded_gqa_decode(self):
        """GQA params shard on a tp mesh larger than n_kv_heads (K/V
        replicate — the Llama-style TP layout) and decode token-identically."""
        from tony_tpu.parallel import make_mesh, shard_pytree
        gparams = T.init_params(jax.random.PRNGKey(6), self.GCFG)
        prompt = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0,
                                    self.GCFG.vocab_size)
        ref = generate(gparams, prompt, self.GCFG, max_new_tokens=5,
                       rng=jax.random.PRNGKey(0), temperature=0.0)
        mesh = make_mesh({"tp": 4, "dp": 2})   # tp > n_kv_heads=2
        sharded = shard_pytree(gparams, T.logical_axes(self.GCFG), mesh)
        with jax.set_mesh(mesh):
            out = generate(sharded, prompt, self.GCFG, max_new_tokens=5,
                           rng=jax.random.PRNGKey(0), temperature=0.0)
        np.testing.assert_array_equal(np.asarray(ref.tokens),
                                      np.asarray(out.tokens))


@pytest.mark.slow
@pytest.mark.parametrize("batch,num_spec", [(4, 3), (3, 2)])
def test_speculative_device_batched_equals_greedy(batch, num_spec):
    """Batch > 1 speculation (per-row cache frontiers: every row commits
    its OWN acceptance each round; RoPE/mask/K-V writes take [B] position
    vectors) stays token-identical to batched greedy — including rows
    whose acceptances diverge (distinct random draft forces rejections
    at different per-row lengths)."""
    from tony_tpu.models.decode import speculative_generate_device

    params = T.init_params(jax.random.PRNGKey(0), CFG)
    draft_params = T.init_params(jax.random.PRNGKey(99), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(21), (batch, 6), 0,
                                CFG.vocab_size)
    want = generate(params, prompt, CFG, max_new_tokens=9,
                    rng=jax.random.PRNGKey(0), temperature=0.0)
    for draft in (params, draft_params):    # self-draft + rejecting draft
        got = speculative_generate_device(params, draft, prompt, CFG, CFG,
                                          max_new_tokens=9,
                                          num_speculative=num_spec)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want.tokens))


@pytest.mark.slow
def test_speculative_commit_policies_and_rounds():
    """Both commit schedules are token-identical to greedy; per-row
    commits never need MORE rounds than min-commit (self-draft makes the
    round counts deterministic; a rejecting draft makes them diverge)."""
    from tony_tpu.models.decode import speculative_generate_device

    params = T.init_params(jax.random.PRNGKey(0), CFG)
    draft_params = T.init_params(jax.random.PRNGKey(99), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(22), (3, 5), 0,
                                CFG.vocab_size)
    want = generate(params, prompt, CFG, max_new_tokens=8,
                    rng=jax.random.PRNGKey(0), temperature=0.0)
    for draft in (params, draft_params):
        toks_pr, rounds_pr = speculative_generate_device(
            params, draft, prompt, CFG, CFG, max_new_tokens=8,
            num_speculative=3, commit="per_row", return_rounds=True)
        toks_mc, rounds_mc = speculative_generate_device(
            params, draft, prompt, CFG, CFG, max_new_tokens=8,
            num_speculative=3, commit="min", return_rounds=True)
        np.testing.assert_array_equal(np.asarray(toks_pr),
                                      np.asarray(want.tokens))
        np.testing.assert_array_equal(np.asarray(toks_mc),
                                      np.asarray(want.tokens))
        assert int(rounds_pr) <= int(rounds_mc)
    with pytest.raises(ValueError, match="commit policy"):
        speculative_generate_device(params, params, prompt, CFG, CFG,
                                    max_new_tokens=8, num_speculative=3,
                                    commit="bogus")


@pytest.mark.slow
@pytest.mark.parametrize("window", [0, 5, 16])
def test_speculative_window_commit_equals_greedy(window):
    """The bounded-window commit (scatter-free per-row cache writes) is
    token-identical to greedy across window sizes — including window=5,
    the minimum legal slack for k=3, where any acceptance divergence
    immediately clamps. 0 = the 4*(k+1) default."""
    from tony_tpu.models.decode import speculative_generate_device

    params = T.init_params(jax.random.PRNGKey(0), CFG)
    draft_params = T.init_params(jax.random.PRNGKey(99), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(21), (4, 6), 0,
                                CFG.vocab_size)
    want = generate(params, prompt, CFG, max_new_tokens=9,
                    rng=jax.random.PRNGKey(0), temperature=0.0)
    for draft in (params, draft_params):    # self-draft + rejecting draft
        got = speculative_generate_device(params, draft, prompt, CFG, CFG,
                                          max_new_tokens=9,
                                          num_speculative=3,
                                          commit="window", window=window)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want.tokens))


@pytest.mark.slow
def test_speculative_window_commit_clamp_forced():
    """Window commit stays exact when the clamp provably BITES: one row's
    draft is perfect (its tokens' embeddings untouched) and the other's
    is sabotaged (draft embeddings corrupted exactly for the tokens its
    greedy trajectory visits — the rows' trajectories are disjoint for
    this seed, asserted), so per-row speculation diverges ~k positions
    per round while window=k+2 allows divergence 1. Also pins the
    heterogeneity itself via batch-1 round counts, so a model/seed drift
    that equalised acceptance would fail loudly instead of silently
    weakening the test."""
    from tony_tpu.models.decode import speculative_generate_device

    params = T.init_params(jax.random.PRNGKey(0), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(0), (2, 6), 0,
                                CFG.vocab_size)
    n = 24
    want = generate(params, prompt, CFG, max_new_tokens=n,
                    rng=jax.random.PRNGKey(0), temperature=0.0)
    traj = np.asarray(want.tokens)
    set_a = set(traj[0].tolist())
    set_b = set(traj[1][prompt.shape[1]:].tolist())
    assert not (set_a & set_b), "seed drift: trajectories overlap"
    corrupt = jnp.asarray(sorted(set_b - set_a), jnp.int32)
    semi = dict(params, embed=params["embed"].at[corrupt].add(1.0))

    rounds_alone = []
    for r in range(2):
        _, rounds = speculative_generate_device(
            params, semi, prompt[r:r + 1], CFG, CFG, max_new_tokens=n,
            num_speculative=4, commit="per_row", return_rounds=True)
        rounds_alone.append(int(rounds))
    # row 0 speculates near-perfectly, row 1 barely — the batched run's
    # per-row frontiers MUST hit the window bound
    assert rounds_alone[0] < rounds_alone[1] // 2, rounds_alone

    for window in (6, 0):          # slack 1 (max clamping) and default
        got = speculative_generate_device(
            params, semi, prompt, CFG, CFG, max_new_tokens=n,
            num_speculative=4, commit="window", window=window)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want.tokens))

    with pytest.raises(ValueError, match="window"):
        speculative_generate_device(params, semi, prompt, CFG, CFG,
                                    max_new_tokens=n, num_speculative=4,
                                    commit="window", window=3)


class TestBeamSearch:
    BCFG = T.TransformerConfig(vocab_size=17, d_model=24, n_layers=2,
                               n_heads=2, d_ff=48, max_seq=256,
                               dtype=jnp.float32,
                               logits_dtype=jnp.float32, remat=False)

    @pytest.fixture(scope="class")
    def bparams(self):
        return T.init_params(jax.random.PRNGKey(2), self.BCFG)

    def _seq_logprob(self, params, row_tokens, prompt_len, n_tok):
        """Exact logprob of generated tokens via the full forward."""
        toks = jnp.asarray(row_tokens, jnp.int32)[None]
        logits, _ = T.forward(params, toks, self.BCFG)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        total = 0.0
        for i in range(n_tok):
            pos = prompt_len - 1 + i
            total += float(logp[0, pos, int(row_tokens[prompt_len + i])])
        return total

    def test_width_one_equals_greedy(self, bparams):
        from tony_tpu.models.decode import beam_search

        prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 5), 0,
                                    self.BCFG.vocab_size)
        want = generate(bparams, prompt, self.BCFG, max_new_tokens=7,
                        rng=jax.random.PRNGKey(0), temperature=0.0)
        out = beam_search(bparams, prompt, self.BCFG, max_new_tokens=7,
                          beam_width=1)
        np.testing.assert_array_equal(np.asarray(out.tokens[:, 0]),
                                      np.asarray(want.tokens))

    def test_scores_are_exact_and_sorted(self, bparams):
        """Every returned beam's score equals the full-forward logprob of
        its tokens (the KV-cache path and per-step bookkeeping introduce
        no drift), and beams come back sorted, distinct."""
        from tony_tpu.models.decode import beam_search

        prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 4), 0,
                                    self.BCFG.vocab_size)
        out = beam_search(bparams, prompt, self.BCFG, max_new_tokens=6,
                          beam_width=4)
        toks = np.asarray(out.tokens)
        scores = np.asarray(out.scores)
        for r in range(2):
            assert (np.diff(scores[r]) <= 1e-6).all()
            seqs = {tuple(toks[r, wdx]) for wdx in range(4)}
            assert len(seqs) == 4
            for wdx in range(4):
                want = self._seq_logprob(bparams, toks[r, wdx], 4, 6)
                assert abs(want - scores[r, wdx]) < 1e-3, (r, wdx)

    def test_matches_cache_free_reference_beam(self, bparams):
        """Token-identical to a from-scratch beam search that re-runs the
        FULL forward on every prefix each step (no KV cache, no
        reordering) — the cache gather by parent index is the part this
        pins."""
        from tony_tpu.models.decode import beam_search

        cfg = self.BCFG
        prompt = jax.random.randint(jax.random.PRNGKey(5), (1, 4), 0,
                                    cfg.vocab_size)
        w, n = 3, 5

        # reference: python beam over full forwards
        beams = [(0.0, [int(t) for t in np.asarray(prompt[0])])]
        for _ in range(n):
            cand = []
            for score, seq in beams:
                logits, _ = T.forward(
                    bparams, jnp.asarray(seq, jnp.int32)[None], cfg)
                logp = np.asarray(jax.nn.log_softmax(
                    logits[0, -1].astype(jnp.float32)))
                for tok in range(cfg.vocab_size):
                    cand.append((score + float(logp[tok]), seq + [tok]))
            cand.sort(key=lambda x: -x[0])
            beams = cand[:w]

        out = beam_search(bparams, prompt, cfg, max_new_tokens=n,
                          beam_width=w)
        got = [tuple(np.asarray(out.tokens)[0, i]) for i in range(w)]
        want = [tuple(seq) for _, seq in beams]
        assert got == want, (got, want)
        for i in range(w):
            assert abs(float(out.scores[0, i]) - beams[i][0]) < 1e-3

    def test_eos_freezes_beams(self, bparams):
        """Beams that emit eos stop: score frozen, tokens padded with
        eos, length = tokens incl. eos; still exactly the logprob of the
        truncated sequence."""
        from tony_tpu.models.decode import beam_search

        prompt = jax.random.randint(jax.random.PRNGKey(6), (1, 4), 0,
                                    self.BCFG.vocab_size)
        # run once without eos to discover a token on the best path
        free = beam_search(bparams, prompt, self.BCFG, max_new_tokens=6,
                           beam_width=3)
        eos = int(np.asarray(free.tokens)[0, 0, 4 + 2])  # 3rd generated
        out = beam_search(bparams, prompt, self.BCFG, max_new_tokens=6,
                          beam_width=3, eos_id=eos)
        toks = np.asarray(out.tokens)
        for wdx in range(3):
            gen = toks[0, wdx, 4:]
            ln = int(out.lengths[0, wdx])
            if eos in gen.tolist():
                first = gen.tolist().index(eos)
                assert ln == first + 1
                assert (gen[first:] == eos).all()       # eos padding
            else:
                assert ln == 6
            want = self._seq_logprob(bparams, toks[0, wdx], 4, ln)
            assert abs(want - float(out.scores[0, wdx])) < 1e-3

    def test_bad_width_rejected(self, bparams):
        from tony_tpu.models.decode import beam_search

        prompt = jnp.asarray([[1, 2]], jnp.int32)
        with pytest.raises(ValueError, match="beam_width"):
            beam_search(bparams, prompt, self.BCFG, max_new_tokens=3,
                        beam_width=0)
        with pytest.raises(ValueError, match="max_new_tokens"):
            beam_search(bparams, prompt, self.BCFG, max_new_tokens=0,
                        beam_width=2)

    def test_tp_sharded_beams_match_unsharded(self):
        """Beam search under tensor parallelism: sharded params give the
        same beams/scores via XLA sharding propagation — the per-step
        cache gather by parent index must respect the propagated cache
        sharding."""
        from tony_tpu.models.decode import beam_search
        from tony_tpu.parallel import make_mesh, shard_pytree

        cfg = self.BCFG.scaled(vocab_size=16)   # tp-divisible lm_head
        params = T.init_params(jax.random.PRNGKey(2), cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(8), (2, 5), 0,
                                    cfg.vocab_size)
        ref = beam_search(params, prompt, cfg, max_new_tokens=5,
                          beam_width=3)
        mesh = make_mesh({"tp": 2, "dp": 4})
        sharded = shard_pytree(params, T.logical_axes(cfg), mesh)
        with jax.set_mesh(mesh):
            out = beam_search(sharded, prompt, cfg,
                              max_new_tokens=5, beam_width=3)
        np.testing.assert_array_equal(np.asarray(ref.tokens),
                                      np.asarray(out.tokens))
        np.testing.assert_allclose(np.asarray(ref.scores),
                                   np.asarray(out.scores), atol=1e-4)


class TestSpeculativeSampling:
    """Rejection-sampling speculation (temperature > 0): committed
    tokens are distributed exactly as target-only sampling, for any
    draft."""

    SCFG = T.TransformerConfig(vocab_size=11, d_model=24, n_layers=2,
                               n_heads=2, d_ff=48, max_seq=1024,
                               dtype=jnp.float32,
                               logits_dtype=jnp.float32, remat=False)

    def test_requires_rng(self):
        from tony_tpu.models.decode import speculative_generate_device

        params = T.init_params(jax.random.PRNGKey(0), self.SCFG)
        prompt = jnp.asarray([[3, 7, 1, 5]], jnp.int32)
        with pytest.raises(ValueError, match="rng"):
            speculative_generate_device(params, params, prompt, self.SCFG,
                                        self.SCFG, max_new_tokens=4,
                                        num_speculative=2, temperature=0.8)

    def test_self_draft_accepts_everything(self):
        """With draft == target and no filters the accept ratio is
        exactly 1, so the round count is deterministic:
        ceil(max_new / (k+1))."""
        from tony_tpu.models.decode import speculative_generate_device

        params = T.init_params(jax.random.PRNGKey(0), self.SCFG)
        prompt = jnp.asarray([[3, 7, 1, 5]], jnp.int32).repeat(4, 0)
        _, rounds = speculative_generate_device(
            params, params, prompt, self.SCFG, self.SCFG,
            max_new_tokens=12, num_speculative=3, temperature=1.0,
            rng=jax.random.PRNGKey(5), return_rounds=True)
        assert int(rounds) == 3

    @pytest.mark.slow
    def test_matches_target_distribution_any_draft(self):
        """The core guarantee, measured: the 2-token joint distribution
        of speculative sampling with a MISMATCHED draft (a different
        random model) matches direct target sampling to sampling noise
        (TV ~ 0.05 at ~3k samples), while the draft's own distribution
        is far away (TV ~ 0.7) — so the tolerance has discriminating
        power. Run under the bounded-window commit with the minimum
        window so the clamped-pending path (accepted-token-at-the-cut)
        is exercised too."""
        from tony_tpu.models.decode import speculative_generate_device

        cfg = self.SCFG
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        draft = T.init_params(jax.random.PRNGKey(99), cfg)
        pm = jnp.asarray([[3, 7, 1, 5]], jnp.int32).repeat(512, 0)
        n = 2

        def joint(fn, seed0, batches=6):
            c = np.zeros((cfg.vocab_size, cfg.vocab_size))
            for i in range(batches):
                a = fn(jax.random.PRNGKey(seed0 + i))
                for r in a:
                    c[r[0], r[1]] += 1
            return c / c.sum()

        ref = joint(lambda key: np.asarray(generate(
            params, pm, cfg, max_new_tokens=n, rng=key, temperature=0.9,
            top_p=0.85).tokens[:, -n:]), 200)
        spec = joint(lambda key: np.asarray(speculative_generate_device(
            params, draft, pm, cfg, cfg, max_new_tokens=n,
            num_speculative=3, temperature=0.9, top_p=0.85,
            commit="window", window=5, rng=key)[:, -n:]), 100)
        draft_only = joint(lambda key: np.asarray(generate(
            draft, pm, cfg, max_new_tokens=n, rng=key, temperature=0.9,
            top_p=0.85).tokens[:, -n:]), 300)

        tv_spec = 0.5 * np.abs(spec - ref).sum()
        tv_draft = 0.5 * np.abs(draft_only - ref).sum()
        assert tv_spec < 0.1, tv_spec
        assert tv_draft > 0.3, tv_draft    # the test can tell them apart


class TestQuantizedKVCache:
    """int8 KV cache (cfg.kv_cache_dtype="int8"): k/v stored int8 with
    per-token, per-kv-head absmax scales in parallel [.., KV, 1] buffers.
    Exactness contract: the quantized ATTENTION math is deterministic, so
    everything downstream that compares quant-to-quant (serving vs
    generate, beam width-1 vs greedy, speculative vs greedy) stays
    token-identical on CPU; quant-to-float agreement is approximate
    (int8 rounding, ~1% relative on the attention output)."""

    QCFG = CFG.scaled(kv_cache_dtype="int8")

    def test_cache_layout(self):
        from tony_tpu.models import decode as D
        c = D.init_kv_cache(self.QCFG, 2, 64)
        kv, hd = self.QCFG.kv_heads, self.QCFG.head_dim
        assert c["k"].dtype == jnp.int8 and c["v"].dtype == jnp.int8
        assert c["k"].shape == (self.QCFG.n_layers, 2, 64, kv * hd)
        assert c["k_scale"].shape == (self.QCFG.n_layers, 2, 64, kv)
        assert c["k_scale"].dtype == jnp.float32

    def test_quantize_roundtrip_error_bounded(self):
        from tony_tpu.models import decode as D
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 2, 32),
                              jnp.float32)
        q, s = D._kv_quantize(x)
        assert q.dtype == jnp.int8 and s.shape == (4, 7, 2, 1)
        err = jnp.abs(q.astype(jnp.float32) * s - x)
        # symmetric absmax: per-element error <= scale/2 = absmax/254
        bound = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 254.0
        assert bool(jnp.all(err <= bound + 1e-7))

    def _quant_bufs(self, key, b, max_len, kv, d):
        from tony_tpu.models import decode as D
        ks = jax.random.split(jax.random.PRNGKey(key), 2)
        k = jax.random.normal(ks[0], (1, b, max_len, kv, d), jnp.float32)
        v = jax.random.normal(ks[1], (1, b, max_len, kv, d), jnp.float32)
        kq, ksc = D._kv_quantize(k)
        vq, vsc = D._kv_quantize(v)
        return (D.kv_from_wire({"k": kq, "v": vq, "k_scale": ksc,
                                "v_scale": vsc}),
                D.kv_from_wire({"k": kq.astype(jnp.float32) * ksc,
                                "v": vq.astype(jnp.float32) * vsc}))

    @pytest.mark.parametrize("max_len,q_start,n_q", [(192, 150, 1),
                                                     (1024, 700, 3)])
    def test_scale_fold_matches_dequantized(self, max_len, q_start, n_q):
        """The K scale applied on the scores and the V scale folded into
        p must equal attention over the explicitly dequantized cache
        (same math, reassociated) — covers the dense AND blockwise
        dispatch (max_len 1024 >= _BLOCKWISE_MIN_LEN)."""
        from tony_tpu.models import decode as D
        bufs_q, bufs_dq = self._quant_bufs(max_len, 2, max_len, 2, 32)
        q = jax.random.normal(jax.random.PRNGKey(1), (2, n_q, 8, 32),
                              jnp.float32)
        got = D._cached_attention(q, bufs_q, 0, jnp.asarray(q_start))
        want = D._cached_attention(q, bufs_dq, 0, jnp.asarray(q_start))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-2)

    def test_quantized_attention_close_to_float(self):
        """int8 rounding bounds the attention-output error (~1% rel)."""
        from tony_tpu.models import decode as D
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        k = jax.random.normal(ks[0], (1, 2, 192, 2, 32), jnp.float32)
        v = jax.random.normal(ks[1], (1, 2, 192, 2, 32), jnp.float32)
        q = jax.random.normal(ks[2], (2, 1, 4, 32), jnp.float32)
        kq, ksc = D._kv_quantize(k)
        vq, vsc = D._kv_quantize(v)
        of = D._cached_attention(q, D.kv_from_wire({"k": k, "v": v}), 0,
                                 jnp.asarray(150))
        oq = D._cached_attention(
            q, D.kv_from_wire({"k": kq, "v": vq, "k_scale": ksc,
                               "v_scale": vsc}), 0,
            jnp.asarray(150))
        rel = float(jnp.max(jnp.abs(of - oq)) / jnp.max(jnp.abs(of)))
        assert rel < 0.05, rel

    def test_generate_runs_and_tracks_float(self, params):
        """Quantized greedy generate stays on the float model's rails:
        the FIRST token (sharpest signal, no drift) matches, and per-step
        model logprobs stay close while the streams agree."""
        prompt = jax.random.randint(jax.random.PRNGKey(40), (2, 8), 0,
                                    CFG.vocab_size)
        rng = jax.random.PRNGKey(0)
        out_f = generate(params, prompt, CFG, 24, rng)
        out_q = generate(params, prompt, self.QCFG, 24, rng)
        assert out_q.tokens.shape == out_f.tokens.shape
        assert bool(jnp.all(out_f.tokens[:, 8] == out_q.tokens[:, 8]))

    def test_extend_step_matches_sequential_quant(self, params):
        """Chunked verify == single steps under quantization (the
        property speculative decoding relies on). Cache CONTENTS are
        identical (per-token quantization is chunk-width-independent);
        logits agree to the same dot-rounding tolerance as the
        unquantized chunk-vs-sequential test above."""
        from tony_tpu.models import decode as D
        prompt = jax.random.randint(jax.random.PRNGKey(41), (1, 6), 0,
                                    CFG.vocab_size)
        toks = jax.random.randint(jax.random.PRNGKey(42), (1, 4), 0,
                                  CFG.vocab_size)
        _, c1 = D.prefill(params, prompt, self.QCFG, max_len=16)
        lg_chunk, c1 = D.extend_step(params, toks, c1, 6, self.QCFG)
        _, c2 = D.prefill(params, prompt, self.QCFG, max_len=16)
        for i in range(4):
            lg, c2 = D.decode_step(params, toks[:, i], c2, 6 + i,
                                   self.QCFG)
            np.testing.assert_allclose(np.asarray(lg_chunk[:, i]),
                                       np.asarray(lg), rtol=2e-4,
                                       atol=2e-4)
        # the chunk's DEQUANTIZED cache matches the sequential writes
        # (bit-equality only holds at layer 0 — deeper layers' K/V
        # inputs inherit shape-dependent dot rounding from the layers
        # below, which can move a value across a rounding boundary)
        w1 = D.kv_to_wire(D._kv_bufs(c1), self.QCFG)
        w2 = D.kv_to_wire(D._kv_bufs(c2), self.QCFG)
        for kn, sn in (("k", "k_scale"), ("v", "v_scale")):
            d1 = np.asarray(w1[kn], np.float32) * np.asarray(w1[sn])
            d2 = np.asarray(w2[kn], np.float32) * np.asarray(w2[sn])
            np.testing.assert_allclose(d1, d2, atol=1e-3)
        np.testing.assert_array_equal(np.asarray(c1["k"][0]),
                                      np.asarray(c2["k"][0]))

    def test_speculative_device_equals_greedy_quant(self, params):
        """Both caches quantized: the speculative program still equals
        quantized greedy generate token for token (CPU-exact)."""
        from tony_tpu.models.decode import speculative_generate_device
        prompt = jax.random.randint(jax.random.PRNGKey(43), (2, 5), 0,
                                    CFG.vocab_size)
        want = generate(params, prompt, self.QCFG, 12,
                        jax.random.PRNGKey(0)).tokens
        got = speculative_generate_device(
            params, params, prompt, self.QCFG, self.QCFG,
            max_new_tokens=12, num_speculative=3)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_beam_width_one_equals_greedy_quant(self, params):
        from tony_tpu.models.decode import beam_search
        prompt = jax.random.randint(jax.random.PRNGKey(44), (2, 6), 0,
                                    CFG.vocab_size)
        bs = beam_search(params, prompt, self.QCFG, 10, beam_width=1)
        g = generate(params, prompt, self.QCFG, 10, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(bs.tokens[:, 0]),
                                      np.asarray(g.tokens))


class TestSlidingWindowDecode:
    """attn_window threads from TransformerConfig through prefill,
    decode_step, and the blockwise cached-attention path: cached decode
    must equal the windowed training forward, and the blockwise loop's
    window-derived LOWER bound (the O(window) serving-cost lever) must
    not change results."""

    WCFG = CFG.scaled(attn_window=24)

    def test_config_validation(self):
        with pytest.raises(ValueError, match="attn_window"):
            CFG.scaled(attn_window=-1)

    def test_window_with_cp_mesh_rejected(self):
        from tony_tpu.parallel.mesh import make_mesh
        mesh = make_mesh({"cp": 2, "dp": -1})
        q = jnp.zeros((2, 8, 4, 8), jnp.float32)
        with pytest.raises(NotImplementedError, match="attn_window"):
            T._attention(q, q, q, mesh, "ring", 8)

    def test_windowed_generate_equals_windowed_forward(self, params):
        prompt = jax.random.randint(jax.random.PRNGKey(50), (2, 30), 0,
                                    CFG.vocab_size)
        out = generate(params, prompt, self.WCFG, 8,
                       jax.random.PRNGKey(0))
        want = full_forward_greedy(params, prompt, 8, cfg=self.WCFG)
        np.testing.assert_array_equal(np.asarray(out.tokens),
                                      np.asarray(want))
        # the window genuinely bites at these lengths: full attention
        # decodes differently
        out_full = generate(params, prompt, CFG, 8, jax.random.PRNGKey(0))
        assert bool((out.tokens != out_full.tokens).any())

    @pytest.mark.parametrize("q_start,n_q", [(700, 1), (700, 3), (120, 1)])
    def test_blockwise_window_matches_dense_formula(self, q_start, n_q):
        """q_start 700 with window 128 puts the loop's lower bound at
        block 2 — the skipped leading blocks must not change the result
        (and corrupting them must have no effect)."""
        from tony_tpu.models import decode as D
        w = 128
        ks = jax.random.split(jax.random.PRNGKey(60), 3)
        max_len, kv, h, d = 1024, 2, 4, 16
        q = jax.random.normal(ks[0], (2, n_q, h, d), jnp.float32)
        k_cache = jax.random.normal(ks[1], (2, max_len, kv, d), jnp.float32)
        v_cache = jax.random.normal(ks[2], (2, max_len, kv, d), jnp.float32)
        got = D._cached_attention_blockwise(
            q, D.kv_from_wire({"k": k_cache[None], "v": v_cache[None]}),
            0, jnp.asarray(q_start), attn_window=w)
        # dense masked oracle
        q_pos = q_start + jnp.arange(n_q)
        k_pos = jnp.arange(max_len)
        mask = ((k_pos[None, :] <= q_pos[:, None])
                & (q_pos[:, None] - k_pos[None, :] < w))
        group = h // kv
        qg = q.reshape(2, n_q, kv, group, d)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache) * d ** -0.5
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        want = jnp.einsum("bkgqs,bskd->bqkgd", p,
                          v_cache).reshape(2, n_q, h, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        # out-of-window cache rows are never read: corrupt them
        if q_start - w > 0:
            kc = k_cache.at[:, :q_start - w].set(1e4)
            vc = v_cache.at[:, :q_start - w].set(-1e4)
            got2 = D._cached_attention_blockwise(
                q, D.kv_from_wire({"k": kc[None], "v": vc[None]}), 0,
                jnp.asarray(q_start), attn_window=w)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(got2))

    def test_window_composes_with_int8_cache(self, params):
        """attn_window + kv_cache_dtype="int8" together: windowed quant
        generate equals the same windowed quant full-forward chain only
        approximately (int8), so assert the serving-relevant exactness
        instead — blockwise quant windowed == dense-on-dequantized
        windowed."""
        from tony_tpu.models import decode as D
        w = 128
        ks = jax.random.split(jax.random.PRNGKey(61), 3)
        max_len, kv, h, d = 1024, 2, 4, 16
        q = jax.random.normal(ks[0], (2, 1, h, d), jnp.float32)
        k_c = jax.random.normal(ks[1], (2, max_len, kv, d), jnp.float32)
        v_c = jax.random.normal(ks[2], (2, max_len, kv, d), jnp.float32)
        kq, ksc = D._kv_quantize(k_c[None])
        vq, vsc = D._kv_quantize(v_c[None])
        got = D._cached_attention_blockwise(
            q, D.kv_from_wire({"k": kq, "v": vq, "k_scale": ksc,
                               "v_scale": vsc}), 0,
            jnp.asarray(700), attn_window=w)
        want = D._cached_attention_blockwise(
            q, D.kv_from_wire({"k": kq.astype(jnp.float32) * ksc,
                               "v": vq.astype(jnp.float32) * vsc}), 0,
            jnp.asarray(700), attn_window=w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-2)


class TestRollingCache:
    """Rolling (ring-buffer) KV cache: O(capacity) memory however long
    the stream runs. Requires a sliding window (full-causal queries need
    the history the ring overwrote); reads mask rows by their ring
    offset from each query's absolute position."""

    LCFG = CFG.scaled(attn_window=24)
    RCFG = LCFG.scaled(kv_cache_capacity=32)

    def test_validation(self):
        with pytest.raises(ValueError, match="attn_window"):
            CFG.scaled(kv_cache_capacity=32)
        with pytest.raises(ValueError, match="kv_cache_capacity"):
            CFG.scaled(attn_window=24, kv_cache_capacity=8)

    def test_cache_is_capacity_sized(self):
        c = init_kv_cache(self.RCFG, 2, 999)
        assert c["k"].shape[2] == 32

    def test_oversized_capacity_warns_o_capacity_cost(self):
        """_ring_cached_attention is dense over ALL capacity rows every
        step: capacity a large multiple of the window silently pays
        O(capacity) per token, not O(window) — init warns once. A
        capacity near the window (the intended regime) stays quiet."""
        import warnings

        big = CFG.scaled(attn_window=24, kv_cache_capacity=96)
        with pytest.warns(UserWarning, match=r"O\(capacity\)"):
            init_kv_cache(big, 1, 999)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            init_kv_cache(self.RCFG, 1, 999)     # 32 rows, window 24

    def test_ring_generate_equals_linear_windowed(self, params):
        """Same positions attended, same math: ring generate matches the
        linear windowed-cache generate (prompt shorter than capacity —
        no wraparound reordering of the softmax rows)."""
        prompt = jax.random.randint(jax.random.PRNGKey(70), (2, 20), 0,
                                    CFG.vocab_size)
        out_lin = generate(params, prompt, self.LCFG, 30,
                           jax.random.PRNGKey(0))
        out_ring = generate(params, prompt, self.RCFG, 30,
                            jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(out_lin.tokens),
                                      np.asarray(out_ring.tokens))

    def test_generation_far_past_capacity(self, params):
        """The headline property: generate 3x the ring capacity in one
        stream — the fixed 32-row cache serves a 96-token generation —
        and the stream stays in close agreement with the linear windowed
        reference (jit partitioning rounds differently; wraparound
        reorders softmax row order, so bit-equality is not the
        contract past capacity)."""
        prompt = jax.random.randint(jax.random.PRNGKey(71), (1, 10), 0,
                                    CFG.vocab_size)
        out = generate(params, prompt, self.RCFG, 96,
                       jax.random.PRNGKey(0))
        tk = np.asarray(out.tokens)
        assert tk.shape == (1, 106)
        assert (tk >= 0).all() and (tk < CFG.vocab_size).all()
        ref = generate(params, prompt, self.LCFG, 96,
                       jax.random.PRNGKey(0))
        agree = (tk == np.asarray(ref.tokens)).mean()
        assert agree > 0.8, agree

    def test_prompt_longer_than_capacity(self, params):
        """Prefill keeps only the last `capacity` prompt rows — all a
        windowed query can ever reach. First decode logits must match
        the linear windowed cache's exactly (same eager prefill math)."""
        from tony_tpu.models import decode as D
        prompt = jax.random.randint(jax.random.PRNGKey(72), (2, 45), 0,
                                    CFG.vocab_size)
        lg_r, c_r = D.prefill(params, prompt, self.RCFG, max_len=60)
        lg_l, c_l = D.prefill(params, prompt, self.LCFG, max_len=60)
        np.testing.assert_array_equal(np.asarray(lg_r), np.asarray(lg_l))
        nxt = jnp.argmax(lg_r, -1)
        s_r, _ = D.decode_step(params, nxt, c_r, c_r["length"], self.RCFG)
        s_l, _ = D.decode_step(params, nxt, c_l, c_l["length"], self.LCFG)
        np.testing.assert_allclose(np.asarray(s_r), np.asarray(s_l),
                                   rtol=2e-4, atol=2e-4)

    def test_batcher_slots_independent(self, params):
        """2-slot ring serving == each request through a 1-slot batcher
        (same jit partitioning on both sides — exact), including a
        request whose prompt exceeds the capacity and one that runs
        past max_len (the ring lifts the length ceiling)."""
        from tony_tpu.models.serve import ContinuousBatcher
        rs = np.random.RandomState(5)
        prompts = [list(rs.randint(0, CFG.vocab_size, size=n))
                   for n in (10, 45)]
        budgets = [60, 20]
        b2 = ContinuousBatcher(params, self.RCFG, batch=2, max_len=48,
                               chunk=4)
        outs = b2.serve(prompts, max_new_tokens=budgets)
        for i, p in enumerate(prompts):
            b1 = ContinuousBatcher(params, self.RCFG, batch=1,
                                   max_len=48, chunk=4)
            solo = b1.serve([p], max_new_tokens=[budgets[i]])
            assert outs[i] == solo[0], f"request {i}"

    def test_refusals(self, params):
        from tony_tpu.models import decode as D
        from tony_tpu.models.serve import (ContinuousBatcher,
                                           SpeculativeContinuousBatcher)
        prompt = jax.random.randint(jax.random.PRNGKey(73), (1, 8), 0,
                                    CFG.vocab_size)
        with pytest.raises(ValueError, match="linear KV cache"):
            D.beam_search(params, prompt, self.RCFG, 4)
        with pytest.raises(ValueError, match="linear KV cache"):
            D.speculative_generate_device(params, params, prompt,
                                          self.RCFG, self.RCFG,
                                          max_new_tokens=4)
        with pytest.raises(ValueError, match="linear KV cache"):
            ContinuousBatcher(params, self.RCFG, batch=1, max_len=32,
                              shared_prefix=[1, 2, 3])
        with pytest.raises(ValueError, match="linear KV"):
            SpeculativeContinuousBatcher(params, self.RCFG, params,
                                         self.RCFG, batch=1, max_len=32)

    def test_int8_ring_composes(self, params):
        cfg = self.RCFG.scaled(kv_cache_dtype="int8")
        prompt = jax.random.randint(jax.random.PRNGKey(74), (2, 12), 0,
                                    CFG.vocab_size)
        out = generate(params, prompt, cfg, 50, jax.random.PRNGKey(0))
        tk = np.asarray(out.tokens)
        assert tk.shape == (2, 62)
        assert (tk >= 0).all() and (tk < CFG.vocab_size).all()


class TestWindowCombinations:
    """Feature-combination coverage: sliding-window models (linear
    cache) through the chunked-verify, beam, and serving paths — the
    window mask must hold for K>1 chunk queries and per-row frontiers,
    not just single-step decode."""

    WCFG = CFG.scaled(attn_window=24)

    def test_speculative_equals_windowed_greedy(self, params):
        """Chunked verify under a window: the draft's chunk and the
        target's k+1-wide verify both mask by the window, so the device
        speculative program still reproduces windowed greedy exactly."""
        from tony_tpu.models.decode import speculative_generate_device
        prompt = jax.random.randint(jax.random.PRNGKey(80), (2, 30), 0,
                                    CFG.vocab_size)
        want = generate(params, prompt, self.WCFG, 16,
                        jax.random.PRNGKey(0)).tokens
        got = speculative_generate_device(
            params, params, prompt, self.WCFG, self.WCFG,
            max_new_tokens=16, num_speculative=3)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # non-vacuity: the window genuinely bites at this prompt — a
        # path that silently ignored attn_window would NOT match `want`
        full = generate(params, prompt, CFG, 16,
                        jax.random.PRNGKey(0)).tokens
        assert bool((want != full).any())

    def test_beam_width_one_equals_windowed_greedy(self, params):
        from tony_tpu.models.decode import beam_search
        prompt = jax.random.randint(jax.random.PRNGKey(81), (2, 28), 0,
                                    CFG.vocab_size)
        bs = beam_search(params, prompt, self.WCFG, 12, beam_width=1)
        g = generate(params, prompt, self.WCFG, 12, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(bs.tokens[:, 0]),
                                      np.asarray(g.tokens))
        # non-vacuity: windowed differs from full attention here
        full = generate(params, prompt, CFG, 12, jax.random.PRNGKey(0))
        assert bool((g.tokens != full.tokens).any())

    def test_serving_token_identical_under_window(self, params):
        """Continuous batching with a windowed model (linear cache):
        per-request outputs equal solo windowed generate, including a
        reused slot."""
        from tony_tpu.models.serve import ContinuousBatcher
        rs = np.random.RandomState(9)
        prompts = [list(rs.randint(0, CFG.vocab_size, size=n))
                   for n in (26, 30, 28)]
        b = ContinuousBatcher(params, self.WCFG, batch=2, max_len=48,
                              chunk=4)
        outs = b.serve(prompts, max_new_tokens=8)
        diverged = False
        for i, p in enumerate(prompts):
            pm = jnp.asarray(p, jnp.int32)[None]
            want = generate(params, pm, self.WCFG, 8, jax.random.PRNGKey(0))
            assert outs[i] == [int(t) for t in
                               np.asarray(want.tokens[0, len(p):])], i
            full = generate(params, pm, CFG, 8, jax.random.PRNGKey(0))
            diverged |= bool((want.tokens != full.tokens).any())
        # non-vacuity: at least one request's windowed output differs
        # from full attention
        assert diverged

