"""Parallel-layer tests on the virtual 8-device CPU mesh (conftest.py).

Covers the green-field strategies SURVEY.md §2.3 flags as absent from the
reference and first-class here: mesh construction/presets, logical sharding
rules, ring attention (CP), GPipe pipelining (PP), and MoE dispatch (EP).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tony_tpu.parallel import (
    logical_to_spec,
    make_mesh,
    moe_ffn,
    parse_mesh_string,
    pipeline_apply,
    ring_attention,
)


@pytest.fixture(scope="module")
def rng():
    return np.random.RandomState(42)


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------

class TestMesh:
    def test_explicit_axes(self):
        mesh = make_mesh({"dp": 2, "tp": 4})
        assert dict(mesh.shape) == {"dp": 2, "tp": 4}

    def test_inferred_axis(self):
        mesh = make_mesh({"dp": -1, "tp": 2})
        assert mesh.shape["dp"] == 4

    def test_default_is_pure_dp(self):
        mesh = make_mesh(None)
        assert dict(mesh.shape) == {"dp": 8}

    def test_canonical_axis_order(self):
        # minor-most (fastest ICI) axis must be tp regardless of dict order
        mesh = make_mesh({"tp": 2, "pp": 2, "dp": 2})
        assert mesh.axis_names == ("pp", "dp", "tp")

    def test_size_mismatch_raises(self):
        with pytest.raises(ValueError):
            make_mesh({"dp": 3})

    def test_two_inferred_raises(self):
        with pytest.raises(ValueError):
            make_mesh({"dp": -1, "tp": -1})

    def test_parse_mesh_string(self):
        assert parse_mesh_string("dp=2, tp=4") == {"dp": 2, "tp": 4}
        assert parse_mesh_string("") == {}
        with pytest.raises(ValueError):
            parse_mesh_string("dp")


class TestHybridMesh:
    """Multi-slice meshes: dcn axes across slices, ici axes within."""

    def test_dcn_major_ici_minor(self):
        from tony_tpu.parallel.mesh import make_hybrid_mesh
        mesh = make_hybrid_mesh({"tp": 2, "fsdp": 2}, {"dp": 2})
        assert mesh.axis_names == ("dp", "fsdp", "tp")   # dcn axis major
        assert dict(mesh.shape) == {"dp": 2, "fsdp": 2, "tp": 2}
        # contiguous device halves = the two slices (process ids are
        # slice-major, so this matches real multi-slice layout)
        import numpy as np
        devs = np.asarray(mesh.devices)
        first_slice = devs[0].ravel()
        assert [d.id for d in first_slice] == [0, 1, 2, 3]

    def test_ici_inference(self):
        from tony_tpu.parallel.mesh import make_hybrid_mesh
        mesh = make_hybrid_mesh({"tp": -1}, {"dp": 2})
        assert dict(mesh.shape) == {"dp": 2, "tp": 4}

    def test_no_dcn_falls_back_to_flat(self):
        from tony_tpu.parallel.mesh import make_hybrid_mesh
        mesh = make_hybrid_mesh({"dp": 8}, {})
        assert dict(mesh.shape) == {"dp": 8}

    def test_empty_ici_avoids_dcn_name_collision(self):
        # dcn dp + no tony.application.mesh is the documented common case
        from tony_tpu.parallel.mesh import make_hybrid_mesh
        mesh = make_hybrid_mesh({}, {"dp": 2})
        assert dict(mesh.shape) == {"dp": 2, "fsdp": 4}
        assert mesh.axis_names == ("dp", "fsdp")

    def test_errors(self):
        from tony_tpu.parallel.mesh import make_hybrid_mesh
        with pytest.raises(ValueError, match="explicit"):
            make_hybrid_mesh({"tp": 4}, {"dp": -1})
        with pytest.raises(ValueError, match="do not split"):
            make_hybrid_mesh({"tp": 4}, {"dp": 3})
        with pytest.raises(ValueError, match="both"):
            make_hybrid_mesh({"dp": 4}, {"dp": 2})

    def test_train_step_over_hybrid_mesh(self):
        """A dp-across-slices × tp-inside sharded step runs and is finite —
        the tony.{job}.slices=2 data path on the virtual backend."""
        import jax.numpy as jnp
        from tony_tpu.models import transformer as T
        from tony_tpu.models.train import (default_optimizer, init_state,
                                           make_train_step)
        from tony_tpu.parallel.mesh import make_hybrid_mesh
        from tony_tpu.parallel.sharding import shard_pytree
        mesh = make_hybrid_mesh({"tp": -1}, {"dp": 2})
        cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32)
        params = shard_pytree(T.init_params(jax.random.PRNGKey(0), cfg),
                              T.logical_axes(cfg), mesh)
        opt = default_optimizer(lr=1e-3)
        state = init_state(params, opt)
        step = make_train_step(lambda p, b: T.lm_loss(p, b, cfg, mesh),
                               opt, mesh)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0,
                                    cfg.vocab_size)
        batch = {"inputs": tokens[:, :64], "targets": tokens[:, 1:]}
        _, metrics = step(state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

class TestShardingRules:
    def test_batch_maps_to_dp_fsdp(self):
        mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
        spec = logical_to_spec(("batch", "embed", "mlp"), mesh)
        # fsdp is consumed by batch, so embed (same array) must replicate —
        # a mesh axis may shard at most one dim of an array
        assert spec == P(("dp", "fsdp"), None, "tp")

    def test_params_get_fsdp_on_embed(self):
        mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
        assert logical_to_spec(("embed", "mlp"), mesh) == P("fsdp", "tp")

    def test_missing_axes_drop_to_replication(self):
        mesh = make_mesh({"dp": 8})
        spec = logical_to_spec(("batch", "embed", "mlp"), mesh)
        assert spec == P("dp", None, None)

    def test_unknown_logical_name_replicates(self):
        mesh = make_mesh({"dp": 8})
        assert logical_to_spec(("nonesuch",), mesh) == P(None)

    def test_pure_fsdp_activation_no_duplicate_axis(self):
        # regression: ("batch","embed") on {"fsdp": 8} must not produce
        # PartitionSpec("fsdp","fsdp") (DuplicateSpecError under jax)
        mesh = make_mesh({"fsdp": 8})
        spec = logical_to_spec(("batch", "embed"), mesh)
        assert spec == P("fsdp", None)
        from tony_tpu.parallel import logical_sharding
        logical_sharding(("batch", "embed"), mesh)  # must not raise

    def test_param_shardings_tuple_pytree(self):
        # regression: ((W_axes, b_axes), ...) containers must not be
        # swallowed as a single leaf (silent full replication)
        from tony_tpu.parallel import param_shardings
        mesh = make_mesh({"fsdp": 8})
        tree = (("embed", "mlp"), ("mlp",))
        got = param_shardings(tree, mesh)
        assert got[0].spec == P("fsdp", None)
        assert got[1].spec == P(None)


# ---------------------------------------------------------------------------
# ring attention (context parallelism)
# ---------------------------------------------------------------------------

def _dense_attention(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


class TestRingAttention:
    @pytest.fixture(scope="class")
    def qkv(self):
        r = np.random.RandomState(0)
        shape = (2, 32, 4, 16)
        return tuple(jnp.asarray(r.randn(*shape), jnp.float32)
                     for _ in range(3))

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, qkv, causal):
        q, k, v = qkv
        mesh = make_mesh({"dp": 2, "cp": 4})
        out = ring_attention(q, k, v, mesh, causal=causal)
        expect = _dense_attention(q, k, v, causal)
        np.testing.assert_allclose(out, expect, atol=2e-5)

    @pytest.mark.slow
    def test_gradients_match_dense(self, qkv):
        q, k, v = qkv
        mesh = make_mesh({"dp": 2, "cp": 4})
        g = jax.grad(lambda *a: ring_attention(*a, mesh).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: _dense_attention(*a, True).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(g, gr):
            np.testing.assert_allclose(got, want, atol=2e-5)

    @pytest.fixture(scope="class")
    def qkv_gqa(self):
        r = np.random.RandomState(4)
        q = jnp.asarray(r.randn(2, 32, 4, 16), jnp.float32)
        k = jnp.asarray(r.randn(2, 32, 2, 16), jnp.float32)
        v = jnp.asarray(r.randn(2, 32, 2, 16), jnp.float32)
        return q, k, v

    def _dense_gqa(self, q, k, v, causal):
        return _dense_attention(q, jnp.repeat(k, 2, 2),
                                jnp.repeat(v, 2, 2), causal)

    @pytest.mark.parametrize("causal", [True, False])
    def test_gqa_rides_ring_unexpanded(self, qkv_gqa, causal):
        """GQA K/V rotate unexpanded (the ppermute payload is the ring's
        whole inter-chip cost) and expand locally per hop."""
        q, k, v = qkv_gqa
        mesh = make_mesh({"dp": 2, "cp": 4})
        out = ring_attention(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(out, self._dense_gqa(q, k, v, causal),
                                   atol=2e-5)

    def test_gqa_flash_arm(self, qkv_gqa, monkeypatch):
        import importlib
        R = importlib.import_module("tony_tpu.parallel.ring_attention")
        monkeypatch.setattr(R, "_USE_FLASH_CHUNKS", True)
        q, k, v = qkv_gqa
        mesh = make_mesh({"dp": 2, "cp": 4})
        out = ring_attention(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(out, self._dense_gqa(q, k, v, True),
                                   atol=2e-5)

    @pytest.mark.parametrize("kv_heads", [2, 1])
    def test_gqa_with_tp_sharded_heads(self, kv_heads):
        """GQA + a LIVE tp axis: q heads are tp-sharded, so kv heads must
        shard over the same axis (kv_heads % tp == 0) or expand — local
        j // rep pairing on replicated kv heads computes WRONG attention
        (regression for the mis-pairing bug)."""
        r = np.random.RandomState(6)
        q = jnp.asarray(r.randn(2, 32, 4, 16), jnp.float32)
        k = jnp.asarray(r.randn(2, 32, kv_heads, 16), jnp.float32)
        v = jnp.asarray(r.randn(2, 32, kv_heads, 16), jnp.float32)
        mesh = make_mesh({"dp": 2, "cp": 2, "tp": 2})
        out = ring_attention(q, k, v, mesh, causal=True)
        rep = 4 // kv_heads
        want = _dense_attention(q, jnp.repeat(k, rep, 2),
                                jnp.repeat(v, rep, 2), True)
        np.testing.assert_allclose(out, want, atol=2e-5)

    def test_gqa_indivisible_heads_raises(self, qkv_gqa):
        q, k, v = qkv_gqa
        mesh = make_mesh({"dp": 2, "cp": 4})
        with pytest.raises(ValueError, match="divide"):
            ring_attention(q, k[:, :, :1].repeat(3, 2)[:, :, :3], v, mesh)

    @pytest.mark.slow
    def test_gqa_gradients(self, qkv_gqa):
        q, k, v = qkv_gqa
        mesh = make_mesh({"dp": 2, "cp": 4})
        g = jax.grad(lambda *a: ring_attention(*a, mesh).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: self._dense_gqa(*a, True).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(g, gr):
            assert got.shape == want.shape    # dK/dV stay kv_heads-wide
            np.testing.assert_allclose(got, want, atol=3e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_chunk_arm_matches_dense(self, qkv, causal, monkeypatch):
        """The TPU arm (flash kernels per hop + logsumexp merge), forced on
        in interpret mode: values must match the dense oracle."""
        import importlib
        R = importlib.import_module("tony_tpu.parallel.ring_attention")
        monkeypatch.setattr(R, "_USE_FLASH_CHUNKS", True)
        q, k, v = qkv
        mesh = make_mesh({"dp": 2, "cp": 4})
        out = ring_attention(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(out, _dense_attention(q, k, v, causal),
                                   atol=2e-5)

    @pytest.mark.slow
    def test_flash_chunk_arm_gradients(self, qkv, monkeypatch):
        """Backward through the flash arm: d(lse) flows through the merge
        into the chunk kernels (the transposed ring)."""
        import importlib
        R = importlib.import_module("tony_tpu.parallel.ring_attention")
        monkeypatch.setattr(R, "_USE_FLASH_CHUNKS", True)
        q, k, v = qkv
        mesh = make_mesh({"dp": 2, "cp": 4})
        g = jax.grad(lambda *a: (ring_attention(*a, mesh) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: (_dense_attention(*a, True) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(g, gr):
            np.testing.assert_allclose(got, want, atol=5e-5)

    def test_no_cp_axis_fallback(self, qkv):
        q, k, v = qkv
        mesh = make_mesh({"dp": 2, "tp": 4})
        out = ring_attention(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(out, _dense_attention(q, k, v, True),
                                   atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_no_cp_axis_flash_engine(self, qkv, causal, monkeypatch):
        import importlib
        R = importlib.import_module("tony_tpu.parallel.ring_attention")
        monkeypatch.setattr(R, "_USE_FLASH_CHUNKS", True)
        q, k, v = qkv
        mesh = make_mesh({"dp": 2, "tp": 4})
        out = ring_attention(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(out, _dense_attention(q, k, v, causal),
                                   atol=2e-5)

    def test_heads_over_tp(self, qkv):
        q, k, v = qkv
        mesh = make_mesh({"cp": 4, "tp": 2})
        out = ring_attention(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(out, _dense_attention(q, k, v, True),
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# pipeline parallelism
# ---------------------------------------------------------------------------

class TestShardAttention:
    """``shard_attention``: the island every attention arm rides on a
    multi-device mesh (the flash kernel cannot be partitioned by the
    compiler). The flash arm runs here in interpret mode — the wrapper,
    specs and GQA expansion are what the chip runs too."""

    @staticmethod
    def _mesh(axes):
        n = int(np.prod(list(axes.values())))
        return make_mesh(axes, devices=jax.devices()[:n])

    @staticmethod
    def _qkv(b, h, hk, seed=0):
        r = np.random.RandomState(seed)
        q = jnp.asarray(r.randn(b, 32, h, 16), jnp.float32)
        k = jnp.asarray(r.randn(b, 32, hk, 16), jnp.float32)
        v = jnp.asarray(r.randn(b, 32, hk, 16), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("axes,b,h,hk", [
        ({"dp": 2, "tp": 2}, 4, 4, 4),      # MHA: batch over dp, heads tp
        ({"dp": 2, "tp": 2}, 4, 4, 2),      # GQA, tp | kv heads: unexpanded
        ({"dp": 2, "tp": 4}, 4, 4, 2),      # GQA, tp ∤ kv heads: expanded
        ({"dp": 2, "fsdp": 2, "tp": 2}, 4, 4, 4),   # batch over dp AND fsdp
        ({"dp": 4, "tp": 2}, 2, 4, 4),      # dp ∤ batch: batch replicates
        ({"dp": 2, "tp": 4}, 4, 6, 6),      # tp ∤ heads: heads replicate
    ])
    def test_flash_island_matches_dense(self, axes, b, h, hk):
        from tony_tpu.ops.attention import flash_attention
        from tony_tpu.parallel.sharding import shard_attention
        q, k, v = self._qkv(b, h, hk)
        mesh = self._mesh(axes)
        attn = functools.partial(flash_attention, causal=True)
        out = jax.jit(lambda *a: shard_attention(attn, *a, mesh))(q, k, v)
        rep = h // hk
        want = _dense_attention(q, jnp.repeat(k, rep, 2),
                                jnp.repeat(v, rep, 2), True)
        np.testing.assert_allclose(out, want, atol=2e-5)

    def test_gradients_match_dense(self):
        from tony_tpu.ops.attention import flash_attention
        from tony_tpu.parallel.sharding import shard_attention
        q, k, v = self._qkv(4, 4, 2, seed=1)
        mesh = self._mesh({"dp": 2, "tp": 2})
        attn = functools.partial(flash_attention, causal=True)
        g = jax.grad(lambda *a: (shard_attention(attn, *a, mesh) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: (_dense_attention(
            q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(g, gr):
            np.testing.assert_allclose(got, want, atol=5e-5)

    def test_no_mesh_or_one_device_is_a_plain_call(self):
        from tony_tpu.parallel.sharding import shard_attention
        q, k, v = self._qkv(2, 4, 4)
        calls = []

        def attn(q, k, v):
            calls.append(q.shape)
            return q
        shard_attention(attn, q, k, v, None)
        shard_attention(attn, q, k, v, self._mesh({"dp": 1}))
        assert calls == [q.shape, q.shape]      # global shapes: no island

    def test_ambient_mesh_is_used(self):
        """``mesh=None`` under ``jax.set_mesh`` (the sharded serve path:
        prefill calls ``_attention`` with no mesh argument)."""
        from tony_tpu.parallel.sharding import shard_attention
        q, k, v = self._qkv(4, 4, 4)
        seen = []

        def attn(q, k, v):
            seen.append(q.shape)
            return q
        with jax.set_mesh(self._mesh({"dp": 2, "tp": 2})):
            jax.jit(lambda *a: shard_attention(attn, *a, None))(q, k, v)
        assert seen == [(2, 32, 2, 16)]         # per-device shard


class TestPipeline:
    @staticmethod
    def _stage(p, h):
        w, b = p
        return jnp.tanh(h @ w + b)

    @pytest.fixture(scope="class")
    def problem(self):
        r = np.random.RandomState(1)
        s, b, d = 4, 8, 16
        W = jnp.asarray(r.randn(s, d, d) * 0.1, jnp.float32)
        bias = jnp.asarray(r.randn(s, d) * 0.1, jnp.float32)
        x = jnp.asarray(r.randn(b, d), jnp.float32)
        h = x
        for i in range(s):
            h = jnp.tanh(h @ W[i] + bias[i])
        return W, bias, x, h

    def test_matches_sequential(self, problem):
        W, b, x, want = problem
        mesh = make_mesh({"pp": 4, "dp": 2})
        out = pipeline_apply(self._stage, (W, b), x, mesh, num_microbatches=4)
        np.testing.assert_allclose(out, want, atol=1e-6)

    @pytest.mark.slow
    def test_gradients_match_sequential(self, problem):
        W, b, x, _ = problem
        mesh = make_mesh({"pp": 4, "dp": 2})

        def ref_loss(W, b):
            h = x
            for i in range(W.shape[0]):
                h = self._stage((W[i], b[i]), h)
            return h.sum()

        got = jax.grad(lambda W, b: pipeline_apply(
            self._stage, (W, b), x, mesh, num_microbatches=4).sum(),
            argnums=(0, 1))(W, b)
        want = jax.grad(ref_loss, argnums=(0, 1))(W, b)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-5)

    def test_degenerate_no_pp_axis(self, problem):
        W, b, x, want = problem
        mesh = make_mesh({"dp": 8})
        out = pipeline_apply(self._stage, (W, b), x, mesh, num_microbatches=2)
        np.testing.assert_allclose(out, want, atol=1e-6)

    def test_indivisible_microbatches_raises(self, problem):
        W, b, x, _ = problem
        mesh = make_mesh({"pp": 4, "dp": 2})
        with pytest.raises(ValueError):
            pipeline_apply(self._stage, (W, b), x, mesh, num_microbatches=3)

    def test_indivisible_microbatches_raises_without_pp(self, problem):
        # regression: validation must not be skipped on the degenerate path
        W, b, x, _ = problem
        mesh = make_mesh({"dp": 8})
        with pytest.raises(ValueError):
            pipeline_apply(self._stage, (W, b), x, mesh, num_microbatches=3)

    def test_stage_count_mismatch_raises(self, problem):
        # regression: 4 stages over pp=2 silently dropped stages 1 and 3
        W, b, x, _ = problem
        mesh = make_mesh({"pp": 2, "dp": 4})
        with pytest.raises(ValueError, match="stacked stages"):
            pipeline_apply(self._stage, (W, b), x, mesh, num_microbatches=4)


# ---------------------------------------------------------------------------
# pipeline parallelism on the flagship transformer (forward routes through
# the GPipe schedule automatically when the mesh has a pp axis > 1)
# ---------------------------------------------------------------------------

class TestPipelineTransformer:
    @pytest.fixture(scope="class")
    def setup(self):
        from tony_tpu.models import transformer as T
        from tony_tpu.parallel import shard_pytree

        cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=True)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 65), 0,
                                    cfg.vocab_size)
        batch = {"inputs": tokens[:, :64], "targets": tokens[:, 1:65]}
        ref_loss = float(T.lm_loss(params, batch, cfg, None))
        return T, shard_pytree, cfg, params, batch, ref_loss

    def test_pp_loss_matches_unpipelined(self, setup):
        T, shard_pytree, cfg, params, batch, ref_loss = setup
        mesh = make_mesh({"pp": 2, "dp": 4})
        sp = shard_pytree(params, T.logical_axes(cfg), mesh)
        loss = jax.jit(lambda p, b: T.lm_loss(p, b, cfg, mesh))(sp, batch)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)

    def test_pp4_loss_matches_unpipelined(self, setup):
        # pp = n_layers/... : tiny has 2 layers, so scale to 4 for pp=4
        T, shard_pytree, cfg, params, batch, ref_loss = setup
        cfg4 = cfg.scaled(n_layers=4)
        params4 = T.init_params(jax.random.PRNGKey(3), cfg4)
        ref = float(T.lm_loss(params4, batch, cfg4, None))
        mesh = make_mesh({"pp": 4, "dp": 2})
        sp = shard_pytree(params4, T.logical_axes(cfg4), mesh)
        loss = jax.jit(lambda p, b: T.lm_loss(p, b, cfg4, mesh))(sp, batch)
        np.testing.assert_allclose(float(loss), ref, rtol=1e-5)

    @pytest.mark.slow
    def test_pp_gradients_match_unpipelined(self, setup):
        T, shard_pytree, cfg, params, batch, _ = setup
        mesh = make_mesh({"pp": 2, "dp": 4})
        sp = shard_pytree(params, T.logical_axes(cfg), mesh)
        g_ref = jax.grad(lambda p: T.lm_loss(p, batch, cfg, None))(params)
        g_pp = jax.jit(
            jax.grad(lambda p: T.lm_loss(p, batch, cfg, mesh)))(sp)
        flat_ref, _ = jax.tree_util.tree_flatten_with_path(g_ref)
        for (path, a), b in zip(flat_ref, jax.tree.leaves(g_pp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=str(path))

    def test_1f1b_loss_and_grads_match_unpipelined(self, setup):
        """The 1F1B schedule (explicit-vjp pipeline, O(pp) live
        activations) reproduces the unsharded model's loss AND full
        gradient pytree."""
        T, shard_pytree, cfg, params, batch, ref_loss = setup
        mesh = make_mesh({"pp": 2, "dp": 4})
        sp = shard_pytree(params, T.logical_axes(cfg), mesh)
        g_ref = jax.grad(lambda p: T.lm_loss(p, batch, cfg, None))(params)
        with jax.set_mesh(mesh):
            loss, g = jax.jit(lambda p, b: T.lm_value_and_grad(
                p, b, cfg, mesh))(sp, batch)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)
        flat_ref, _ = jax.tree_util.tree_flatten_with_path(g_ref)
        for (path, a), b in zip(flat_ref, jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=str(path))

    def test_1f1b_matches_gpipe_grads(self, setup):
        """Same mesh, same microbatching: the two schedules must agree on
        loss and gradients (they compute the same math in a different
        order)."""
        T, shard_pytree, cfg, params, batch, _ = setup
        mesh = make_mesh({"pp": 2, "dp": 4})
        sp = shard_pytree(params, T.logical_axes(cfg), mesh)
        with jax.set_mesh(mesh):
            l_gp, g_gp = jax.jit(jax.value_and_grad(
                lambda p: T.lm_loss(p, batch, cfg, mesh)))(sp)
            l_1f, g_1f = jax.jit(lambda p, b: T.lm_value_and_grad(
                p, b, cfg, mesh))(sp, batch)
        np.testing.assert_allclose(float(l_1f), float(l_gp), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g_gp), jax.tree.leaves(g_1f)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5)

    def test_1f1b_pp4_deep_schedule(self, setup):
        """pp=4 with M > S microbatches exercises warmup, steady 1F1B
        cadence, and cooldown on every stage."""
        T, shard_pytree, cfg, params, batch, _ = setup
        cfg4 = cfg.scaled(n_layers=4, pp_microbatches=8)
        params4 = T.init_params(jax.random.PRNGKey(3), cfg4)
        ref_loss, g_ref = jax.value_and_grad(
            lambda p: T.lm_loss(p, batch, cfg4, None))(params4)
        mesh = make_mesh({"pp": 4, "dp": 2})
        sp = shard_pytree(params4, T.logical_axes(cfg4), mesh)
        with jax.set_mesh(mesh):
            loss, g = jax.jit(lambda p, b: T.lm_value_and_grad(
                p, b, cfg4, mesh))(sp, batch)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        flat_ref, _ = jax.tree_util.tree_flatten_with_path(g_ref)
        for (path, a), b in zip(flat_ref, jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=str(path))

    def test_1f1b_tp_sharded_head(self, setup):
        """pp x tp: the loss head runs vocab-SHARDED inside the pipeline
        (distributed logsumexp + psum'd picked logit; activation
        cotangent psum'd over tp) and still reproduces the unsharded
        loss and gradients — the memory parity point with GPipe's
        propagated head sharding."""
        T, shard_pytree, cfg, params, batch, ref_loss = setup
        mesh = make_mesh({"pp": 2, "tp": 2, "dp": 2})
        sp = shard_pytree(params, T.logical_axes(cfg), mesh)
        g_ref = jax.grad(lambda p: T.lm_loss(p, batch, cfg, None))(params)
        with jax.set_mesh(mesh):
            loss, g = jax.jit(lambda p, b: T.lm_value_and_grad(
                p, b, cfg, mesh))(sp, batch)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)
        flat_ref, _ = jax.tree_util.tree_flatten_with_path(g_ref)
        for (path, a), b in zip(flat_ref, jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=str(path))

    def test_1f1b_degenerate_no_pp_axis(self, setup):
        """Without a pp axis the same entry point falls back to plain AD
        and still matches the reference."""
        T, shard_pytree, cfg, params, batch, ref_loss = setup
        mesh = make_mesh({"dp": 8})
        sp = shard_pytree(params, T.logical_axes(cfg), mesh)
        with jax.set_mesh(mesh):
            loss, g = jax.jit(lambda p, b: T.lm_value_and_grad(
                p, b, cfg, mesh))(sp, batch)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)
        g_ref = jax.grad(lambda p: T.lm_loss(p, batch, cfg, None))(params)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5)

    def test_1f1b_train_step_reduces_loss(self, setup):
        from tony_tpu.models.train import (default_optimizer, init_state,
                                           make_train_step)
        T, shard_pytree, cfg, params, batch, _ = setup
        mesh = make_mesh({"pp": 2, "dp": 4})
        sp = shard_pytree(jax.tree.map(jnp.copy, params),
                          T.logical_axes(cfg), mesh)
        opt = default_optimizer(lr=1e-3)
        state = init_state(sp, opt)
        step = make_train_step(
            None, opt, mesh,
            value_and_grad_fn=lambda p, b: T.lm_value_and_grad(
                p, b, cfg, mesh))
        state, m0 = step(state, batch)
        for _ in range(3):
            state, m = step(state, batch)
        assert float(m["loss"]) < float(m0["loss"])
        assert bool(jnp.isfinite(m["grad_norm"]))

    def test_1f1b_moe_replicated_experts_matches_gpipe(self, setup):
        """MoE x 1F1B with experts REPLICATED (no ep axis): the stage
        aux joins the loss inside each backward-tick vjp (one vjp covers
        the activation path and the aux path), so loss AND gradients
        match the GPipe schedule on the same mesh and microbatching."""
        T, shard_pytree, cfg, params, batch, _ = setup
        mcfg = cfg.scaled(num_experts=4)
        mparams = T.init_params(jax.random.PRNGKey(5), mcfg)
        mesh = make_mesh({"pp": 2, "dp": 4})
        sp = shard_pytree(mparams, T.logical_axes(mcfg), mesh)
        with jax.set_mesh(mesh):
            l_gp, g_gp = jax.jit(jax.value_and_grad(
                lambda p: T.lm_loss(p, batch, mcfg, mesh)))(sp, )
            l_1f, g_1f = jax.jit(lambda p, b: T.lm_value_and_grad(
                p, b, mcfg, mesh))(sp, batch)
        np.testing.assert_allclose(float(l_1f), float(l_gp), rtol=1e-6)
        flat_ref, _ = jax.tree_util.tree_flatten_with_path(g_gp)
        for (path, a), b in zip(flat_ref, jax.tree.leaves(g_1f)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=str(path))

    def test_1f1b_moe_with_tp_sharded_head_matches_gpipe(self, setup):
        """MoE x 1F1B on a pp x tp x dp mesh: the vocab-sharded head's
        psum reductions must not double-count the REPLICATED aux-path
        gradients (the aux seed pre-divides by the tp product) — loss
        and full gradients match GPipe on the same mesh (round-5 review
        caught an x-tp overcount here)."""
        T, shard_pytree, cfg, params, batch, _ = setup
        mcfg = cfg.scaled(num_experts=4)
        mparams = T.init_params(jax.random.PRNGKey(5), mcfg)
        mesh = make_mesh({"pp": 2, "tp": 2, "dp": 2})
        sp = shard_pytree(mparams, T.logical_axes(mcfg), mesh)
        with jax.set_mesh(mesh):
            l_gp, g_gp = jax.jit(jax.value_and_grad(
                lambda p: T.lm_loss(p, batch, mcfg, mesh)))(sp)
            l_1f, g_1f = jax.jit(lambda p, b: T.lm_value_and_grad(
                p, b, mcfg, mesh))(sp, batch)
        np.testing.assert_allclose(float(l_1f), float(l_gp), rtol=1e-6)
        flat_ref, _ = jax.tree_util.tree_flatten_with_path(g_gp)
        for (path, a), b in zip(flat_ref, jax.tree.leaves(g_1f)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, err_msg=str(path))

    def test_1f1b_moe_ep_sharded_rejected(self, setup):
        """ep-SHARDED experts stay on GPipe: the explicit-collective
        dispatch's psum transposes are not exact under per-rank vjps."""
        T, shard_pytree, cfg, params, batch, _ = setup
        mcfg = cfg.scaled(num_experts=4, pp_schedule="1f1b")
        mesh = make_mesh({"pp": 2, "ep": 2, "dp": 2})
        with pytest.raises(NotImplementedError, match="REPLICATED"):
            T.lm_value_and_grad(T.init_params(jax.random.PRNGKey(9), mcfg),
                                batch, mcfg, mesh)

    @pytest.mark.slow
    def test_pp_train_step_reduces_loss(self, setup):
        from tony_tpu.models.train import (default_optimizer, init_state,
                                           make_train_step)
        T, shard_pytree, cfg, params, batch, _ = setup
        mesh = make_mesh({"pp": 2, "dp": 4})
        # copy: on the CPU backend device_put aliases the host buffers, and
        # the donating train step would delete the class-scoped params
        sp = shard_pytree(jax.tree.map(jnp.copy, params),
                          T.logical_axes(cfg), mesh)
        opt = default_optimizer(lr=1e-3)
        state = init_state(sp, opt)
        step = make_train_step(lambda p, b: T.lm_loss(p, b, cfg, mesh),
                               opt, mesh)
        state, m0 = step(state, batch)
        for _ in range(3):
            state, m = step(state, batch)
        assert float(m["loss"]) < float(m0["loss"])
        assert bool(jnp.isfinite(m["grad_norm"]))

    def test_pp_over_dcn(self, setup):
        # pp across the slice (DCN) axis — ppermute is point-to-point, the
        # one collective pattern that tolerates the slow cross-slice network
        from tony_tpu.parallel.mesh import make_hybrid_mesh
        T, shard_pytree, cfg, params, batch, ref_loss = setup
        hmesh = make_hybrid_mesh({"dp": -1}, {"pp": 2})
        assert dict(hmesh.shape) == {"pp": 2, "dp": 4}
        sp = shard_pytree(params, T.logical_axes(cfg), hmesh)
        loss = jax.jit(lambda p, b: T.lm_loss(p, b, cfg, hmesh))(sp, batch)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)

    def test_pp_indivisible_layers_raises(self, setup):
        T, shard_pytree, cfg, params, batch, _ = setup
        cfg3 = cfg.scaled(n_layers=3)
        params3 = T.init_params(jax.random.PRNGKey(4), cfg3)
        mesh = make_mesh({"pp": 2, "dp": 4})
        with pytest.raises(ValueError, match="pipeline stages"):
            T.lm_loss(params3, batch, cfg3, mesh)

    def test_pp_moe_loss_matches_unpipelined(self, setup):
        """MoE composes with pipeline parallelism: the stage body runs the
        explicit-collective dispatch (moe_ffn_manual) with experts sharded
        over an ep axis orthogonal to pp, and the aux loss rides the
        pipeline's side channel. Aux is a per-microbatch mean (nonlinear
        in the routing fractions), so the match is approximate at the
        microbatch level — tight here because routing is identical."""
        T, shard_pytree, cfg, params, batch, _ = setup
        mcfg = cfg.scaled(num_experts=4)
        mparams = T.init_params(jax.random.PRNGKey(5), mcfg)
        ref = float(T.lm_loss(mparams, batch, mcfg, None))
        mesh = make_mesh({"pp": 2, "ep": 2, "dp": 2})
        sp = shard_pytree(mparams, T.logical_axes(mcfg), mesh)
        loss = jax.jit(lambda p, b: T.lm_loss(p, b, mcfg, mesh))(sp, batch)
        np.testing.assert_allclose(float(loss), ref, rtol=2e-3)

    @pytest.mark.slow
    def test_pp_moe_trains(self, setup):
        from tony_tpu.models.train import (default_optimizer, init_state,
                                           make_train_step)
        T, shard_pytree, cfg, params, batch, _ = setup
        mcfg = cfg.scaled(num_experts=4)
        mesh = make_mesh({"pp": 2, "ep": 2, "dp": 2})
        sp = shard_pytree(T.init_params(jax.random.PRNGKey(6), mcfg),
                          T.logical_axes(mcfg), mesh)
        opt = default_optimizer(lr=1e-3)
        state = init_state(sp, opt)
        step = make_train_step(lambda p, b: T.lm_loss(p, b, mcfg, mesh),
                               opt, mesh)
        state, m0 = step(state, batch)
        for _ in range(3):
            state, m = step(state, batch)
        assert float(m["loss"]) < float(m0["loss"])
        assert bool(jnp.isfinite(m["grad_norm"]))

    def test_pp_moe_without_ep_axis_matches_unpipelined(self, setup):
        """MoE + pipeline on a mesh with NO ep axis: the stage body takes
        the GSPMD-constraint dispatch (moe_ffn) with expert weights
        replicated across pp ranks, relying on constrain's Manual-axes
        fallback inside shard_map — previously an untested configuration
        (round-4 advisor finding)."""
        T, shard_pytree, cfg, params, batch, _ = setup
        mcfg = cfg.scaled(num_experts=4)
        mparams = T.init_params(jax.random.PRNGKey(5), mcfg)
        ref = float(T.lm_loss(mparams, batch, mcfg, None))
        mesh = make_mesh({"pp": 2, "dp": 4})
        sp = shard_pytree(mparams, T.logical_axes(mcfg), mesh)
        loss = jax.jit(lambda p, b: T.lm_loss(p, b, mcfg, mesh))(sp, batch)
        np.testing.assert_allclose(float(loss), ref, rtol=2e-3)

    def test_pp_moe_indivisible_experts_raises(self, setup):
        T, shard_pytree, cfg, params, batch, _ = setup
        mcfg = cfg.scaled(num_experts=3)
        mparams = T.init_params(jax.random.PRNGKey(7), mcfg)
        mesh = make_mesh({"pp": 2, "ep": 2, "dp": 2})
        with pytest.raises(ValueError, match="num_experts"):
            T.lm_loss(mparams, batch, mcfg, mesh)

    def test_pp_with_gqa(self, setup):
        """Pipeline stages run the GQA-native attention path (kv heads <
        heads) — the two features must compose."""
        T, shard_pytree, cfg, params, batch, _ = setup
        gcfg = cfg.scaled(n_kv_heads=2)
        gparams = T.init_params(jax.random.PRNGKey(7), gcfg)
        ref = float(T.lm_loss(gparams, batch, gcfg, None))
        mesh = make_mesh({"pp": 2, "dp": 4})
        sp = shard_pytree(gparams, T.logical_axes(gcfg), mesh)
        loss = jax.jit(lambda p, b: T.lm_loss(p, b, gcfg, mesh))(sp, batch)
        np.testing.assert_allclose(float(loss), ref, rtol=1e-5)

    def test_pp_explicit_microbatches(self, setup):
        T, shard_pytree, cfg, params, batch, ref_loss = setup
        mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
        cfg_m = cfg.scaled(pp_microbatches=8)
        sp = shard_pytree(params, T.logical_axes(cfg_m), mesh)
        loss = jax.jit(lambda p, b: T.lm_loss(p, b, cfg_m, mesh))(sp, batch)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=1e-5)


# ---------------------------------------------------------------------------
# expert parallelism (MoE)
# ---------------------------------------------------------------------------

class TestMoE:
    @pytest.fixture(scope="class")
    def weights(self):
        r = np.random.RandomState(2)
        d, e, h = 8, 4, 32
        return (jnp.asarray(r.randn(d, e), jnp.float32),
                jnp.asarray(r.randn(e, d, h) * 0.1, jnp.float32),
                jnp.asarray(r.randn(e, h, d) * 0.1, jnp.float32))

    def test_matches_dense_reference(self, weights, rng):
        rw, w1, w2 = weights
        b, s, d = 2, 16, rw.shape[0]
        x = jnp.asarray(rng.randn(b, s, d), jnp.float32)
        # capacity_factor huge → nothing dropped → must equal per-token math
        out, metrics = moe_ffn(x, rw, w1, w2, top_k=2, capacity_factor=100.0)
        vals, idx = jax.lax.top_k(jax.nn.softmax(x @ rw, -1), 2)
        vals = vals / vals.sum(-1, keepdims=True)
        ref = np.zeros((b, s, d), np.float32)
        for bi in range(b):
            for si in range(s):
                for ki in range(2):
                    e = int(idx[bi, si, ki])
                    hid = jax.nn.gelu(x[bi, si] @ w1[e])
                    ref[bi, si] += float(vals[bi, si, ki]) * np.asarray(
                        hid @ w2[e])
        np.testing.assert_allclose(out, ref, atol=1e-5)
        assert float(metrics.dropped_fraction) == 0.0

    def test_capacity_drops_overflow(self, weights, rng):
        rw, w1, w2 = weights
        x = jnp.asarray(rng.randn(1, 32, rw.shape[0]), jnp.float32)
        # capacity_factor well below 1 forces drops on imbalanced routing
        _, metrics = moe_ffn(x, rw, w1, w2, top_k=1, capacity_factor=0.25)
        assert float(metrics.dropped_fraction) > 0.0

    def test_differentiable(self, weights, rng):
        rw, w1, w2 = weights
        x = jnp.asarray(rng.randn(2, 8, rw.shape[0]), jnp.float32)
        g = jax.grad(lambda x: moe_ffn(x, rw, w1, w2)[0].sum())(x)
        assert bool(jnp.isfinite(g).all())


class TestUlyssesAttention:
    @pytest.fixture(scope="class")
    def qkv(self):
        r = np.random.RandomState(1)
        shape = (2, 32, 4, 16)
        return tuple(jnp.asarray(r.randn(*shape), jnp.float32)
                     for _ in range(3))

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, qkv, causal):
        from tony_tpu.parallel import ulysses_attention
        q, k, v = qkv
        mesh = make_mesh({"dp": 2, "cp": 4})
        out = ulysses_attention(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(out, _dense_attention(q, k, v, causal),
                                   atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_engine_matches_dense(self, qkv, causal, monkeypatch):
        """The TPU arm: post-all-to-all [B, S_full, H/cp, D] chunks run the
        flash kernels (forced on, interpret mode)."""
        import importlib
        R = importlib.import_module("tony_tpu.parallel.ring_attention")
        monkeypatch.setattr(R, "_USE_FLASH_CHUNKS", True)
        from tony_tpu.parallel import ulysses_attention
        q, k, v = qkv
        mesh = make_mesh({"dp": 2, "cp": 4})
        out = ulysses_attention(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(out, _dense_attention(q, k, v, causal),
                                   atol=2e-5)

    def test_flash_engine_rejects_untileable_seq(self, monkeypatch):
        """With the flash engine on, a full sequence that tiles no flash
        block must fail actionably, not silently go dense O(S²)."""
        import importlib
        R = importlib.import_module("tony_tpu.parallel.ring_attention")
        monkeypatch.setattr(R, "_USE_FLASH_CHUNKS", True)
        from tony_tpu.parallel import ulysses_attention
        r = np.random.RandomState(2)
        # S_full = 36: local 9 over cp=4, tiles no block (36 % 8 != 0)
        q, k, v = (jnp.asarray(r.randn(2, 36, 4, 16), jnp.float32)
                   for _ in range(3))
        mesh = make_mesh({"dp": 2, "cp": 4})
        with pytest.raises(ValueError, match="pad the per-device"):
            ulysses_attention(q, k, v, mesh, causal=True)

    def test_matches_ring(self, qkv):
        """Both context-parallel strategies compute the same attention."""
        from tony_tpu.parallel import ring_attention, ulysses_attention
        q, k, v = qkv
        mesh = make_mesh({"dp": 2, "cp": 4})
        np.testing.assert_allclose(
            ulysses_attention(q, k, v, mesh, causal=True),
            ring_attention(q, k, v, mesh, causal=True), atol=2e-5)

    @pytest.mark.slow
    def test_gradients_match_dense(self, qkv):
        from tony_tpu.parallel import ulysses_attention
        q, k, v = qkv
        mesh = make_mesh({"dp": 2, "cp": 4})
        g = jax.grad(lambda *a: ulysses_attention(*a, mesh).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: _dense_attention(*a, True).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(g, gr):
            np.testing.assert_allclose(got, want, atol=2e-5)

    def test_no_cp_axis_fallback(self, qkv):
        from tony_tpu.parallel import ulysses_attention
        q, k, v = qkv
        mesh = make_mesh({"dp": 2, "tp": 4})
        np.testing.assert_allclose(
            ulysses_attention(q, k, v, mesh, causal=True),
            _dense_attention(q, k, v, True), atol=2e-5)

    @pytest.fixture(scope="class")
    def gqa_qkv(self):
        r = np.random.RandomState(7)
        q = jnp.asarray(r.randn(2, 32, 8, 16), jnp.float32)
        k = jnp.asarray(r.randn(2, 32, 4, 16), jnp.float32)   # 2 groups
        v = jnp.asarray(r.randn(2, 32, 4, 16), jnp.float32)
        return q, k, v

    def _gqa_dense(self, q, k, v, causal=True):
        rep = q.shape[2] // k.shape[2]
        return _dense_attention(q, jnp.repeat(k, rep, axis=2),
                                jnp.repeat(v, rep, axis=2), causal)

    @pytest.mark.parametrize("causal", [True, False])
    def test_gqa_unexpanded_matches_dense(self, gqa_qkv, causal,
                                          monkeypatch):
        """kv heads divide cp: K/V ride the all-to-alls UNEXPANDED — the
        local body must receive H_kv-wide K/V (the payload assertion) and
        still compute the grouped attention exactly."""
        import tony_tpu.parallel.ulysses as U
        q, k, v = gqa_qkv
        seen = []
        orig = U.ulysses_attention_local

        def spy(q, k, v, **kw):
            seen.append(k.shape)
            return orig(q, k, v, **kw)

        monkeypatch.setattr(U, "ulysses_attention_local", spy)
        mesh = make_mesh({"dp": 2, "cp": 4})
        out = U.ulysses_attention(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(out, self._gqa_dense(q, k, v, causal),
                                   atol=2e-5)
        # local shard saw [B, S/cp, H_kv, D] — unexpanded (4 kv heads,
        # not 8): the inter-chip K/V payload is H/H_kv x smaller
        assert seen and seen[0][2] == 4, seen

    @pytest.mark.slow
    def test_gqa_unexpanded_grads_match_dense(self, gqa_qkv):
        from tony_tpu.parallel import ulysses_attention
        q, k, v = gqa_qkv
        mesh = make_mesh({"dp": 2, "cp": 4})
        g = jax.grad(lambda *a: ulysses_attention(*a, mesh).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: self._gqa_dense(*a).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(g, gr):
            np.testing.assert_allclose(got, want, atol=2e-5)

    def test_gqa_indivisible_kv_expands(self, monkeypatch):
        """kv heads that cannot split over cp (2 % 4 != 0) expand to full
        width — correctness over the payload saving."""
        import tony_tpu.parallel.ulysses as U
        r = np.random.RandomState(8)
        q = jnp.asarray(r.randn(2, 32, 8, 16), jnp.float32)
        k = jnp.asarray(r.randn(2, 32, 2, 16), jnp.float32)
        v = jnp.asarray(r.randn(2, 32, 2, 16), jnp.float32)
        seen = []
        orig = U.ulysses_attention_local

        def spy(q, k, v, **kw):
            seen.append(k.shape)
            return orig(q, k, v, **kw)

        monkeypatch.setattr(U, "ulysses_attention_local", spy)
        mesh = make_mesh({"dp": 2, "cp": 4})
        out = U.ulysses_attention(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(out, self._gqa_dense(q, k, v, True),
                                   atol=2e-5)
        assert seen and seen[0][2] == 8, seen    # expanded

    def test_gqa_unexpanded_matches_ring(self, gqa_qkv):
        """Both cp strategies agree on grouped-query attention with
        unexpanded K/V."""
        from tony_tpu.parallel import ring_attention, ulysses_attention
        q, k, v = gqa_qkv
        mesh = make_mesh({"dp": 2, "cp": 4})
        np.testing.assert_allclose(
            ulysses_attention(q, k, v, mesh, causal=True),
            ring_attention(q, k, v, mesh, causal=True), atol=2e-5)

    def test_indivisible_heads_rejected(self):
        from tony_tpu.parallel import ulysses_attention
        r = np.random.RandomState(2)
        q = k = v = jnp.asarray(r.randn(2, 24, 3, 8), jnp.float32)
        mesh = make_mesh({"cp": 8})      # 8 devices; 3 heads % 8 != 0
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, k, v, mesh, causal=True)


@pytest.mark.slow
def test_transformer_trains_with_ulysses_cp():
    """cp_strategy="ulysses" drives the model's attention through the
    all-to-all path end to end (loss finite, grads flow)."""
    from tony_tpu.models import transformer as T
    from tony_tpu.models.train import (default_optimizer, init_state,
                                       make_train_step)
    from tony_tpu.parallel import shard_pytree

    mesh = make_mesh({"dp": 2, "cp": 4})
    cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False,
                                   cp_strategy="ulysses")
    params = shard_pytree(T.init_params(jax.random.PRNGKey(0), cfg),
                          T.logical_axes(cfg), mesh)
    opt = default_optimizer(lr=1e-3)
    state = init_state(params, opt)
    step = make_train_step(lambda p, b: T.lm_loss(p, b, cfg, mesh), opt,
                           mesh)
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0,
                             cfg.vocab_size)
    batch = {"inputs": tok[:, :64], "targets": tok[:, 1:]}
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))


def test_ulysses_with_tp_head_sharding():
    """Heads shard over tp while sequence shards over cp — both strategies
    agree (the spec must not replicate heads across tp)."""
    from tony_tpu.parallel import ring_attention, ulysses_attention
    r = np.random.RandomState(3)
    q, k, v = (jnp.asarray(r.randn(2, 16, 4, 8), jnp.float32)
               for _ in range(3))
    mesh = make_mesh({"cp": 2, "tp": 2, "dp": 2})
    np.testing.assert_allclose(
        ulysses_attention(q, k, v, mesh, causal=True),
        ring_attention(q, k, v, mesh, causal=True), atol=2e-5)


def test_unknown_cp_strategy_rejected():
    from tony_tpu.models import transformer as T
    import pytest as _pytest
    cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32, cp_strategy="ulyses")
    tok = jnp.zeros((1, 16), jnp.int32)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    with _pytest.raises(ValueError, match="cp_strategy"):
        T.forward(params, tok, cfg)


class TestRematPolicy:
    """What the block keeps for its backward (models/remat.LADDER) is a
    memory/FLOP trade, never the math: every rung gives rung 0's loss
    and gradients to float32 round-off."""

    @staticmethod
    def _loss_and_grads(monkeypatch, rung, mesh=None, flash=False):
        from tony_tpu.models import remat
        from tony_tpu.models import transformer as T
        # a device with room for everything; the scope's ceiling picks
        monkeypatch.setattr(remat, "device_memory", lambda: (1 << 50, 0))
        if flash:
            # the flash arm (interpreted kernels): its names join in
            monkeypatch.setattr(T, "_attention", lambda q, k, v, *a: (
                T.flash_attention(q, k, v, causal=True, block_q=16,
                                  block_k=16)))
        cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                  cfg.vocab_size)
        batch = {"inputs": toks[:, :32], "targets": toks[:, 1:]}
        sc = remat.Scope(ceiling=rung)
        with remat.scope(sc):
            loss, grads = jax.jit(jax.value_and_grad(
                lambda p: T.lm_loss(p, batch, cfg, mesh)))(params)
        assert sc.rung == rung
        return float(loss), grads

    def _assert_rung_is_rung_0(self, monkeypatch, rung, **arm):
        l0, g0 = self._loss_and_grads(monkeypatch, 0, **arm)
        l, g = self._loss_and_grads(monkeypatch, rung, **arm)
        np.testing.assert_allclose(l, l0, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)

    @pytest.mark.parametrize("arm", ["dense", "flash"])
    @pytest.mark.parametrize("rung", [1, 2, 3, 4])
    def test_every_rung_is_rung_0(self, monkeypatch, rung, arm):
        self._assert_rung_is_rung_0(monkeypatch, rung, flash=arm == "flash")

    @pytest.mark.parametrize("rung", [2, 4])
    def test_every_rung_is_rung_0_on_a_mesh(self, monkeypatch, rung):
        mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
        self._assert_rung_is_rung_0(monkeypatch, rung, mesh=mesh)

    def test_a_rung_keeps_its_names_and_full_pins_rung_0(self, monkeypatch):
        """Rung 2 saves the output projection as a residual of the scanned
        block (the kernel's operands join on the flash arm);
        ``remat_policy="full"`` is rung 0 whatever the device has."""
        from tony_tpu.models import remat
        from tony_tpu.models import transformer as T
        monkeypatch.setattr(remat, "device_memory", lambda: (1 << 50, 0))
        cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32,
                                       remat_policy="full")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}
        sc = remat.Scope()
        with remat.scope(sc):
            jax.grad(lambda p: T.lm_loss(p, batch, cfg))(params)
        assert sc.rung == 0
        with remat.scope(remat.Scope(ceiling=2)) as sc:
            text = str(jax.make_jaxpr(jax.grad(lambda p: T.lm_loss(
                p, batch, cfg.scaled(remat_policy="fit"))))(params))
        assert sc.rung == 2
        # the backward's scan reads the kept projection as a stacked
        # input, 2 layers x [2, 32, 128], beside the block inputs
        assert text.count("f32[2,2,32,128]") > str(jax.make_jaxpr(jax.grad(
            lambda p: T.lm_loss(p, batch, cfg)))(params)).count(
                "f32[2,2,32,128]")
