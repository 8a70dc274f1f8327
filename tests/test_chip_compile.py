"""Compile the main path's kernels for a DESCRIBED v5e, without the chip.

The TPU compiler is installed here and compiles for a topology that is
described and not attached (``topologies.get_topology_desc``). These
compiles see what interpret mode cannot: tiling rules, VMEM limits, and
"Mosaic kernels cannot be automatically partitioned" on a mesh. Nothing
runs, so they say nothing about results or times.

This is the ONLY file that describes a chip. The topology, and everything
built from it, lives in fixtures (never at import, in a ``skipif`` or in
``parametrize`` arguments): the process that describes a topology holds
libtpu's lock until it exits, so under xdist only the worker that is GIVEN
this file may load it, and every worker must collect the same tests.
``mosaic.interpret`` is steered here, in the test — without that the
compile would be of the interpreter's program, with the interpreter's
block shapes.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


class _Chip:
    """What a case needs from the described topology: shapes placed on
    one chip or on the 2x2 ``("dp", "tp")`` mesh, and the compile."""

    def __init__(self, topo):
        self.one = SingleDeviceSharding(topo.devices[0])
        self.mesh = Mesh(np.array(topo.devices).reshape(2, 2),
                         ("dp", "tp"))

    def shape(self, dims, dtype=jnp.bfloat16, spec=None):
        sharding = (self.one if spec is None
                    else NamedSharding(self.mesh, spec))
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    def place(self, tree, shardings=None):
        """``jax.eval_shape`` output → the same shapes with shardings."""
        if shardings is None:
            return jax.tree.map(lambda x: self.shape(x.shape, x.dtype), tree)
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    @staticmethod
    def compile(fn, *shapes):
        text = jax.jit(fn).lower(*shapes).compile().as_text()
        assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
        return text


@pytest.fixture
def chip(topo, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    from tony_tpu.ops import mosaic
    monkeypatch.setattr(mosaic, "interpret", lambda: False)
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip — keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield _Chip(topo)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (batch, seq, heads, kv heads, head_dim) of the presets' train shapes
FLASH_SHAPES = {
    "small": (32, 1024, 8, 8, 64),
    "small-gqa2": (32, 1024, 8, 2, 64),
    "base": (8, 2048, 12, 12, 64),
    "large": (4, 1024, 16, 16, 96),
    "small-s8192": (4, 8192, 8, 8, 64),
}


def _qkv(chip, name):
    b, s, h, hk, d = FLASH_SHAPES[name]
    return (chip.shape((b, s, h, d)), chip.shape((b, s, hk, d)),
            chip.shape((b, s, hk, d)))


def _grad_of(attn):
    return jax.grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("name", sorted(FLASH_SHAPES))
def test_flash_attention_compiles_for_v5e(chip, name, direction):
    from tony_tpu.ops.attention import flash_attention
    attn = functools.partial(flash_attention, causal=True)
    chip.compile(attn if direction == "fwd" else _grad_of(attn),
                 *_qkv(chip, name))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_windowed_flash_compiles_for_v5e(chip, direction):
    from tony_tpu.ops.attention import flash_attention
    attn = functools.partial(flash_attention, causal=True, window=1024)
    chip.compile(attn if direction == "fwd" else _grad_of(attn),
                 *_qkv(chip, "small-s8192"))


def test_prefill_compiles_at_padded_serve_length(chip):
    """A 300-token prompt runs the forward at ``_flash_safe_len`` (512):
    the padded program, at ``large`` width, holds the flash kernel."""
    from tony_tpu.models import decode as D
    from tony_tpu.models import transformer as T
    cfg = T.PRESETS["large"].scaled(n_layers=2)
    assert D._flash_safe_len(300) == 512 and D._pad_prompts()
    params = chip.place(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    chip.compile(lambda p, t: D.prefill(p, t, cfg, max_len=576),
                 params, chip.shape((8, 300), jnp.int32))


@pytest.mark.parametrize("op", ["rms_norm", "layer_norm"])
def test_fused_norms_compile_for_v5e(chip, op):
    from tony_tpu.ops import norms
    x = chip.shape((4, 1024, 1536))
    w = chip.shape((1536,))
    if op == "rms_norm":
        chip.compile(norms.rms_norm, x, w)
    else:
        chip.compile(norms.layer_norm, x, w, w)


def test_fused_adamw_leaf_update_compiles_for_v5e(chip):
    """One ``large`` MLP leaf ([1536, 6144] bf16, f32 moments) through
    the fused optimizer's whole update."""
    from tony_tpu.ops.optim import FusedAdamW
    opt = FusedAdamW(1e-3)
    params = {"w": chip.shape((1536, 6144))}
    state = chip.place(jax.eval_shape(opt.init, params))
    chip.compile(opt.fused_apply, params, state, params)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_sharded_flash_compiles_on_2x2_mesh(chip, direction):
    """The repair: ``_attention`` with a mesh runs flash inside
    ``shard_map`` on ``("dp", "tp")``. Bare under pjit (the parent
    commit) the same call raises "Mosaic kernels cannot be automatically
    partitioned"."""
    from tony_tpu.models import transformer as T
    attn = lambda q, k, v: T._attention(q, k, v, chip.mesh)
    b, s, h, _, d = FLASH_SHAPES["large"]
    x = chip.shape((2 * b, s, h, d), spec=P("dp", None, "tp", None))
    text = chip.compile(attn if direction == "fwd" else _grad_of(attn),
                        x, x, x)
    # each device runs the kernel on its own shard: nothing gathers q/k/v
    assert "all-gather" not in text


def test_large_lm_grad_compiles_on_2x2_mesh(chip):
    """``jax.grad`` of ``lm_loss`` at ``large`` width (depth cut to 2) with
    params and batch sharded over the 2x2 mesh — the path a multi-chip
    training job without a ``cp`` axis takes. Fails on the parent commit
    with the Mosaic partitioning error."""
    from tony_tpu.models import transformer as T
    from tony_tpu.parallel.sharding import (logical_sharding,
                                            param_shardings)
    cfg = T.PRESETS["large"].scaled(n_layers=2)
    params = chip.place(
        jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg)),
        param_shardings(T.logical_axes(cfg), chip.mesh))
    tokens = jax.ShapeDtypeStruct(
        (8, 1024), jnp.int32,
        sharding=logical_sharding(("batch", "seq"), chip.mesh))
    batch = {"inputs": tokens, "targets": tokens}
    chip.compile(jax.value_and_grad(
        lambda p, b: T.lm_loss(p, b, cfg, chip.mesh)), params, batch)


# (d_model, heads, kv heads, d_ff, vocab, slots, cache rows) of the
# benchmark's serving widths; depth is cut to 4 (a case under 10 s)
STEP_ROWS_CASES = {
    "phi3mini-6slots": (3072, 32, 32, 8192, 32064, 6, 1280),
    "phi3mini-8slots": (3072, 32, 32, 8192, 32064, 8, 1280),
    "mistral7b-6slots": (4096, 32, 8, 14336, 32000, 6, 8192),
}


@pytest.mark.parametrize("name", sorted(STEP_ROWS_CASES))
def test_step_rows_never_copies_the_cache(chip, name):
    """The decode chunk (``serve.step_rows``, ``n=8``, per-row frontiers)
    holds the K/V cache in ONE layout from its arguments through the
    ``while`` to its results: no instruction copies a cache-sized
    buffer, and the program's temporaries are smaller than one cache
    buffer. With a trailing [.., KV, hd] (the parent of PR 26) the
    Phi-3-mini cases fail: head_dim 96 pads to 128 lanes in the loop's
    layout and not in the arguments', so the program begins and ends
    with 4 transposing copies of the whole cache (temporaries 0.51 GB
    at this depth and 6 slots, against 0.19 GB a buffer)."""
    import re

    from tony_tpu.models import decode as D
    from tony_tpu.models import serve as S
    from tony_tpu.models import transformer as T
    d_model, heads, kv, d_ff, vocab, slots, rows = STEP_ROWS_CASES[name]
    cfg = T.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_layers=4, n_heads=heads,
        n_kv_heads=kv, d_ff=d_ff, max_seq=rows, dtype=jnp.bfloat16)
    params = chip.place(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    cache = jax.eval_shape(lambda: D.init_kv_cache(cfg, slots, rows))
    cache = chip.place(dict(cache, length=chip.shape((slots,), jnp.int32)))
    compiled = S.step_rows.lower(
        params, cache, chip.shape((slots, vocab), cfg.logits_storage_dtype),
        chip.shape((slots, 2), jnp.uint32), chip.shape((slots,), jnp.int32),
        n=8, cfg=cfg).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_step_rows")

    buf = cache["k"]
    dims = ",".join(str(d) for d in buf.shape)
    cache_sized = [
        m.group(0) for m in re.finditer(
            r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", text)
        if np.prod([int(d) for d in m.group(1).split(",")]) == buf.size]
    assert not cache_sized, cache_sized
    # entry arguments, the while's carry, the results: one layout
    layouts = set(re.findall(r"bf16\[" + dims + r"\](\{[^}]*\})", text))
    assert len(layouts) == 1, layouts
    carried = [ln for ln in text.splitlines()
               if " while(" in ln and f"bf16[{dims}]" in ln]
    assert carried, "no while carries the cache"
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < buf.size * buf.dtype.itemsize, temporaries
    # nor is a layer's slice of a stacked projection weight made a buffer
    # of its own (``_decode_block``'s barrier on v: without it the flatten
    # folds into the v projection and every step copies all of wv)
    sliced = re.findall(r"copy-start[.\d]* = \(bf16\[1," + str(d_model)
                        + r",\d+,\d+\]", text)
    assert not sliced, sliced
