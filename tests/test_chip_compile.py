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
        self.dp1 = Mesh(np.array(topo.devices[:1]), ("dp",))

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


def test_train_cell_step_fits_at_the_rung_the_ladder_takes(chip,
                                                           monkeypatch):
    """The benchmark's train step (``train-mistral7b-8k``: Mistral-7B
    widths, 4 layers, 2 x 8,192, bf16 AdamW, donated state, through
    ``make_train_step``) compiled for one v5e at the rung
    ``models/remat.py`` takes when the device says 15.75 GiB, and at
    rung 0. Held here so that a model or optimizer change that eats the
    headroom fails a test and not a chip run:

    - rung 0 (``remat_policy="full"``, the program of PRs 24–30) peaks
      at 12,130,829,824 bytes by ``memory_analysis()`` — NOT the
      9,118,390,784 of ``peak_bytes_in_use``, which does not see a
      program's temporaries;
    - the ladder takes rung 3 (3.77 GB saved: the flash output, q/k/v,
      the output projection, the MLP's gate) at 15,903,482,368, under
      the limit less the runtime's 258 MiB; rung 4 is refused by the
      compiler ("Used 16.59G of 15.75G hbm");
    - the ladder's own reckoning of rung 0 (state + gradients +
      ``working_bytes``) is the compiler's peak within the band its
      docstring gives."""
    from tony_tpu.models import remat
    from tony_tpu.models import transformer as T
    from tony_tpu.models.train import (default_optimizer, init_state,
                                       make_train_step)
    from tony_tpu.runtime import metrics as metrics_mod
    limit = int(15.75 * (1 << 30))
    # a described chip gives no numbers: the limit is a v5e's, and nothing
    # is resident before the step's own arguments (an AOT compile)
    monkeypatch.setattr(remat, "device_memory", lambda: (limit, 0))
    cfg = T.TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=4, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq=32768, attn_window=4096,
        dtype=jnp.bfloat16)
    opt = default_optimizer(lr=3e-4, weight_decay=0.01, warmup_steps=1,
                            total_steps=10000)
    mesh = chip.dp1
    on_mesh = NamedSharding(mesh, P())
    shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_mesh),
        jax.eval_shape(lambda p: init_state(p, opt), shapes))
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=on_mesh)
    batch = {"inputs": tokens, "targets": tokens}

    def compiled(c):
        step = make_train_step(lambda p, b: T.lm_loss(p, b, c, mesh), opt,
                               mesh)
        program = step.lower(state, batch).compile()
        rung = int(metrics_mod.get_default().gauge(
            "tony_train_saved_rung").value)
        return (rung, program.memory_analysis().peak_memory_in_bytes,
                program.as_text().count("tony_flash_fwd"))

    rung, peak, fwd_calls = compiled(cfg)
    rung0, peak0, fwd_calls0 = compiled(cfg.scaled(remat_policy="full"))
    assert (rung, rung0) == (3, 0)
    assert peak + (258 << 20) < limit, peak
    assert 15.6e9 < peak < 16.2e9 and 11.9e9 < peak0 < 12.4e9, (peak, peak0)
    assert fwd_calls < fwd_calls0           # the replayed forward is gone
    held, grads = remat.bytes_a_device(state), remat.bytes_a_device(shapes)
    reckoned = held + grads + remat.working_bytes(cfg, 2, 8192, mesh,
                                                  T.DEFAULT_RULES)
    assert -0.26e9 < reckoned - peak0 < 0.8e9, (reckoned, peak0)
    assert peak - peak0 == pytest.approx(
        remat.rung_bytes(cfg, 2, 8192, mesh, T.DEFAULT_RULES)[3], rel=0.01)


# (d_model, heads, kv heads, d_ff, vocab, slots, cache rows) of the
# benchmark's serving widths; depth is cut to 4 (a case under 10 s)
STEP_ROWS_CASES = {
    "phi3mini-6slots": (3072, 32, 32, 8192, 32064, 6, 1280),
    "phi3mini-8slots": (3072, 32, 32, 8192, 32064, 8, 1280),
    "mistral7b-6slots": (4096, 32, 8, 14336, 32000, 6, 8192),
}


def _dense_serving(chip, name):
    """(cfg, params, cache) of ``STEP_ROWS_CASES[name]`` at depth 4, as
    shapes on the described chip, the cache with per-row frontiers."""
    from tony_tpu.models import decode as D
    from tony_tpu.models import transformer as T
    d_model, heads, kv, d_ff, vocab, slots, rows = STEP_ROWS_CASES[name]
    cfg = T.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_layers=4, n_heads=heads,
        n_kv_heads=kv, d_ff=d_ff, max_seq=rows, dtype=jnp.bfloat16)
    params = chip.place(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    cache = jax.eval_shape(lambda: D.init_kv_cache(cfg, slots, rows))
    return cfg, params, chip.place(
        dict(cache, length=chip.shape((slots,), jnp.int32)))


def _cache_sized_copies(text, buf):
    """The ``copy`` instructions of a compiled program's text whose
    result has as many elements as the cache buffer ``buf``."""
    import re
    return [m.group(0) for m in re.finditer(
                r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", text)
            if np.prod([int(d) for d in m.group(1).split(",")]) == buf.size]


def _held_in_one_layout(text, dims):
    """Every mention of the buffer ``bf16[dims]`` names ONE tiled layout
    (``{3,2,1,0:T(8,128)(2,1)}``: entry arguments, loop carries, results),
    and a launch's ``operand_layout_constraints`` — a bare order of
    dimensions — name that layout's order."""
    import re
    layouts = set(re.findall(r"bf16\[" + dims + r"\](\{[^}]*\})", text))
    tiled = {lay for lay in layouts if ":" in lay}
    assert len(tiled) == 1, layouts
    assert layouts - tiled <= {next(iter(tiled)).split(":")[0] + "}"}, layouts


@pytest.mark.parametrize("steps", [2, 8])
@pytest.mark.parametrize("name", sorted(STEP_ROWS_CASES))
def test_step_rows_never_copies_the_cache(chip, name, steps):
    """The decode program (``serve.step_rows``, per-row frontiers) — at
    the TWO steps the cells run since PR 42 (``serve._DECODE_STEPS``: a
    copy at the program's edge would be paid every other step there) and
    at the 8 they ran before — holds the K/V cache in ONE layout from its
    arguments through the ``while`` to its results: no instruction
    copies a cache-sized buffer, and the program's temporaries are
    smaller than one cache buffer. With a trailing [.., KV, hd] (the parent of PR 26) the
    Phi-3-mini cases fail: head_dim 96 pads to 128 lanes in the loop's
    layout and not in the arguments', so the program begins and ends
    with 4 transposing copies of the whole cache (temporaries 0.51 GB
    at this depth and 6 slots, against 0.19 GB a buffer)."""
    import re

    from tony_tpu.models import serve as S
    d_model, _, _, _, vocab, slots, _ = STEP_ROWS_CASES[name]
    cfg, params, cache = _dense_serving(chip, name)
    compiled = S.step_rows.lower(
        params, cache, chip.shape((slots, vocab), cfg.logits_storage_dtype),
        chip.shape((slots, 2), jnp.uint32), chip.shape((slots,), jnp.int32),
        n=steps, cfg=cfg).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_step_rows")
    assert S._DECODE_STEPS == 2

    buf = cache["k"]
    dims = ",".join(str(d) for d in buf.shape)
    assert not _cache_sized_copies(text, buf)
    # the cached read is the kernel over each slot's own live blocks
    # (PR 36), on the stacked buffer as stored: one launch a layer
    assert len(set(re.findall(r"%(tony_cached_attn[.\d]*) = ", text))) == 4
    # entry arguments, the while's carry, the results: one layout. A
    # buffer's layout names its tiling (``{3,2,1,0:T(8,128)(2,1)}``); the
    # launch's ``operand_layout_constraints`` name a bare order of
    # dimensions, which has to be the buffers' own
    _held_in_one_layout(text, dims)
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


@pytest.mark.parametrize("name", ["phi3mini-8slots", "mistral7b-6slots"])
def test_decode_step_on_a_tp_mesh_reads_its_own_heads(chip, name):
    """Tensor-parallel serving (``docs/serving.md``): ``decode_step``
    under ``jax.set_mesh`` on the 2x2 ``("dp", "tp")`` mesh, weights cut
    by the rules, the cache by slots and K/V heads. The cached read is a
    Mosaic call, which the partitioner cannot split ("Mosaic kernels
    cannot be automatically partitioned"): it runs a device inside
    ``shard_cached_attention``'s island, on that device's slots and its
    heads' columns of the stored rows — so the program holds the launch,
    gathers no cache and copies none. Heads of 96 (16 a device: whole
    rows of 1,536 lanes) and of 128 (GQA, 4 K/V heads a device)."""
    import re

    from tony_tpu.models import decode as D
    from tony_tpu.models import transformer as T
    from tony_tpu.parallel.sharding import param_shardings
    d_model, heads, kv, d_ff, vocab, slots, rows = STEP_ROWS_CASES[name]
    slots -= slots % 2
    cfg = T.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_layers=2, n_heads=heads,
        n_kv_heads=kv, d_ff=d_ff, max_seq=rows, dtype=jnp.bfloat16)
    params = chip.place(
        jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg)),
        param_shardings(T.logical_axes(cfg), chip.mesh))
    width = kv * cfg.head_dim
    buf = chip.shape((2, slots, rows, width),
                     spec=P(None, "dp", None, "tp"))
    per_slot = chip.shape((slots,), jnp.int32, spec=P("dp"))
    cache = {"k": buf, "v": buf, "length": per_slot}
    with jax.set_mesh(chip.mesh):
        compiled = jax.jit(functools.partial(D.decode_step, cfg=cfg),
                           donate_argnums=2).lower(
            params, per_slot, cache, per_slot).compile()
    text = compiled.as_text()
    assert len(set(re.findall(r"%(tony_cached_attn[.\d]*) = ", text))) == 2
    # a device's share of the cache, as the launch's operand and as the
    # program's result: [L, slots / dp, rows, KV·hd / tp]
    local = f"bf16[2,{slots // 2},{rows},{width // 2}]"
    assert local in text
    assert f"bf16[2,{slots},{rows}" not in text         # never whole
    gathered = [ln for ln in text.splitlines()
                if "all-gather" in ln and f",{rows}," in ln]
    assert not gathered, gathered
    assert not _cache_sized_copies(
        text, jax.ShapeDtypeStruct((2, slots // 2, rows, width // 2),
                                   jnp.bfloat16))


def test_admit_rows_at_token_budget_widths(chip):
    """The admission programs of the Phi-3 cells once a dispatch is sized
    in tokens (``serve.admit_width``, 6 slots): the longest bucket at one
    row, ``(1, 1024)``, and the widest dispatch the cells' buckets give,
    ``(4, 64)``. Neither copies a cache-sized buffer on the way to
    ``place_rows``, and each holds fewer temporaries than ``(6, 1024)``,
    the shape every admission had while it was padded to the slots."""
    from tony_tpu.models import serve as S
    slots = STEP_ROWS_CASES["phi3mini-6slots"][5]
    assert (S.admit_width(1024, slots), S.admit_width(64, slots)) == (1, 4)
    cfg, params, cache = _dense_serving(chip, "phi3mini-6slots")
    logits = chip.shape((slots, cfg.vocab_size), cfg.logits_storage_dtype)

    def compiled(width, bucket):
        return S.admit_rows.lower(
            params, cache, logits, chip.shape((width,), jnp.int32),
            chip.shape((width, bucket), jnp.int32),
            chip.shape((width,), jnp.int32), cfg=cfg).compile()

    padded = compiled(slots, 1024).memory_analysis().temp_size_in_bytes
    for width, bucket in ((1, 1024), (4, 64)):
        program = compiled(width, bucket)
        text = program.as_text()
        assert text.startswith("HloModule jit_admit_rows")
        # a 64-position bucket is under the kernels' 128-lane tile and
        # takes flash_attention's dense arm, at every width
        assert ("tpu_custom_call" in text) == (bucket >= 128), (width,
                                                                bucket)
        assert not _cache_sized_copies(text, cache["k"])
        temporaries = program.memory_analysis().temp_size_in_bytes
        assert temporaries < padded, (width, bucket, temporaries, padded)


# ---------------------------------------------------------------------------
# Latent attention + sparse experts at Kimi-K2.5's widths (PR 28)
# ---------------------------------------------------------------------------

def _kimi_cfg(expert_layers: int):
    """Kimi-K2.5's widths with ONE dense layer and ``expert_layers`` of
    12 held experts (of 384), 20,480 vocabulary rows: the cell's
    configuration at a depth that compiles in seconds."""
    from tony_tpu.models import transformer as T
    return T.TransformerConfig(
        vocab_size=20480, d_model=7168, n_layers=1 + expert_layers,
        n_heads=64, d_ff=18432, dtype=jnp.bfloat16, remat=False,
        rms_eps=1e-5, rope_base=50000.0,
        rope_scaling=T.RopeYarn(64.0, 32.0, 1.0, 4096, 1.0, 1.0),
        layer_kinds=("dense",) + ("moe",) * expert_layers,
        latent=T.LatentAttention(1536, 512, 128, 64, 128),
        experts=T.SparseExperts(384, 8, 2048, 2.827, 0, 12))


@pytest.mark.parametrize("rows,tm,k,n", [
    (448, 16, 7168, 2048), (448, 16, 2048, 7168),       # a decode step
    (2048, 256, 7168, 2048), (2048, 256, 2048, 7168)])  # a prefill chunk
def test_grouped_matmul_compiles_for_v5e(chip, rows, tm, k, n):
    """The routed-expert product (``tony_moe_gmm``) at the published
    expert shapes, 5 layers x 12 experts stacked: scalar-prefetched tile
    groups, skipped tiles, blocks inside VMEM."""
    from tony_tpu.ops.grouped_matmul import grouped_matmul
    text = chip.compile(
        lambda lhs, rhs, tg, nt: grouped_matmul(
            lhs, rhs, tg, nt, tm=tm, out_dtype=jnp.float32),
        chip.shape((rows, k)), chip.shape((60, k, n)),
        chip.shape((rows // tm,), jnp.int32), chip.shape((), jnp.int32))
    assert "tony_moe_gmm" in text


def test_latent_prompt_attention_fits_vmem(chip):
    """The expanded prefill at 32 slots x the 512 bucket: 64 heads of
    192 (v padded from 128) through the flash kernel at the narrower
    blocks ``_latent_prompt_attention`` asks for — the defaults are 23 MB
    of the 16 MB of VMEM at this head width."""
    from tony_tpu.models import decode as D
    cfg = _kimi_cfg(1)
    la = cfg.latent
    text = chip.compile(
        lambda q_n, q_r, row, wkv_b: D._latent_prompt_attention(
            q_n, q_r, row, {"wkv_b": wkv_b}, cfg),
        chip.shape((32, 512, 64, la.nope_dim)),
        chip.shape((32, 512, 64, la.rope_dim)),
        chip.shape((32, 512, 1, la.stored_row)),
        chip.shape((la.kv_rank, 64, la.nope_dim + la.v_dim)))
    assert "tony_flash_fwd" in text


def test_latent_step_rows_never_copies_the_cache_or_an_expert_layer(chip):
    """The decode chunk at the cell's 32 slots x 2,048 rows: the latent
    buffer ([L, B, rows, 640]: 576 stored in whole lane tiles) keeps ONE
    layout from the arguments through the read loops to the results — at
    576 minor the compiler kept the rows minor inside the loop and copied
    the whole cache around every layer's write — and the routed experts
    reach their kernel stacked: no layer's 1 GB of expert weights is
    copied out for the call (the temporaries stay under ONE expert's
    three matrices)."""
    import re

    from tony_tpu.models import decode as D
    from tony_tpu.models import serve as S
    from tony_tpu.models import transformer as T
    cfg = _kimi_cfg(2)
    slots, rows = 32, 2048
    params = chip.place(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    cache = jax.eval_shape(lambda: D.init_kv_cache(cfg, slots, rows))
    cache = chip.place(dict(cache, length=chip.shape((slots,), jnp.int32)))
    compiled = S.step_rows.lower(
        params, cache,
        chip.shape((slots, cfg.vocab_size), cfg.logits_storage_dtype),
        chip.shape((slots, 2), jnp.uint32), chip.shape((slots,), jnp.int32),
        n=S._DECODE_STEPS, cfg=cfg).compile()
    text = compiled.as_text()
    buf = cache["ckv"]
    assert buf.shape == (3, slots, rows, 640)
    dims = ",".join(str(d) for d in buf.shape)
    assert not re.findall(r"bf16\[" + dims + r"\]\{[^}]*\} copy\(", text)
    _held_in_one_layout(text, dims)
    assert text.count("tony_moe_gmm") >= 6        # 2 layers x gate/up/down
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 7168 * 2048 * 2


def test_latent_prompt_attention_takes_an_unaligned_short_prompt(chip):
    """A prompt of at most 256 that is no multiple of 128 (``prefill``
    leaves it unpadded) takes the dense arm instead of failing the
    narrower q block's divisibility (found on the chip, PR 28)."""
    from tony_tpu.models import decode as D
    cfg = _kimi_cfg(1)
    la = cfg.latent
    jax.jit(lambda q_n, q_r, row, wkv_b: D._latent_prompt_attention(
        q_n, q_r, row, {"wkv_b": wkv_b}, cfg)).lower(
        chip.shape((2, 255, 64, la.nope_dim)),
        chip.shape((2, 255, 64, la.rope_dim)),
        chip.shape((2, 255, 1, la.stored_row)),
        chip.shape((la.kv_rank, 64, la.nope_dim + la.v_dim))).compile()


# ---------------------------------------------------------------------------
# Window + full attention kinds, each owning its cache, at Command A+'s
# widths and the cell's own depth, slots and rows (PR 35)
# ---------------------------------------------------------------------------

def _cell_serving(chip, config: str, slots: int, rows: int):
    """(cfg, params, cache, logits) of a benchmark configuration as its
    serving cell runs it, as shapes on the described chip."""
    from benchmark.lib import modelcfg
    from tony_tpu.models import decode as D
    c = modelcfg.load(config)
    fam = modelcfg.family(c)
    cfg = fam.program_config(c, dtype=jnp.bfloat16, remat=False)
    params = chip.place(jax.eval_shape(
        lambda: fam.make_params(7, c, jnp.bfloat16)))
    cache = jax.eval_shape(lambda: D.init_kv_cache(cfg, slots, rows))
    cache = chip.place(dict(cache, length=chip.shape((slots,), jnp.int32)))
    return cfg, params, cache, chip.shape((slots, cfg.vocab_size),
                                          cfg.logits_storage_dtype)


def _mixed_serving(chip):
    """``command-a-plus-l4-ep8`` as the cell
    ``serve-commandaplus-mixedlen`` runs it — 3 window + 1 full layer,
    16 held experts, 32 slots x 16,384 rows: 13.2 GB of arguments."""
    return _cell_serving(chip, "command-a-plus-l4-ep8", 32, 16384)


#: what one v5e chip's compiler allows a program (15.75 GiB)
_HBM = int(15.75 * 2**30)


def _copies_of_any(text, cache):
    return [c for n in ("k", "v", "k_ring", "v_ring")
            for c in _cache_sized_copies(text, cache[n])]


def test_mixed_step_rows_holds_ring_and_linear_cache_in_place(chip):
    """The decode chunk of the mixed model at the cell's size: the window
    kinds' rings ([3, 32, 4096, 1024]) and the full kind's linear buffer
    ([1, 32, 16384, 1024]) are each written and read in place — no
    cache-sized copy — the routed experts reach their kernel stacked,
    and the whole program fits the chip beside its 13.2 GB of
    arguments."""
    from tony_tpu.models import serve as S
    cfg, params, cache, logits = _mixed_serving(chip)
    assert cache["k_ring"].shape == (3, 32, 4096, 1024)
    assert cache["k"].shape == (1, 32, 16384, 1024)
    compiled = S.step_rows.lower(
        params, cache, logits, chip.shape((32, 2), jnp.uint32),
        chip.shape((32,), jnp.int32), n=S._DECODE_STEPS, cfg=cfg).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_step_rows")
    assert not _copies_of_any(text, cache)
    assert text.count("tony_moe_gmm") >= 12       # 4 layers x gate/up/down
    # each layer's cached read is the kernel over its slots' own live
    # blocks (PR 36): three over the rings, one over the linear buffer
    import re
    assert len(set(re.findall(r"%(tony_cached_attn[.\d]*) = ", text))) == 4
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1 << 30
    assert memory.peak_memory_in_bytes < _HBM


def test_mixed_admit_rows_at_the_longest_bucket_fits_the_chip(chip):
    """The 16,384 bucket's admission (one row: ``admit_width``): the
    flash kernel windowed on the window kinds and plain causal on the
    full kind at 128 query / 8 K/V heads of 128, the rows landed in the
    rings by position modulo 4,096 and in the linear buffer as they are
    — no cache-sized copy on the way — and the program's peak under what
    the chip allows beside weights and cache."""
    from tony_tpu.models import serve as S
    cfg, params, cache, logits = _mixed_serving(chip)
    assert S.admit_width(16384, 32) == 1
    compiled = S.admit_rows.lower(
        params, cache, logits, chip.shape((1,), jnp.int32),
        chip.shape((1, 16384), jnp.int32), chip.shape((1,), jnp.int32),
        cfg=cfg).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_admit_rows")
    assert "tony_flash_fwd" in text and "tony_moe_gmm" in text
    assert not _copies_of_any(text, cache)
    assert compiled.memory_analysis().peak_memory_in_bytes < _HBM


# ---------------------------------------------------------------------------
# The double layer (two latent attentions, two dense SwiGLUs, a shortcut-
# connected routed block with zero experts) at LongCat-Flash-Chat's widths
# and the cell's own depth, slots and rows (PR 37)
# ---------------------------------------------------------------------------

def _double_layer_serving(chip):
    """``longcat-flash-l4-ep32`` as the cell
    ``serve-longcatflash-wide-decode`` runs it — 4 double layers, 16 held
    experts, 64 slots x 4,096 rows in 8 latent row-sets: 13.07 GB of
    arguments."""
    return _cell_serving(chip, "longcat-flash-l4-ep32", 64, 4096)


def test_double_layer_step_rows_copies_no_cache_and_no_half(chip):
    """The decode chunk at the cell's size: the latent buffer ([8, 64,
    4096, 640]: two row-sets a layer) is written and read in place in all
    eight attentions, the routed experts reach their kernel stacked, and
    a half's matrices are cut [layer, half] where they are used — a
    layer's slice holding both halves has two readers and was copied out
    whole (2.1 GB of temporaries; 0.16 GB as kept) — so the program fits
    the chip beside its 13.07 GB of arguments."""
    import re

    from tony_tpu.models import serve as S
    cfg, params, cache, logits = _double_layer_serving(chip)
    buf = cache["ckv"]
    assert buf.shape == (8, 64, 4096, 640)
    compiled = S.step_rows.lower(
        params, cache, logits, chip.shape((64, 2), jnp.uint32),
        chip.shape((64,), jnp.int32), n=S._DECODE_STEPS, cfg=cfg).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_step_rows")
    dims = ",".join(str(d) for d in buf.shape)
    assert not re.findall(r"bf16\[" + dims + r"\]\{[^}]*\} copy\(", text)
    _held_in_one_layout(text, dims)
    assert text.count("tony_moe_gmm") >= 12       # 4 layers x gate/up/down
    assert "moe_zero" in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1 << 29
    assert memory.peak_memory_in_bytes < _HBM


def test_double_layer_admit_rows_at_the_longest_bucket_fits_the_chip(chip):
    """The 2,048 bucket's admission (one row: ``admit_width``): expanded
    latent attention through the flash kernel in all eight attentions,
    the rows landed in their own row-sets, the routed block dropless over
    2,048 tokens, under what the chip allows beside weights and cache."""
    from tony_tpu.models import serve as S
    cfg, params, cache, logits = _double_layer_serving(chip)
    assert S.admit_width(2048, 64) == 1
    compiled = S.admit_rows.lower(
        params, cache, logits, chip.shape((1,), jnp.int32),
        chip.shape((1, 2048), jnp.int32), chip.shape((1,), jnp.int32),
        cfg=cfg).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_admit_rows")
    assert "tony_flash_fwd" in text and "tony_moe_gmm" in text
    assert not _cache_sized_copies(text, cache["ckv"])
    assert compiled.memory_analysis().peak_memory_in_bytes < _HBM


# ---------------------------------------------------------------------------
# The latent cached read as ``tony_cached_attn`` (PR 38): the stored row is
# the one K/V head and its own value, each slot's own live blocks
# ---------------------------------------------------------------------------

def _launches(text):
    """name -> operand names of every ``tony_cached_attn`` launch of a
    compiled program: (grid bound, layer, q_pos, slot, blk, lo, hi, qx,
    the cache — and V where there is one)."""
    import re
    return {m.group(1): re.findall(r"%([\w.\-]+)", m.group(2))
            for m in re.finditer(
                r"%(tony_cached_attn[.\d]*) = [^\n]*? custom-call\(([^)]*)\)",
                text)}


def _source(text, name):
    """The instruction a launch's operand comes from, moves between
    memory spaces (``copy-start`` / ``copy-done``) followed back."""
    import re
    while True:
        m = re.search(r"%" + re.escape(name)
                      + r" = [^\n]* copy-(?:start|done)\(%([\w.\-]+)", text)
        if m is None:
            return name
        name = m.group(1)


@pytest.mark.parametrize("config,slots,rows,reads", [
    ("kimi-k2.5-l6-ep32", 32, 2048, 6),
    ("longcat-flash-l4-ep32", 64, 4096, 8)])
def test_latent_step_rows_reads_each_slots_own_blocks(chip, config, slots,
                                                      rows, reads):
    """The decode chunk of both latent cells at their own depth, slots
    and rows: ONE ``tony_cached_attn`` launch a latent read (6 a step in
    Kimi's cell, 8 — two a double layer — in LongCat's) on the stacked
    buffer as stored, with no V operand; the work list is built from the
    positions alone, so all the launches of a step share ONE; the walk's
    loop with its float32 ``[B, H, 640]`` carry is gone; no cache-sized
    copy; and the kernel's blocks fit VMEM (the compile would refuse)."""
    from tony_tpu.models import serve as S
    cfg, params, cache, logits = _cell_serving(chip, config, slots, rows)
    buf = cache["ckv"]
    assert buf.shape == (reads, slots, rows, 640)
    compiled = S.step_rows.lower(
        params, cache, logits, chip.shape((slots, 2), jnp.uint32),
        chip.shape((slots,), jnp.int32), n=S._DECODE_STEPS, cfg=cfg).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_step_rows")
    launches = _launches(text)
    assert len(launches) == reads
    dims = ",".join(str(d) for d in buf.shape)
    for operands in launches.values():
        assert len(operands) == 9              # qx and ONE cache operand
    # slot, blk, lo, hi: one producer each for all the launches
    for i in (3, 4, 5, 6):
        assert len({_source(text, ops[i]) for ops in launches.values()}) == 1
    assert f"f32[{slots},{cfg.n_heads},640]" not in text    # the carry
    assert not _cache_sized_copies(text, buf)
    _held_in_one_layout(text, dims)
    assert compiled.memory_analysis().peak_memory_in_bytes < _HBM


@pytest.mark.parametrize("axes", ["dp2-tp2", "dp4"])
def test_latent_decode_step_on_a_mesh(chip, topo, axes):
    """``decode_step`` of a latent model (Kimi-K2.5's attention, two
    dense layers) under ``jax.set_mesh``. The latent cache has ONE head:
    nothing of it splits over ``tp``. So with a live "heads" axis
    ``_read_arm`` leaves the read to the ``jnp`` walk, which the
    partitioner cuts as it did — no launch reaches it bare ("Mosaic
    kernels cannot be automatically partitioned") — and with batch axes
    alone the launch runs a device inside ``shard_cached_attention``'s
    island, on its own slots. Either way no cache row is gathered and
    the cache stays a device's share."""
    import re

    from tony_tpu.models import decode as D
    from tony_tpu.models import transformer as T
    from tony_tpu.parallel.sharding import param_shardings
    mesh = chip.mesh if axes == "dp2-tp2" else Mesh(
        np.array(topo.devices), ("dp",))
    dp = mesh.shape["dp"]
    cfg = T.TransformerConfig(
        vocab_size=20480, d_model=7168, n_layers=2, n_heads=64, d_ff=18432,
        dtype=jnp.bfloat16, remat=False, rms_eps=1e-5, rope_base=50000.0,
        rope_scaling=T.RopeYarn(64.0, 32.0, 1.0, 4096, 1.0, 1.0),
        layer_kinds=("dense", "dense"),
        latent=T.LatentAttention(1536, 512, 128, 64, 128))
    slots, rows = 8, 2048
    params = chip.place(
        jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg)),
        param_shardings(T.logical_axes(cfg), mesh))
    on = lambda spec: NamedSharding(mesh, spec)         # noqa: E731
    per_slot = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=on(P("dp")))
    cache = {"length": per_slot, "ckv": jax.ShapeDtypeStruct(
        (2, slots, rows, 640), jnp.bfloat16,
        sharding=on(P(None, "dp", None, None)))}
    with jax.set_mesh(mesh):
        arm = D._read_arm(rows, 1, False)
        text = jax.jit(functools.partial(D.decode_step, cfg=cfg),
                       donate_argnums=2).lower(
            params, per_slot, cache, per_slot).compile().as_text()
    assert arm == ("walk" if axes == "dp2-tp2" else "kernel")
    launches = set(re.findall(r"%(tony_cached_attn[.\d]*) = ", text))
    assert len(launches) == (0 if arm == "walk" else 2)
    assert f"bf16[2,{slots // dp},{rows},640]" in text
    assert f"bf16[2,{slots},{rows}" not in text         # never whole
    gathered = [ln for ln in text.splitlines()
                if "all-gather" in ln and f",{rows}," in ln]
    assert not gathered, gathered


# ---------------------------------------------------------------------------
# A state-space layer kind beside full attention at granite-4.0-h-micro's
# widths, all 40 layers, the cell's own slots and rows (PR 41)
# ---------------------------------------------------------------------------

def _hybrid_serving(chip):
    """``granite-4.0-h-micro-l40`` as the cell
    ``serve-granite4hmicro-wide-decode`` runs it — 36 state-space mixers
    and 4 full-attention layers, 64 slots: 2.42 GB of recurrent state,
    0.16 GB of conv window and 2.15 GB of K/V rows beside 6.38 GB of
    weights."""
    return _cell_serving(chip, "granite-4.0-h-micro-l40", 64, 4096)


def _state_sized_copies(text, cache):
    return [c for n in ("ssm", "conv", "k", "v")
            for c in _cache_sized_copies(text, cache[n])]


def test_hybrid_step_rows_updates_the_state_in_place(chip):
    """The decode chunk at the cell's size: every mixer's state update is
    ONE ``tony_ssm_step`` launch against the stacked state ([36, 64, 128,
    4096]: N on the sublanes, heads x head width on the lanes) aliased in
    and out — no state-sized and no cache-sized copy in the program —,
    the four full layers read through ``tony_cached_attn`` at heads of 64
    (rows 512 wide), and the whole fits the chip beside its 11.2 GB of
    arguments."""
    import re

    from tony_tpu.models import serve as S
    cfg, params, cache, logits = _hybrid_serving(chip)
    assert cache["ssm"].shape == (36, 64, 128, 4096)
    assert cache["conv"].shape == (36, 64, 3, 4352)
    assert cache["k"].shape == (4, 64, 4096, 512)
    compiled = S.step_rows.lower(
        params, cache, logits, chip.shape((64, 2), jnp.uint32),
        chip.shape((64,), jnp.int32), n=S._DECODE_STEPS, cfg=cfg).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_step_rows")
    assert not _state_sized_copies(text, cache)
    assert len(set(re.findall(r"%(tony_ssm_step[.\d]*) = ", text))) == 36
    assert len(set(re.findall(r"%(tony_cached_attn[.\d]*) = ", text))) == 4
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1 << 30
    assert memory.peak_memory_in_bytes < _HBM
    print("hybrid step_rows peak", memory.peak_memory_in_bytes,
          "temp", memory.temp_size_in_bytes)


def test_hybrid_admit_rows_at_the_longest_bucket_fits_the_chip(chip):
    """The 2,048 bucket's admission (one row: ``admit_width``): the
    chunked scan in ``jnp`` (eight chunks of 256 carry a [128, 4096]
    state through each of 36 mixers), the flash kernel plain causal at
    32 / 8 heads of 64, the state landed whole and the rows as they are
    — nothing state- or cache-sized copied on the way — and the
    program's peak under what the chip allows beside weights and
    cache."""
    from tony_tpu.models import serve as S
    cfg, params, cache, logits = _hybrid_serving(chip)
    assert S.admit_width(2048, 64) == 1
    compiled = S.admit_rows.lower(
        params, cache, logits, chip.shape((1,), jnp.int32),
        chip.shape((1, 2048), jnp.int32), chip.shape((1,), jnp.int32),
        cfg=cfg).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_admit_rows")
    assert "tony_flash_fwd" in text
    assert not _state_sized_copies(text, cache)
    memory = compiled.memory_analysis()
    assert memory.peak_memory_in_bytes < _HBM
    print("hybrid admit_rows 2048 peak", memory.peak_memory_in_bytes,
          "temp", memory.temp_size_in_bytes)
