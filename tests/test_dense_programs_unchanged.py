"""The dense programs do not change when a model with more than one kind
of layer arrives beside them: the lowered text of ``step_rows``,
``admit_rows`` and the gradient of ``lm_loss`` for the two dense
configurations' shapes of layer (MHA of 96 full-causal; GQA 4/2 with a
window) at toy width is, hash for hash, the text of the commit before
latent attention and sparse experts (06229a9, PR 27): no new operand, no
new output (``serve._device_stats`` is an EMPTY pytree there), no
reordered op. A later PR that means to change a dense program replaces
the hash it changed, and says so; run this file as a script with a
checkout's root to print that checkout's hashes.

PR 31 replaced ``mistral.grad`` (b30bf7cc7e203f12 → 51ab5765b7edf26b):
a block under ``remat=True`` now marks what ``models/remat.LADDER`` may
keep for the backward with ``checkpoint_name``. On the CPU the rung is 0
and the program is the parent's op for op — a name lowers to nothing —
but each name is an equation of the trace, so the counters in the private
functions' names (``@_where_116`` → ``@_where_118``) moved; with those
suffixes stripped the two texts are equal. ``phi.grad`` (``remat=False``:
no names) and the four serving programs are the parent's, hash for hash.

PR 37 added the serving programs of the two models with ``layer_kinds``
the benchmark holds — latent attention with a dense and two expert layers
beside a shared expert (``kimi``), window and full kinds in a parallel
block with averaged shared experts (``commanda``), both at toy width —
at the hashes of ITS parent (350c6c9, PR 36): the double layer, softmax
routing, zero experts and the scales on the latent bottlenecks arrived
beside them and changed no op of theirs (``n_zero == 0`` keeps the
device counters at two entries and traces no zero term; a scale of 1.0
traces no multiply).

PR 41 added the double layer itself (``longcat``: the benchmark's toy of
it, ``tests/data/tiny-scmoe.json`` through its family's
``program_config``) at the hashes of ITS parent (cf6d3ce, PR 40), and
changed none of the ten above: the state-space kind, ``embed_scale``,
``residual_scale`` and ``attn_scale`` arrived beside them, and each traces
nothing at its default (1.0, 1.0, None) — in ``_embed``, ``_branch``,
``_gqa_qkv`` and ``_latent_scale``, which every kinded program now passes
through.
"""

import hashlib
import os
import sys

import pytest

#: sha256[:16] of the lowered text at 06229a9 (PR 27), CPU backend;
#: ``mistral.grad`` as of PR 31 (docstring)
PARENT = {
    "mistral.admit_rows": "e950ef452b1d16c7",
    "mistral.grad": "51ab5765b7edf26b",
    "mistral.step_rows": "e71b707f3a28fcb1",
    "phi.admit_rows": "32ad1762b4f0f5e8",
    "phi.grad": "05a842aa6f92e610",
    "phi.step_rows": "51240cd8f81fe970",
    # at 350c6c9 (PR 36), the parent of PR 37
    "kimi.admit_rows": "334f32fe7cfa91ad",
    "kimi.step_rows": "e1e355cce2a334e4",
    "commanda.admit_rows": "8548109614c9a831",
    "commanda.step_rows": "54bbd7fab9606df0",
    # at cf6d3ce (PR 40), the parent of PR 41
    "longcat.admit_rows": "df2589367ee4f5c2",
    "longcat.step_rows": "22a2dd9c22f171ad",
}


def lowered(which: str) -> str:
    import jax
    import jax.numpy as jnp
    from tony_tpu.models import decode as D
    from tony_tpu.models import serve as S
    from tony_tpu.models import transformer as T
    model, program = which.split(".")
    cfg = {
        "phi": T.TransformerConfig(
            vocab_size=512, d_model=192, n_layers=2, n_heads=2, d_ff=256,
            max_seq=1024, dtype=jnp.bfloat16, remat=False),
        "mistral": T.TransformerConfig(
            vocab_size=512, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=256, max_seq=1024, attn_window=64,
            dtype=jnp.bfloat16),
        "kimi": lambda: T.TransformerConfig(
            vocab_size=512, d_model=128, n_layers=3, n_heads=4, d_ff=256,
            max_seq=1024, dtype=jnp.bfloat16, remat=False, rms_eps=1e-5,
            rope_base=50000.0,
            rope_scaling=T.RopeYarn(64.0, 32.0, 1.0, 4096, 1.0, 1.0),
            layer_kinds=("dense", "moe", "moe"),
            latent=T.LatentAttention(48, 32, 32, 16, 32),
            experts=T.SparseExperts(16, 4, 64, 2.827, 4, 8)),
        "commanda": lambda: T.TransformerConfig(
            vocab_size=512, d_model=128, n_layers=2, n_heads=8,
            n_kv_heads=2, head_dim=16, max_seq=1024, dtype=jnp.bfloat16,
            remat=False, rms_eps=1e-5, rope_base=50000.0, attn_window=64,
            layer_kinds=("window_moe", "full_moe"),
            experts=T.SparseExperts(16, 4, 64, first=4, held=8,
                                    n_shared=2, shared_mean=True),
            norm="layer", parallel_block=True, tie_embeddings=True,
            logit_scale=0.5),
        "longcat": lambda: _toy_of_the_benchmark("tiny-scmoe.json"),
    }[model]
    if callable(cfg):
        cfg = cfg()
    slots = 4
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    if program == "grad":
        return jax.jit(jax.value_and_grad(
            lambda p, b: T.lm_loss(p, b, cfg))).lower(
                params, {"tokens": sds((2, 129), jnp.int32)}).as_text()
    cache = jax.eval_shape(lambda: dict(
        D.init_kv_cache(cfg, slots, 640),
        length=jnp.zeros((slots,), jnp.int32)))
    logits = sds((slots, cfg.vocab_size), cfg.logits_storage_dtype)
    rows = sds((slots,), jnp.int32)
    if program == "step_rows":
        return S.step_rows.lower(params, cache, logits,
                                 sds((slots, 2), jnp.uint32), rows, 8,
                                 cfg).as_text()
    return S.admit_rows.lower(params, cache, logits, rows,
                              sds((slots, 64), jnp.int32), rows,
                              cfg).as_text()


def _toy_of_the_benchmark(name: str):
    import jax.numpy as jnp
    from benchmark.lib import modelcfg
    c = modelcfg.load(os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "data", name))
    return modelcfg.family(c).program_config(c, dtype=jnp.bfloat16,
                                             remat=False)


def digest(which: str) -> str:
    return hashlib.sha256(lowered(which).encode()).hexdigest()[:16]


@pytest.mark.parametrize("which", sorted(PARENT))
def test_lowered_text_is_the_parents(which):
    assert digest(which) == PARENT[which]


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.abspath(sys.argv[1]))
    for name in sorted(PARENT):
        print(name, digest(name))
