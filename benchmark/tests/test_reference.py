"""The float32 reference against the program at toy width on the CPU
(both float32 there): the training forward, loss and gradients, and
prefill + decode through the cache. And the control: the reference in a
lower precision has to land outside what the sound program does."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import modelcfg, reference, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**31 + 11
OPT = {"lr": 3e-4, "weight_decay": 0.01, "warmup_steps": 1,
       "total_steps": 10000}


@pytest.fixture(scope="module")
def tiny():
    c = modelcfg.load(os.path.join(HERE, "tiny.json"))
    fam = modelcfg.family(c)
    return (c, fam.program_config(c, dtype=jnp.float32),
            fam.make_params(SEED, c, jnp.float32))


def _batches(c, b=2, s=64):
    rows = traffic.token_records(SEED, 2 * b, s, c["vocab_size"])
    return [(rows[i * b:(i + 1) * b, :s], rows[i * b:(i + 1) * b, 1:])
            for i in range(2)]


def test_weights_are_the_same_stacked_and_layer_by_layer(tiny):
    c, _, params = tiny
    for li in range(c["num_hidden_layers"]):
        one = modelcfg.family(c).layer_weights(
            np.uint32(SEED), np.int32(li), c, jnp.float32)
        for name, w in one.items():
            # the same draws; XLA's fusion may differ by one float32 ulp
            np.testing.assert_allclose(w, params["blocks"][name][li],
                                       rtol=3e-7, atol=0)


def test_forward_matches_transformer_forward(tiny):
    from tony_tpu.models import transformer as T
    c, cfg, params = tiny
    tokens = _batches(c)[0][0]
    want, _ = T.forward(params, jnp.asarray(tokens), cfg)
    got = reference.Reference(c, SEED, None, jnp.float32).logits(tokens)
    # float32 both sides; the window (48 < 64) and GQA (4/2) are live
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_loss_and_gradients_match_lm_loss(tiny):
    from tony_tpu.models import transformer as T
    c, cfg, params = tiny
    inputs, targets = _batches(c)[0]
    batch = {"inputs": jnp.asarray(inputs), "targets": jnp.asarray(targets)}
    loss, grads = jax.value_and_grad(T.lm_loss)(params, batch, cfg)
    ref_loss, ref = reference.Reference(
        c, SEED, None, jnp.float32).loss_and_grads(inputs, targets)
    assert ref_loss == pytest.approx(float(loss), abs=1e-5)
    for name, g in ref.items():
        parts = name.split("/")
        want = (grads["blocks"][parts[1]][int(parts[2])]
                if parts[0] == "blocks" else grads[name])
        np.testing.assert_allclose(g, want, atol=2e-5, rtol=1e-3)


def test_prefill_and_decode_through_the_cache_match(tiny):
    """The serving path: a padded prefill then decode steps through the
    cache give, at every served position, the reference's best token."""
    from tony_tpu.models.serve import ContinuousBatcher
    c, cfg, params = tiny
    rs = np.random.default_rng(5)
    prompts = [rs.integers(0, c["vocab_size"], n).tolist()
               for n in (17, 30)]
    served = ContinuousBatcher(params, cfg.scaled(remat=False), batch=2,
                               max_len=64).serve(prompts, [12, 9])
    # two blocks of one row, one per width: the blocks are found again
    gaps = reference.served_token_gaps(
        c, SEED, list(zip(prompts, served)), [32, 64], rows=1,
        weight_dtype=jnp.float32)
    assert [len(g) for g in gaps] == [12, 9]
    assert max(max(g) for g in gaps) < 1e-3


def test_control_lands_outside_the_sound_runs(tiny):
    """The train cell's control at toy size: the reference with int8 or
    fp8 weights in the program's place is told apart from the reference.
    (The serving cells' control is the program's own int8 paths:
    ``test_run_faults.test_serve_control``.)"""
    c, _, _ = tiny
    ref = reference.train_two_steps(c, SEED, _batches(c), OPT,
                                    weight_dtype=jnp.float32)
    for mode, least in (("int8", 0.002), ("fp8", 0.004)):
        low = reference.train_two_steps(c, SEED, _batches(c), OPT, mode=mode,
                                        weight_dtype=jnp.float32)
        gap, _ = reference.worst_leaf_gap(low["grad_norm"],
                                          ref["grad_norm"])
        assert gap > least, (mode, gap)
