"""The seam is wide enough: a second family (``toy_moe.py``: the program's
expert block, other leaves, other keywords, another reference layer and
another count of bytes) lives under ``benchmark/tests/`` alone, and a toy
train mix and a toy serve mix through ``run.run_cell`` come out correct
with it, and not correct with the timed path broken underneath."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.lib import modelcfg
from benchmark.tests.test_families import PROVIDES, _size
from benchmark.tests.test_run_faults import SERVE_LIMITS, TRAIN_LIMITS

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "tiny-moe.json")


def _bench(traffic_file, e2e, per_layer=()):
    return {"workloads": [{"name": "toy", "chips": 1, "config": CONFIG,
                           "traffic": os.path.join(HERE, traffic_file)}],
            "end_to_end": [{"name": n, "unit": "x"} for n in e2e],
            "per_layer": [{"name": n, "unit": "x"} for n in per_layer]}


def test_the_fixture_is_a_whole_family():
    from tony_tpu.models import transformer as T
    c = modelcfg.load(CONFIG)
    fam = modelcfg.family(c)
    assert fam.__file__ == os.path.join(HERE, "toy_moe.py")
    assert not [n for n in PROVIDES if not hasattr(fam, n)]
    made = jax.eval_shape(lambda: fam.make_params(7, c, jnp.bfloat16))
    own = jax.eval_shape(lambda: T.init_params(
        jax.random.PRNGKey(0), fam.program_config(c, dtype=jnp.bfloat16)))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), made) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), own)
    assert fam.param_count(c) == _size(made)
    # 2 of 4 experts for one slot, all of them from two slots on
    one, two = ({"mix": {"slots": n}} for n in (1, 2))
    experts = 2 * 128 * 96 * 2                      # one expert, bf16
    assert fam.decode_step_bytes(c, 0.0, two) - \
        fam.decode_step_bytes(c, 0.0, one) == 2 * 2 * experts
    assert fam.decode_step_bytes(c, 0.0, two) == \
        fam.decode_step_bytes(c, 0.0, None)


def test_reference_layer_is_the_programs_expert_block():
    """Float32 both sides on the CPU: the plain layer (every expert for
    every token, the router's k weighted) against the program's dispatch."""
    from tony_tpu.models import transformer as T
    from benchmark.lib import reference, traffic
    c = modelcfg.load(CONFIG)
    fam = modelcfg.family(c)
    seed = 2**31 + 12
    tokens = traffic.token_records(seed, 2, 64, c["vocab_size"])[:, :64]
    want, _ = T.forward(fam.make_params(seed, c, jnp.float32),
                        jnp.asarray(tokens),
                        fam.program_config(c, dtype=jnp.float32))
    got = reference.Reference(c, seed, None, jnp.float32).logits(tokens)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


@pytest.mark.parametrize("fault,correct", [
    ("", True), ("frozen_state", False), ("half_batch", False)])
def test_train_run(tmp_path, fault, correct):
    got = run.run_cell(_bench("train-tiny.json",
                              ["train_tokens_per_s", "setup_s"]),
                       "toy", 2**31 + 13, 2.0, 0, platform="cpu",
                       root=str(tmp_path), fault=fault, limits=TRAIN_LIMITS)
    assert got["correct"] is correct
    assert got["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("mix,fault,correct", [
    ("chat-tiny.json", "", True), ("saturated-tiny.json", "", True),
    ("chat-tiny.json", "wrong_token_one_slot", False)])
def test_serve_run(tmp_path, mix, fault, correct):
    got = run.run_cell(_bench(mix, ["serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"]),
                       "toy", 2**31 + 14, 3.0, 0, platform="cpu",
                       root=str(tmp_path), fault=fault, limits=SERVE_LIMITS)
    assert got["correct"] is correct
    assert got["failed"] == 0 and got["attempted"] > 0
