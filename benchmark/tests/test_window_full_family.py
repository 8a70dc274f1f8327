"""The window / full attention family (``families/window_full_moe_decoder.
py``) and the reader that came with its cell, on the CPU: the reference's
two layer types against a plain dense computation that shares none of its
code; the layer's type riding inert in ``router_bias``; the live rows a
sliding layer must read; ``admit_attention_share_pct.serve`` on a
hand-made trace."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import modelcfg

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(os.path.dirname(os.path.dirname(HERE)), "tests", "data",
                   "tiny-window-full-moe.json")
MS = 1_000_000


def _plain_attention(q, k, v, window):
    """[S, H, d] x [S, KV, d]: every (head, query, key) by hand."""
    s, h, d = q.shape
    g = h // k.shape[1]
    out = np.zeros((s, h, d))
    for head in range(h):
        kk, vv = k[:, head // g], v[:, head // g]
        for i in range(s):
            lo = 0 if window is None else max(0, i - window + 1)
            sc = kk[lo:i + 1] @ q[i, head] * d ** -0.5
            p = np.exp(sc - sc.max())
            out[i, head] = (p / p.sum()) @ vv[lo:i + 1]
    return out


@pytest.mark.parametrize("full", [False, True])
def test_reference_attention_is_the_window_or_the_whole_context(full):
    c = modelcfg.load(TOY)
    fam = modelcfg.family(c)
    rs = np.random.default_rng(3)
    q = rs.normal(size=(1, 40, 8, 16)).astype(np.float32)
    k = rs.normal(size=(1, 40, 2, 16)).astype(np.float32)
    v = rs.normal(size=(1, 40, 2, 16)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = fam.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            12, jnp.asarray(full))
    want = _plain_attention(q[0], k[0], v[0], None if full else 12)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=0)


def test_layer_type_rides_in_the_bias_and_only_a_sliding_layer_rotates():
    """``layer_weights`` marks the full layer (index 3 of the toy's four)
    with a constant 1 over ``router_bias``; in ``layer_forward`` that
    switches rotation and window off together: a full layer's output at a
    position does not move when the sequence is shifted along the
    positions (no rotation), a sliding layer's last row does not see a
    key 12 or more behind it."""
    c = modelcfg.load(TOY)
    fam = modelcfg.family(c)
    p = [jax.tree.map(lambda w: w.astype(jnp.float32), fam.layer_weights(
        np.uint32(5), np.int32(li), c, jnp.float32, "moe"))
        for li in range(4)]
    assert [float(w["router_bias"][0]) for w in p] == [0, 0, 0, 1]
    assert all(float(jnp.ptp(w["router_bias"])) == 0 for w in p)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 30, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for li, sliding in ((0, True), (3, False)):
            base = fam.layer_forward(x, p[li], c, "moe")
            far = x.at[0, 3, 5].add(3.0)        # 26 behind the last row
            moved = fam.layer_forward(far, p[li], c, "moe")
            sees = float(jnp.abs(moved[0, -1] - base[0, -1]).max()) > 1e-6
            assert sees != sliding, (li, sees)
    rot = fam.rope(x.reshape(1, 30, 4, 16), jnp.arange(30)[None], c,
                   jnp.asarray(True))
    np.testing.assert_array_equal(rot, x.reshape(1, 30, 4, 16))


def test_window_share_of_live_rows():
    """A request of prompt 10 and 5 answers holds 10..14 rows; a window
    of 12 reads 10, 11, 12, 12, 12 of them."""
    c = dict(modelcfg.load(TOY), sliding_window=12)
    fam = modelcfg.family(c)
    mix = {"pool_requests": 1, "shape_seed": 1,
           "prompt_tokens": {"median": 10, "sigma": 0.0, "min": 10,
                             "max": 10},
           "answer_tokens": {"median": 5, "sigma": 0.0, "min": 5, "max": 5}}
    assert fam.window_share(c, mix) == pytest.approx(57 / 60)
    ctx = {"mix": dict(mix, slots=2)}
    per_row = (fam.decode_step_bytes(c, 100.0, ctx)
               - fam.decode_step_bytes(c, 0.0, ctx)) / 100
    assert per_row == pytest.approx((1 + 3 * 57 / 60) * 2 * 2 * 16 * 2)


def _trace(modules, ops):
    return {"trace": {"devices": [{"modules": modules, "ops": ops}]}}


def test_attention_share_of_the_admissions():
    flash = "%tony_flash_fwd.3 tpu_custom_call"
    ctx = _trace(
        [["jit_step_rows(1)", 0, 90 * MS],
         ["jit_admit_rows(7)", 90 * MS, 40 * MS],
         ["jit_step_rows(1)", 130 * MS, 90 * MS],
         ["jit_admit_rows(8)", 220 * MS, 10 * MS]],
        [["%fusion.1", 0, 90 * MS],
         [flash, 95 * MS, 6 * MS], [flash, 110 * MS, 4 * MS],
         ["%tony_moe_gmm.2 tpu_custom_call", 120 * MS, 5 * MS],
         [flash, 140 * MS, 50 * MS],            # outside an admission
         [flash, 221 * MS, 5 * MS]])
    assert bench_run.read_metric("admit_attention_share_pct.serve", ctx) \
        == pytest.approx(100.0 * 15 / 50)


def test_attention_share_reads_none_without_the_kernel_or_an_admission():
    ctx = _trace([["jit_admit_rows(7)", 0, 40 * MS]],
                 [["%fusion.1", 0, 40 * MS]])
    assert bench_run.read_metric("admit_attention_share_pct.serve",
                                 ctx) is None
    ctx = _trace([["jit_step_rows(1)", 0, 40 * MS]],
                 [["%tony_flash_fwd.3 tpu_custom_call", 0, 40 * MS]])
    assert bench_run.read_metric("admit_attention_share_pct.serve",
                                 ctx) is None


def test_the_cells_files_are_the_issues():
    with open(os.path.join(os.path.dirname(HERE), "traffic",
                           "saturated-mixed-lengths.json")) as f:
        mix = json.load(f)
    assert (mix["clients"], mix["slots"], mix["cache_rows"],
            mix["drain_seconds"], mix["loop"]) == (64, 32, 16384, 40,
                                                   "closed")
    assert mix["prompt_tokens"] == {"median": 3072, "sigma": 0.9,
                                    "min": 224, "max": 14336}
    assert mix["answer_tokens"] == {"median": 384, "sigma": 0.6, "min": 64,
                                    "max": 1536}
    # the warm-up's ladder (min, 2 min, ... <= max) reaches the longest
    # bucket: 224 x 64 = 14,336 pads to 16,384
    n = mix["prompt_tokens"]["min"]
    while n * 2 <= mix["prompt_tokens"]["max"]:
        n *= 2
    assert n == 14336 and max(mix["check_widths"]) == 16384
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] \
        <= mix["cache_rows"]
