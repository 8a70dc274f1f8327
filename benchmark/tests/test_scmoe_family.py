"""The shortcut-connected family (``families/scmoe_mla_decoder.py``) and
the reader that came with its cell, on the CPU: the reference's routed
block against a plain computation that shares none of its code (softmax
over ALL outputs, not renormalised; a zero expert is the identity); the
parent of a run refused in ``check``; ``moe_gmm_share_pct.serve`` on a
hand-made trace; the cell's files against ISSUE 37's parameters."""

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
                   "tiny-scmoe.json")
MS = 1_000_000


def test_reference_block_is_softmax_over_all_outputs_and_identity_zeros():
    """By hand, a token at a time: scores over the 16 routed + 8 zero
    outputs, the 4 largest of score + bias, weights = scores x 6 (they do
    NOT sum to 6), held experts [4, 8) computed, absent ones left out,
    zero ones the token itself."""
    c = modelcfg.load(TOY)
    fam = modelcfg.family(c)
    p = {n: np.asarray(w, np.float64) for n, w in fam.layer_weights(
        np.uint32(5), np.int32(1), c, jnp.float32, "moe").items()}
    h = np.random.default_rng(2).normal(size=(7, 128))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(fam.experts(jnp.asarray(h, jnp.float32), {
            n: jnp.asarray(w, jnp.float32) for n, w in p.items()}, c))
    want = np.zeros_like(h)
    classes = set()
    for t, x in enumerate(h):
        logits = x @ p["router"]
        z = np.exp(logits - logits.max())
        z /= z.sum()
        picks = np.argsort(-(z + p["router_bias"]))[:4]
        assert z[picks].sum() * 6 < 6 * 0.9
        for e in picks:
            w = z[e] * 6
            if e >= 16:
                want[t] += w * x
                classes.add("zero")
            elif 4 <= e < 8:
                g, u, d = (p[n][e - 4] for n in ("w_gate", "w_up", "w_down"))
                a = x @ g
                want[t] += w * ((a / (1 + np.exp(-a)) * (x @ u)) @ d)
                classes.add("held")
            else:
                classes.add("absent")
    assert classes == {"zero", "held", "absent"}
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_a_program_without_zero_experts_is_refused_in_check(tmp_path):
    """The parent commit of PR 37 on this family: ``check`` reads the
    program's source and raises at once, JAX-free — the run fails in
    milliseconds with exit code 1 and never starts a replica."""
    c = modelcfg.load(TOY)
    fam = modelcfg.family(c)
    kept = modelcfg.BENCH_DIR
    root = tmp_path / "old"
    (root / "tony_tpu" / "models").mkdir(parents=True)
    (root / "tony_tpu" / "models" / "transformer.py").write_text(
        'LAYER_KINDS = {"dense": 1, "moe": 2, "window_moe": 3}\n')
    modelcfg.BENCH_DIR = str(root / "benchmark")
    try:
        with pytest.raises(ValueError, match="no zero\\s+experts|no double"):
            fam.check(c, "toy")
    finally:
        modelcfg.BENCH_DIR = kept
    fam.check(c, "toy")
    with pytest.raises(ValueError, match="identity"):
        fam.check(dict(c, zero_expert_type="constant"), "toy")
    with pytest.raises(ValueError, match="not renormalised"):
        fam.check(dict(c, norm_topk_prob=True), "toy")
    with pytest.raises(ValueError, match="num_hidden_layers"):
        fam.check(dict(c, num_hidden_layers=3), "toy")


def _kernel(n, start, dur):
    return [f"%tony_moe_gmm.{n} tpu_custom_call", start, dur]


def _trace(modules, ops):
    return {"trace": {"devices": [{"modules": modules, "ops": ops}]}}


def test_gmm_share_of_the_decode_chunks():
    ctx = _trace(
        [["jit_step_rows(1)", 0, 80 * MS],
         ["jit_admit_rows(2)", 80 * MS, 40 * MS],
         ["jit_step_rows(1)", 120 * MS, 80 * MS]],
        [_kernel(3, 10 * MS, 8 * MS), ["%fusion.9", 20 * MS, 50 * MS],
         ["%tony_cached_attn.1 tpu_custom_call", 70 * MS, 5 * MS],
         # a launch inside an admission is not a decode chunk's
         _kernel(3, 90 * MS, 30 * MS),
         _kernel(4, 130 * MS, 16 * MS), _kernel(5, 150 * MS, 8 * MS)])
    assert bench_run.read_metric("moe_gmm_share_pct.serve", ctx) == \
        pytest.approx(100.0 * 32 / 160)


def test_gmm_share_reads_none_without_the_kernel_or_a_chunk():
    assert bench_run.read_metric("moe_gmm_share_pct.serve", _trace(
        [["jit_step_rows(1)", 0, 80 * MS]],
        [["%fusion.1", 0, 50 * MS]])) is None
    assert bench_run.read_metric("moe_gmm_share_pct.serve", _trace(
        [["jit_admit_rows(2)", 0, 80 * MS]],
        [_kernel(3, 10 * MS, 8 * MS)])) is None


def test_the_wide_decode_cells_files_are_the_issues():
    with open(os.path.join(os.path.dirname(HERE), "traffic",
                           "saturated-wide-long-answers.json")) as f:
        mix = json.load(f)
    assert (mix["kind"], mix["loop"], mix["clients"], mix["slots"],
            mix["cache_rows"], mix["drain_seconds"]) == (
        "serve_drain", "closed", 128, 64, 4096, 60)
    assert mix["prompt_tokens"] == {"median": 768, "sigma": 0.8, "min": 64,
                                    "max": 2048}
    assert mix["answer_tokens"] == {"median": 768, "sigma": 0.5, "min": 128,
                                    "max": 2048}
    assert (mix["pool_requests"], mix["shape_seed"],
            mix["check_requests"], mix["check_widths"]) == (
        800, 20261001, 16, [1024, 2048, 4096])
    # the warm-up's ladder (min, 2 min, ... <= max) reaches the longest
    # bucket, and the longest request fits a slot's rows
    n = mix["prompt_tokens"]["min"]
    while n * 2 <= mix["prompt_tokens"]["max"]:
        n *= 2
    assert n == 2048
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] \
        <= mix["cache_rows"] == max(mix["check_widths"])
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "serve-longcatflash-wide-decode"
    assert bench["workloads"][-1]["name"] == cell
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if cell in m.get("workloads", [])}
    assert listed == {
        "serve_tokens_per_s", "itl_p95_ms", "engine_host_ms.serve",
        "step_utilization.serve", "decode_bw_pct.serve",
        "wire_emit_ms.serve", "moe_experts_roofline.serve",
        "admit_device_share_pct.serve", "admit_device_ms.serve",
        "moe_gmm_share_pct.serve"}
    new = bench["per_layer"][-1]
    assert (new["name"], new["unit"], new["better"], new["source"],
            new["layer"], new["moves"]) == (
        "moe_gmm_share_pct.serve", "%", "lower", "device_trace",
        "model step", "itl_p95_ms")
    assert new["workloads"] == ["serve-kimik25-saturated",
                                "serve-commandaplus-mixedlen", cell]
    with open(os.path.join(os.path.dirname(HERE), "limits",
                           f"{cell}.json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == {
        "served_token_mismatch_share", "served_token_mean_gap",
        "served_token_widest_gap", "streams_with_wrong_token_count"}
    assert limits["limits"]["streams_with_wrong_token_count"] == 0
