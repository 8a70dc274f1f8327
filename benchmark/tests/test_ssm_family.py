"""The state-space hybrid family (``families/ssm_hybrid_decoder.py``) and
the two readers that came with its cell, on the CPU: its counts at the
published widths, to the unit; the reference's mixer against a plain
token-at-a-time computation that shares none of its code; the ``live``
mask of ``tools/control_state.py`` (the sound reading over ``[prompt | pad
| answer]`` is the plain reference over ``[prompt | answer]``) and its
four faults at toy size; ``ssm_step_share_pct.serve`` and
``ssm_state_roofline.serve`` on a hand-made trace; the cell's files
against ISSUE 41's parameters."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.lib import modelcfg, reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(ROOT, "tests", "data", "tiny-ssm-hybrid.json")
CONFIG, CELL = "granite-4.0-h-micro-l40", "serve-granite4hmicro-wide-decode"
MS = 1_000_000


def test_counts_at_the_published_widths():
    """ISSUE 41's arithmetic, to the unit: a mixer 25,847,232, a
    state-space layer 76,182,976, an attention layer 60,821,504, the
    tied embedding 205,520,896, the model 3,191,396,096; a decode step
    moves the weights once, the state read AND written for every slot,
    and the live K/V rows of the four full layers."""
    c = modelcfg.load(CONFIG)
    fam = modelcfg.family(c)
    m = fam._dims(c)
    assert (m["in"], m["conv"], m["inner"]) == (8512, 4352, 4096)
    mixer = fam._ssm_matrices(m) + sum(fam._ssm_small(m))
    assert mixer == 25_847_232
    assert mixer + fam._mlp_params(m) + 2 * m["d"] == 76_182_976
    assert fam._attn_matrices(m) + fam._mlp_params(m) + 2 * m["d"] \
        == 60_821_504
    assert fam.param_count(c) == 3_191_396_096
    kinds = fam.layer_kinds(c)
    assert (kinds.count("ssm"), kinds.count("attention")) == (36, 4)
    assert [i for i, k in enumerate(kinds) if k == "attention"] \
        == [5, 15, 25, 35]
    # the weights (dt_bias, A_log, D float32), nothing else without a run
    once = 2 * fam.param_count(c) + 36 * 192 * 2
    assert fam.decode_step_bytes(c, 0.0, None) == once
    ctx = {"mix": {"slots": 64}}
    width = 2 if c["state_dtype"] == "bfloat16" else 4
    state = 2 * 64 * 36 * (128 * 4096 * width + 3 * 4352 * 2)
    assert fam.decode_step_bytes(c, 0.0, ctx) == once + state
    assert fam.decode_step_bytes(c, 1.0, ctx) - once - state \
        == 4 * 2 * 512 * 2                   # a K and a V row a full layer
    fl, by = fam.ssm_step_flops_bytes(c, 64)
    assert fl == 5 * 64 * 4096 * 128
    assert by == 64 * (2 * 128 * 4096 * width + 4 * (3 * 4096 + 256))
    assert fam.forward_flops_per_token(c, 1024) > 2 * (
        fam.param_count(c) - 36 * 25_000) + 36 * 5 * 4096 * 128


def test_reference_mixer_is_the_recurrence_a_token_at_a_time():
    """By hand, in float64 numpy, one position after another: the conv
    over the last four inputs (zeros before position 0), softplus steps,
    ``S <- exp(dt a) S + dt u (x) B``, ``y = S C + D u``, the gate and
    then the norm over all d_inner."""
    c = modelcfg.load(TOY)
    fam = modelcfg.family(c)
    p = {n: np.asarray(w, np.float64) for n, w in fam.layer_weights(
        np.uint32(5), np.int32(0), c, jnp.float32, "ssm").items()}
    h = np.random.default_rng(2).normal(size=(1, 11, 64))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(fam.ssm_mixer(jnp.asarray(h, jnp.float32), {
            n: jnp.asarray(w, jnp.float32) for n, w in p.items()}, c))
    heads, width, n, inner, conv = 8, 16, 16, 128, 160
    silu = lambda v: v / (1 + np.exp(-v))                   # noqa: E731
    state = np.zeros((heads, width, n))
    inputs = np.zeros((3 + 11, conv))
    want = np.zeros((11, 64))
    for t in range(11):
        zcd = h[0, t] @ p["w_in"]
        z, inputs[3 + t], d = zcd[:inner], zcd[inner:inner + conv], \
            zcd[inner + conv:]
        cc = silu(p["conv_b"] + sum(p["conv_w"][j] * inputs[t + j]
                                    for j in range(4)))
        u = cc[:inner].reshape(heads, width)
        b, cm = cc[inner:inner + n], cc[inner + n:]
        dt = np.log1p(np.exp(d + p["dt_bias"]))
        a = -np.exp(p["A_log"])
        state = (np.exp(dt * a)[:, None, None] * state
                 + (dt[:, None] * u)[:, :, None] * b)
        y = (state @ cm + p["D"][:, None] * u).reshape(inner) * silu(z)
        y = y / np.sqrt(np.mean(y * y) + 1e-5) * p["gate_norm"]
        want[t] = y @ p["w_out"]
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=0)


def _toy():
    c = modelcfg.load(TOY)
    return c, modelcfg.family(c)


def test_the_live_mask_skips_padding_as_an_admission_must():
    """The sound reading of ``tools/control_state.py``: the reference
    over ``[prompt | pad | answer]`` under the ``live`` mask gives, at the
    served positions, the logits of the plain reference over ``[prompt |
    answer]`` — the state took the identity step through the padding,
    the conv's window did not shift, attention did not see it."""
    from benchmark.tools import control_routed, control_state
    c, fam = _toy()
    seed = 2**31 + 5
    with jax.default_matmul_precision("highest"):
        tokens, live, served = control_state.rows_of(seed, 512, 2, 9, 7, 10)
        masked = control_routed.logits_of(
            c, seed, control_state.skipping(fam, live), None, tokens)
        keep = np.asarray(live[0])
        plain = np.asarray(reference.Reference(c, seed, None).logits(
            tokens[:, keep]))
    np.testing.assert_allclose(masked[:, keep], plain, atol=2e-5, rtol=0)
    assert list(served) == [8] + list(range(16, 25))


def test_each_fault_of_the_state_crosses_the_toy_limits():
    """``control_state.read`` at toy size: the state zeroed where decode
    takes over, the padded tail run through, the conv's window taken
    from the tail, the softmax at head_dim^-1/2 — each is not correct
    by the toy cell's limits. (Published widths:
    ``benchmark/limits/serve-granite4hmicro-wide-decode.json``.)"""
    from benchmark.tests.test_run_faults import SERVE_LIMITS
    from benchmark.tools import control_state
    c, fam = _toy()
    lines = []
    with jax.default_matmul_precision("highest"):
        got = control_state.read(c, fam, 2**31 + 6, SERVE_LIMITS, 4, 9, 7,
                                 24, emit=lines.append)
    assert set(got) == set(fam.FAULTS) and len(lines) == 5
    for fault, reading in got.items():
        assert reading["not_correct_by"], (fault, reading)


def test_refused_with_the_reason(tmp_path):
    with open(TOY) as f:
        toy = json.load(f)

    def changed(**keys):
        path = os.path.join(tmp_path, "changed.json")
        with open(path, "w") as f:
            json.dump(dict(toy, **keys), f)
        return path

    with pytest.raises(ValueError, match="mamba or attention"):
        modelcfg.load(changed(layer_types=["mamba", "sliding", "mamba",
                                           "mamba"]))
    with pytest.raises(ValueError, match="an attention layer among them"):
        modelcfg.load(changed(layer_types=["mamba"] * 4))
    with pytest.raises(ValueError, match="mamba_expand x hidden_size"):
        modelcfg.load(changed(mamba_n_heads=4))
    with pytest.raises(ValueError, match="shared SwiGLU alone"):
        modelcfg.load(changed(num_local_experts=8))
    with pytest.raises(ValueError, match="a tied head"):
        modelcfg.load(changed(position_embedding_type="rope"))
    with pytest.raises(ValueError, match="state_dtype"):
        modelcfg.load(changed(state_dtype="int8"))


# ------------------------------------------------------------ the readers
def _launch(n, start, dur):
    return [f'%tony_ssm_step.{n} = (f32[64,1,4096], bf16[36,64,128,4096]) '
            'custom-call(), custom_call_target="tpu_custom_call"', start,
            dur]


def _ctx(modules, ops, chunk=8):
    return {"trace": {"devices": [{"modules": modules, "ops": ops}]},
            "counters": {"chunk": chunk}, "c": modelcfg.load(CONFIG),
            "mix": {"slots": 64},
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}


def test_state_update_share_and_roofline_of_the_decode_chunks():
    modules = [["jit_step_rows(1)", 0, 120 * MS],
               ["jit_admit_rows(2)", 120 * MS, 40 * MS],
               ["jit_step_rows(1)", 160 * MS, 120 * MS]]
    ops = [_launch(3, 10 * MS, 40 * MS), ["%fusion.9", 50 * MS, 50 * MS],
           # a launch inside an admission is not a decode chunk's
           _launch(3, 130 * MS, 30 * MS),
           _launch(4, 170 * MS, 56 * MS)]
    ctx = _ctx(modules, ops)
    assert bench_run.read_metric("ssm_step_share_pct.serve", ctx) == \
        pytest.approx(40.0)
    fam = modelcfg.family(ctx["c"])
    _, by = fam.ssm_step_flops_bytes(ctx["c"], 64)
    least = 8 * 36 * by / 819e9              # bandwidth-bound
    assert bench_run.read_metric("ssm_state_roofline.serve", ctx) == \
        pytest.approx(100.0 * least / 0.048)
    assert 0.04 < least < 0.06


def test_no_kernel_no_chunk_or_no_shape_function_reads_none():
    plain = [["%fusion.1", 0, 50 * MS]]
    chunk = [["jit_step_rows(1)", 0, 80 * MS]]
    for name in ("ssm_step_share_pct.serve", "ssm_state_roofline.serve"):
        assert bench_run.read_metric(name, _ctx(chunk, plain)) is None
        assert bench_run.read_metric(name, _ctx(
            [["jit_admit_rows(2)", 0, 80 * MS]],
            [_launch(3, 10 * MS, 8 * MS)])) is None
    # a family without the kernel's shape function: the parent's program
    # under another configuration
    ctx = _ctx(chunk, [_launch(3, 10 * MS, 8 * MS)])
    ctx["c"] = modelcfg.load("phi-3-mini-4k-l24")
    assert bench_run.read_metric("ssm_state_roofline.serve", ctx) is None


# --------------------------------------------------------- the cell's files
def test_the_cells_files_are_the_issues():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["source"].endswith(
        "ibm-granite/granite-4.0-h-micro/blob/main/config.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "saturated-wide-long-answers-fullvocab", 1)
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == {"ssm_step_share_pct.serve",
                         "ssm_state_roofline.serve"}
    assert {m["moves"] for m in mine.values()} == {"itl_p95_ms"}
    reported = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert reported >= {"serve_tokens_per_s", "itl_p95_ms", "setup_s",
                        "decode_bw_pct.serve", "cached_attn_share_pct.serve",
                        "admit_device_share_pct.serve",
                        "step_utilization.serve"}
    _, _, c, mix = bench_run.load_cell(CELL)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "saturated-wide-long-answers.json")) as f:
        sibling = json.load(f)
    # the other wide-decode cell's lengths, clients and slots: the two
    # differ by the model alone
    for key in ("loop", "clients", "slots", "cache_rows", "prompt_tokens",
                "answer_tokens", "drain_seconds", "pool_requests",
                "trace_seconds", "check_widths"):
        assert mix[key] == sibling[key], key
    assert (mix["kind"], mix["check_rows"], mix["check_requests"],
            mix["shape_seed"]) == ("serve_drain_rows", 1, 8, 20261002)
    # the published widths, unchanged, and nothing cut
    assert c["reduced"] == {} and c["num_hidden_layers"] == 40
    assert (c["hidden_size"], c["mamba_n_heads"], c["mamba_d_head"],
            c["mamba_d_state"], c["mamba_d_conv"], c["mamba_chunk_size"],
            c["shared_intermediate_size"], c["vocab_size"]) == (
        2048, 64, 64, 128, 4, 256, 8192, 100352)
    assert (c["attention_multiplier"], c["embedding_multiplier"],
            c["residual_multiplier"], c["logits_scaling"],
            c["rms_norm_eps"]) == (0.015625, 12, 0.22, 8, 1e-05)
    assert c["layer_types"] == (["mamba"] * 5 + ["attention"]
                                + ["mamba"] * 4) * 4
    with open(os.path.join(ROOT, "benchmark", "limits",
                           f"{CELL}.json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == {
        "served_token_mismatch_share", "served_token_mean_gap",
        "served_token_widest_gap", "streams_with_wrong_token_count"}
    assert limits["limits"]["streams_with_wrong_token_count"] == 0
