"""The readers of what the program names from inside: the engine's waits
and its ``emit`` phase (differenced ``PhaseTimes`` rows), and the flash
kernels by their ``tony_flash_*`` names. Hand-made contexts; a program
that names nothing (the parent) reads as None, never as an error."""

import pytest

from benchmark import run as bench_run
from benchmark.lib import flash_kernels, modelcfg


def _read(name, ctx):
    return bench_run.read_metric(name, ctx)


def _serve_ctx(phases):
    return {"counters": {"phases": phases}}


def test_waits_are_means_over_the_window():
    ctx = _serve_ctx({"queue_wait": {"total_s": 0.6, "count": 4},
                      "first_token": {"total_s": 1.0, "count": 5},
                      "emit": {"total_s": 0.03, "count": 9},
                      "dispatch": {"total_s": 0.1, "count": 10}})
    assert _read("queue_wait_ms.serve", ctx) == pytest.approx(150.0)
    assert _read("admit_to_first_token_ms.serve", ctx) == \
        pytest.approx(200.0)
    # emit is per decode chunk DISPATCHED, not per emit entry
    assert _read("wire_emit_ms.serve", ctx) == pytest.approx(3.0)


@pytest.mark.parametrize("phases", [
    None, {},                                           # no snapshot rows
    {"dispatch": {"total_s": 0.1, "count": 10}},        # the parent's keys
    {"queue_wait": {"total_s": 0.0, "count": 0},        # nothing admitted
     "first_token": {"total_s": 0.0, "count": 0},
     "emit": {"total_s": 0.0, "count": 0},
     "dispatch": {"total_s": 0.0, "count": 0}}])
def test_waits_read_none_where_nothing_was_counted(phases):
    ctx = _serve_ctx(phases)
    for name in ("queue_wait_ms.serve", "admit_to_first_token_ms.serve",
                 "wire_emit_ms.serve"):
        assert _read(name, ctx) is None


def test_forward_and_backward_sum_to_the_step_count():
    for config, batch, seq in (("mistral-7b-l4", 2, 8192),
                               ("phi-3-mini-4k-l24", 4, 1024)):
        c = modelcfg.load(config)
        fam = modelcfg.family(c)
        part = fam.flash_layer_flops_bytes(c, batch, seq)
        fl, by = fam.flash_train_flops_bytes(c, batch, seq)
        layers = c["num_hidden_layers"]
        assert layers * (part["fwd"][0] + part["bwd"][0]) == \
            pytest.approx(fl, rel=1e-12)
        assert layers * (part["fwd"][1] + part["bwd"][1]) == by


def test_mistral_layer_hand_count():
    c = modelcfg.load("mistral-7b-l4")
    part = modelcfg.family(c).flash_layer_flops_bytes(c, 2, 8192)
    # 3072.25 keys attended on average at 8,192 with window 4,096
    pairs = 2 * 8192 * 3072.25
    assert part["fwd"][0] == pytest.approx(4 * pairs * 4096)
    assert part["bwd"][0] == pytest.approx(10 * pairs * 4096)
    # forward: q, o 4096 wide, k, v 1024 wide, bf16; backward: q, o, do,
    # dq and k, v, dk, dv
    assert part["fwd"][1] == 2 * 8192 * (2 * 4096 + 2 * 1024) * 2
    assert part["bwd"][1] == 2 * 8192 * (4 * 4096 + 4 * 1024) * 2


def _train_ctx(ops):
    c = modelcfg.load("mistral-7b-l4")
    return {"trace": {"devices": [{"plane": "/device:TPU:0", "ops": ops,
                                   "modules": []}], "host": []},
            "mix": {"seq_len": 8192}, "c": c, "tokens_per_step": 2 * 8192,
            "cell": {"chips": 1},
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}


def test_kernel_rooflines_per_call():
    c = modelcfg.load("mistral-7b-l4")
    part = modelcfg.family(c).flash_layer_flops_bytes(c, 2, 8192)
    least_f = part["fwd"][0] / 197e12            # FLOP-bound, both
    least_b = part["bwd"][0] / 197e12
    assert least_f > part["fwd"][1] / 819e9
    ms = 1_000_000
    ops = [  # two layers: a forward, its remat replay, dq + dkv
        ["%tony_flash_fwd.16 tpu_custom_call", 0, 10 * ms],
        ["%tony_flash_fwd.16 tpu_custom_call", 20 * ms, 10 * ms],
        ["%tony_flash_fwd.17 tpu_custom_call", 40 * ms, 10 * ms],
        ["%tony_flash_fwd.17 tpu_custom_call", 60 * ms, 10 * ms],
        ["%tony_flash_bwd_dkv.11 tpu_custom_call", 80 * ms, 15 * ms],
        ["%tony_flash_bwd_dq.11 tpu_custom_call", 100 * ms, 10 * ms],
        ["%tony_flash_bwd_dkv.11 tpu_custom_call", 120 * ms, 15 * ms],
        ["%tony_flash_bwd_dq.11 tpu_custom_call", 140 * ms, 10 * ms],
        ["%fusion.3 fusion", 160 * ms, 99 * ms],
        ["%closed_call.9 tpu_custom_call", 300 * ms, 99 * ms]]  # unnamed
    ctx = _train_ctx(ops)
    assert flash_kernels.kernel_calls(ctx["trace"], "fwd") == (0.04, 4)
    assert flash_kernels.kernel_calls(ctx["trace"], "bwd") == (0.05, 2)
    assert _read("flash_fwd_roofline.train", ctx) == \
        pytest.approx(100 * least_f / 0.010)
    assert _read("flash_bwd_roofline.train", ctx) == \
        pytest.approx(100 * least_b / 0.025)
    # the fused backward is one call a layer
    fused = _train_ctx([["%tony_flash_bwd_fused.2 tpu_custom_call",
                         i * 30 * ms, 20 * ms] for i in range(3)])
    assert _read("flash_bwd_roofline.train", fused) == \
        pytest.approx(100 * least_b / 0.020)
    # a mesh of four shares one call's work
    ctx["cell"] = {"chips": 4}
    assert _read("flash_fwd_roofline.train", ctx) == \
        pytest.approx(100 * least_f / 4 / 0.010)


def test_kernel_rooflines_none_without_the_names():
    parent = _train_ctx([["%checkpoint.23 tpu_custom_call", 0, 5],
                         ["%closed_call.9 tpu_custom_call", 9, 5]])
    assert _read("flash_fwd_roofline.train", parent) is None
    assert _read("flash_bwd_roofline.train", parent) is None
    no_peaks = dict(_train_ctx([["%tony_flash_fwd.1 tpu_custom_call", 0, 5]]),
                    peaks=None)
    assert _read("flash_fwd_roofline.train", no_peaks) is None
