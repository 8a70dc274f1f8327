"""Drive the rest of a run — everything but the harness's look for a chip
— on the CPU at toy sizes: sound, it is correct; with the timed path
broken underneath, ``correct`` comes out false."""

import os

import pytest

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN_LIMITS = {"loss_step0_gap": 1e-3, "loss_step1_gap": 1e-3,
                "grad_norm_worst_leaf_gap": 0.01,
                "param_change_worst_leaf_gap": 0.05,
                "nonfinite_losses": 0, "repeated_rows": 0}
SERVE_LIMITS = {"served_token_mismatch_share": 0.02,
                "served_token_mean_gap": 1e-4,
                "served_token_widest_gap": 1e-3,
                "streams_with_wrong_token_count": 0}


def _bench(traffic_file, e2e):
    return {"workloads": [{"name": "toy", "chips": 1,
                           "config": os.path.join(HERE, "tiny.json"),
                           "traffic": os.path.join(HERE, traffic_file)}],
            "end_to_end": [{"name": n, "unit": "x"} for n in e2e],
            "per_layer": []}


@pytest.mark.parametrize("fault,correct", [
    ("", True), ("frozen_state", False), ("half_batch", False)])
def test_train_run(tmp_path, fault, correct):
    got = run.run_cell(_bench("train-tiny.json",
                              ["train_tokens_per_s", "setup_s"]),
                       "toy", 2**31 + 3, 2.0, 0, platform="cpu",
                       root=str(tmp_path), fault=fault, limits=TRAIN_LIMITS)
    assert got["correct"] is correct
    assert got["metrics"]["train_tokens_per_s"]["value"] > 0
    assert got["device"]["platform"] == "cpu"


@pytest.mark.parametrize("mix,fault,correct", [
    ("chat-tiny.json", "", True), ("saturated-tiny.json", "", True),
    ("chat-tiny.json", "wrong_token", False),
    ("chat-tiny.json", "wrong_token_one_slot", False)])
def test_serve_run(tmp_path, mix, fault, correct):
    e2e = ["serve_tokens_per_s", "itl_p95_ms", "setup_s"]
    got = run.run_cell(_bench(mix, e2e), "toy", 2**31 + 4, 3.0, 0,
                       platform="cpu", root=str(tmp_path), fault=fault,
                       limits=SERVE_LIMITS)
    assert got["correct"] is correct
    assert got["failed"] == 0 and got["attempted"] > 0


@pytest.mark.parametrize("path,correct", [
    ("bf16", True), ("int8_weights", False), ("int8_kv", False)])
def test_serve_control(tmp_path, path, correct):
    """The control of a serving cell at toy size: the program's own int8
    weights (``models/quantize.py``) or int8 cache in the program's place,
    through the cell's own driver, come out as not correct."""
    from benchmark.tools import control_serve
    (got,) = control_serve.read(
        path, 6.0, [("toy", 2**31 + 6)], platform="cpu",
        root=str(tmp_path), emit=lambda line: None,
        bench=_bench("saturated-tiny.json", []))
    assert got["finished"] > 0
    assert all(got["compared"][k] <= v
               for k, v in SERVE_LIMITS.items()) is correct


def test_no_result_without_the_platform(tmp_path):
    """Asked for a TPU where there is none: no result, a non-zero exit."""
    with pytest.raises(SystemExit) as e:
        run.run_cell(_bench("train-tiny.json", ["setup_s"]), "toy", 1, 1.0,
                     0, platform="tpu", root=str(tmp_path),
                     limits=TRAIN_LIMITS)
    assert e.value.code not in (0, None)
