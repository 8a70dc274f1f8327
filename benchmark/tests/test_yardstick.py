"""The yardstick's arithmetic: FLOPs against a hand count, generators that
repeat, percentiles, and the trace reduction on a small recorded trace."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import flops, modelcfg, stats, traffic, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def test_flops_mistral_hand_count():
    c = modelcfg.load("mistral-7b-l4")
    # per layer and token, forward: wq 2*4096*4096, wo the same, wk and wv
    # 2*4096*1024 each; MLP 3 matrices of 2*4096*14336; attention 4*4096
    # per attended key, 3072.25 keys on average at 8,192 with window 4,096
    # ((4096*4097/2 + 4096*4096)/8192); head 2*4096*32000
    layer = (2 * 2 * 4096 * 4096 + 2 * 2 * 4096 * 1024
             + 3 * 2 * 4096 * 14336 + 4 * 3072.25 * 4096)
    fam = modelcfg.family(c)
    assert fam.forward_flops_per_token(c, 8192) == pytest.approx(
        4 * layer + 2 * 4096 * 32000, rel=1e-12)
    assert flops.train_flops_per_token(c, 8192) / 1e9 == pytest.approx(
        6.625, abs=0.001)
    assert fam.param_count(c) == 4 * 218_112_000 + 2 * 131_072_000 + 4096


def test_flops_phi3_hand_count():
    c = modelcfg.load("phi-3-mini-4k-l24")
    # MHA: four 3072x3072 projections; MLP 3 x 3072x8192; full causal at
    # 1,024 (window 2,047 never binds): 512.5 keys on average
    layer = 4 * 2 * 3072 * 3072 + 3 * 2 * 3072 * 8192 + 4 * 512.5 * 3072
    fam = modelcfg.family(c)
    assert fam.forward_flops_per_token(c, 1024) == pytest.approx(
        24 * layer + 2 * 3072 * 32064, rel=1e-12)
    assert fam.param_count(c) == pytest.approx(2.915e9, rel=1e-3)
    # a decode step reads every matmul weight once and the live cache:
    # 294,912 bytes a token at 24 layers of 32 x 96 K and V in bf16
    one = fam.decode_step_bytes(c, 1.0) - fam.decode_step_bytes(c, 0.0)
    assert one == 294_912


def test_flash_shape_function():
    c = modelcfg.load("mistral-7b-l4")
    fl, by = modelcfg.family(c).flash_train_flops_bytes(c, 2, 8192)
    pairs = 2 * 8192 * 3072.25
    assert fl == pytest.approx(4 * 14 * pairs * 4096)
    # fwd q,o (4096 wide) + k,v (1024 wide); bwd q,o,do,dq + k,v,dk,dv
    assert by == 4 * 2 * 8192 * ((2 + 4) * 4096 + (2 + 4) * 1024) * 2


def test_generators_repeat_and_keep_the_work():
    mix = traffic.load("chat")
    a = traffic.requests(mix, 2**31 + 5, 40, 32064)
    b = traffic.requests(mix, 2**31 + 5, 40, 32064)
    other = traffic.requests(mix, 9, 40, 32064)
    assert a == b and a != other
    shape = lambda rs: [(len(r["prompt"]), r["max_new_tokens"])  # noqa
                        for r in rs]
    assert shape(a) == shape(other)        # same work, at the same moments
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= 64 and max(lens) <= 1024
    due = traffic.poisson_due_times(mix, 45.0)
    assert len(due) == round(mix["rate_per_s"] * 45) and due[-1] < 45.0
    assert (due == traffic.poisson_due_times(mix, 45.0)).all()
    assert (np.diff(due) > 0).all()
    t1 = traffic.token_records(2**31 + 7, 16, 128, 32000)
    assert (t1 == traffic.token_records(2**31 + 7, 16, 128, 32000)).all()
    assert len({r.tobytes() for r in t1}) == 16
    assert ((np.diff(t1.astype(np.int64), axis=1) % 32000) == 1).mean() > 0.8


def test_percentile_and_spread():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90 and stats.percentile(xs, 95) == 95
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([0, 0, 0, 7], 95) == 7
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0
    assert stats.spread([8, 9, 10, 10, 11, 12]) == pytest.approx(0.25)


def test_interval_arithmetic():
    ev = [["a", 0, 10], ["b", 5, 10], ["all-reduce.1", 12, 8], ["c", 30, 5],
          ["%while.1 = () while()", 0, 20]]       # encloses a, b: no leaf
    tr = {"devices": [{"plane": "/device:TPU:0", "ops": ev, "modules": []}],
          "host": [["bench.train_step", 0, 21], ["bench.sync_lag", 21, 20]]}
    assert xplane.union_ns(ev) == 25
    assert xplane.busy_and_window_s(tr) == (25e-9, 35e-9)
    assert xplane.idle_gaps(tr) == [["bench.sync_lag", 10e-9]]
    assert xplane.top_ops(tr, 2) == [["a", 10e-9], ["b", 10e-9]]
    assert xplane.short_name(
        '%x.1 = f32[2]{0} custom-call(), custom_call_target='
        '"tpu_custom_call"') == "%x.1 tpu_custom_call"
    assert xplane.short_name("%y = bf16[2]{0:T(8)} fusion(%a)") == \
        "%y fusion"


def test_reduction_of_a_recorded_tpu_trace():
    """A trace of three small steps (one matmul fusion, one Mosaic flash
    call) recorded on a TPU v5e by this PR."""
    path = os.path.join(HERE, "data", "small_tpu.xplane.pb")
    tr = xplane.reduce_file(path)
    (dev,) = tr["devices"]
    assert dev["plane"] == "/device:TPU:0"
    assert len(dev["modules"]) == 3
    assert len(dev["ops"]) % 3 == 0 and dev["ops"]
    assert sum(xplane.is_mosaic(e[0]) for e in dev["ops"]) == 3
    busy, window = xplane.busy_and_window_s(tr)
    assert 0 < busy < window
    marks = [e[0] for e in tr["host"]]
    assert marks.count("bench.train_step") == 3
    assert marks.count("bench.sync_lag") == 3
    gaps = dict(xplane.idle_gaps(tr))
    assert gaps and set(gaps) <= {"bench.train_step", "bench.sync_lag",
                                  "unannotated"}
    json.dumps(tr)                     # what the job writes for the parent
