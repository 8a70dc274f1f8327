"""The reader of the decode chunk's cached read (``tony_cached_attn``
launches inside ``jit_step_rows`` executions), from one hand-made trace;
a trace whose chunks name no such kernel — the program before PR 36, the
latent read — reads None, and so does one without a chunk."""

import pytest

from benchmark import run as bench_run

MS = 1_000_000                                  # the trace counts ns


def _kernel(n, start, dur):
    return [f'%tony_cached_attn.{n} = bf16[6,32,3072] custom-call(), '
            'custom_call_target="tpu_custom_call"', start, dur]


def _ctx(modules, ops):
    return {"trace": {"devices": [{"modules": modules, "ops": ops}]}}


def test_share_of_the_chunks_device_time():
    modules = [["jit_step_rows(1)", 0, 80 * MS],
               ["jit_admit_rows(2)", 80 * MS, 40 * MS],
               ["jit_step_rows(1)", 120 * MS, 80 * MS]]
    ops = [_kernel(3, 10 * MS, 8 * MS), ["%fusion.9", 20 * MS, 50 * MS],
           # a launch inside an admission is not a decode chunk's
           _kernel(3, 90 * MS, 30 * MS),
           _kernel(4, 130 * MS, 24 * MS)]
    assert bench_run.read_metric(
        "cached_attn_share_pct.serve", _ctx(modules, ops)) == \
        pytest.approx(20.0)


def test_no_kernel_or_no_chunk_reads_none():
    walk = [["%fusion.1", 0, 50 * MS]]
    assert bench_run.read_metric("cached_attn_share_pct.serve", _ctx(
        [["jit_step_rows(1)", 0, 80 * MS]], walk)) is None
    assert bench_run.read_metric("cached_attn_share_pct.serve", _ctx(
        [["jit_admit_rows(2)", 0, 80 * MS]],
        [_kernel(3, 10 * MS, 8 * MS)])) is None
