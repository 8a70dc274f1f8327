"""The two readers of the admission dispatches (``jit_admit_rows`` on the
"XLA Modules" line): a mean per execution and a share of the window, from
one hand-made trace; a trace without an admission reads None."""

import pytest

from benchmark import run as bench_run

MS = 1_000_000                                  # the trace counts ns


def _ctx(modules):
    ops = [["%fusion.1", start, dur] for _, start, dur in modules]
    return {"trace": {"devices": [{"modules": modules, "ops": ops}]}}


def test_admissions_per_call_and_over_the_window():
    ctx = _ctx([["jit_step_rows(123)", 0, 90 * MS],
                ["jit_admit_rows(77)", 90 * MS, 12 * MS],
                ["jit_step_rows(123)", 102 * MS, 90 * MS],
                ["jit_admit_rows(78)", 192 * MS, 48 * MS],    # another bucket
                ["jit_step_rows(123)", 240 * MS, 60 * MS]])
    assert bench_run.read_metric("admit_device_ms.serve", ctx) == \
        pytest.approx(30.0)
    assert bench_run.read_metric("admit_device_share_pct.serve", ctx) == \
        pytest.approx(20.0)


def test_no_admission_reads_none():
    ctx = _ctx([["jit_step_rows(123)", 0, 90 * MS]])
    assert bench_run.read_metric("admit_device_ms.serve", ctx) is None
    assert bench_run.read_metric("admit_device_share_pct.serve", ctx) is None
