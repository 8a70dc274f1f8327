"""Driver-entry regression tests.

Round-1 postmortem: the driver's multichip check failed because
``dryrun_multichip`` asserted on ``len(jax.devices())`` instead of
bootstrapping a virtual mesh ("need 8 devices, have 1"). These tests
pin the self-bootstrap behavior: from a process that can only see
one device, the dryrun must still pass by re-execing onto a forced
n-device CPU backend — the reference's run-anywhere fake-cluster
property (tony-mini MiniCluster.java:44-60).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import __graft_entry__ as graft  # noqa: E402


def test_virtual_mesh_env_forces_cpu_and_device_count(monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS",
        "--xla_dump_to=/tmp/x --xla_force_host_platform_device_count=8")
    env = graft._virtual_mesh_env(16)
    assert env["JAX_PLATFORMS"] == "cpu"
    # stale forced count replaced, unrelated flags kept
    assert "--xla_force_host_platform_device_count=16" in env["XLA_FLAGS"]
    assert "device_count=8" not in env["XLA_FLAGS"]
    assert "--xla_dump_to=/tmp/x" in env["XLA_FLAGS"]


@pytest.mark.e2e
@pytest.mark.slow
def test_dryrun_bootstraps_when_devices_insufficient():
    """Caller pinned to ONE device must still pass dryrun_multichip(4)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; assert len(jax.devices()) == 1, jax.devices(); "
         "import __graft_entry__ as g; g.dryrun_multichip(4); "
         "print('BOOTSTRAP_OK')"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "BOOTSTRAP_OK" in proc.stdout


@pytest.mark.slow
def test_dryrun_is_self_verifying_against_broken_collective(monkeypatch):
    """A deliberately wrong shard_map body (a ring that never rotates —
    each chunk attends only to its local K/V, the canonical missing-
    collective bug GSPMD can't catch because the result is finite and
    well-shaped) must FAIL the dryrun's sharded-vs-unsharded comparison,
    not sail through a finiteness check."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    import importlib

    R = importlib.import_module("tony_tpu.parallel.ring_attention")

    def corrupted(q, k, v, axis_name="cp", causal=True, scale=None):
        # local-only attention: the ppermute hops are "forgotten"
        return R._single_chunk(q, k, v, causal=causal, scale=scale)

    monkeypatch.setattr(R, "ring_attention_local", corrupted)
    with pytest.raises(AssertionError, match="loss|grad norm"):
        graft._dryrun_body(8)


@pytest.mark.slow
def test_dryrun_self_verification_passes_in_process():
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    graft._dryrun_body(8)
