"""TPU-backend end-to-end against a fake gcloud (the MiniYARN trick).

The reference validates its launch commands as strings (TestTonyClient.
java:23-31) but then exercises the real executor path on MiniYARN; the
fake gcloud on PATH (tests/fake_gcloud.py) gives this backend the same
treatment: slices are directories, ssh runs commands as local processes
under per-worker fake $HOMEs, so staged executors REALLY run — importing
tony_tpu from the staged .tony-framework copy and registering with the
real coordinator over RPC."""

import os
import subprocess
import sys
import threading
import time

import pytest

from tony_tpu.client.client import TonyClient
from tony_tpu.conf.config import TonyConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAKE_GCLOUD = os.path.join(REPO, "tests", "fake_gcloud.py")


@pytest.fixture
def fake_gcloud(tmp_path, monkeypatch):
    """Put a fake `gcloud` on PATH, rooted at tmp_path/fleet."""
    fleet = tmp_path / "fleet"
    fleet.mkdir()
    bindir = tmp_path / "bin"
    bindir.mkdir()
    gcloud = bindir / "gcloud"
    gcloud.write_text(
        f"#!/bin/bash\nexec {sys.executable} {FAKE_GCLOUD} \"$@\"\n")
    gcloud.chmod(0o755)
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")
    monkeypatch.setenv("FAKE_GCLOUD_ROOT", str(fleet))
    monkeypatch.setenv("FAKE_NUM_WORKERS", "2")
    return str(fleet)


def tpu_conf(tmp_path, extra=None):
    base = {
        "tony.staging.dir": str(tmp_path / "staging"),
        "tony.history.location": str(tmp_path / "hist"),
        "tony.application.timeout": "90000",
        "tony.scheduler.backend": "tpu",
        "tony.tpu.project": "test-proj",
        "tony.tpu.zone": "us-test1-a",
        "tony.tpu.accelerator-type": "v5litepod",
        "tony.tpu.state-refresh-ms": "200",
        "tony.worker.instances": "2",
        "tony.worker.tpu.topology": "4x4",     # 16 chips / 8 per host = 2
        "tony.application.python-binary-path": sys.executable,
    }
    base.update(extra or {})
    return TonyConfig(base)


def calls(fleet):
    path = os.path.join(fleet, "calls.log")
    if not os.path.exists(path):
        return []
    return open(path).read().splitlines()


@pytest.mark.e2e
class TestTpuBackendE2E:
    def test_provision_stage_launch_succeed(self, fake_gcloud, tmp_path):
        """Full happy path: slice provisioned, job dir staged to every
        worker home, executors launched over fake ssh run the user command
        with cwd ~/tony-job, job SUCCEEDS."""
        proof = tmp_path / "proof"
        client = TonyClient(
            tpu_conf(tmp_path),
            f'bash -c "pwd >> {proof}-$JOB_NAME-$TASK_INDEX; '
            f'ls tony-final.xml >> {proof}-$JOB_NAME-$TASK_INDEX"')
        assert client.run() == 0

        ops = [c.split()[3] for c in calls(fake_gcloud)]
        assert ops.count("create") == 1
        assert "scp" in ops            # tarball staged
        assert "delete" in ops         # teardown releases the slice

        # every worker home got the full localized job dir
        slice_dirs = [d for d in os.listdir(fake_gcloud)
                      if d.startswith("tony-")]
        assert len(slice_dirs) == 0    # slice deleted at stop()
        # the user command itself proved cwd + staging (one file per task)
        for idx in (0, 1):
            body = open(f"{proof}-worker-{idx}").read()
            assert body.splitlines()[0].endswith("tony-job")
            assert "tony-final.xml" in body

    def test_multi_slice_two_gangs(self, fake_gcloud, tmp_path):
        """tony.worker.slices=2: TWO slices are provisioned and staged,
        each gang's executors run with in-slice --worker indices, and every
        task sees its gang identity (TONY_SLICE_ID / TONY_NUM_SLICES)."""
        proof = tmp_path / "gang"
        client = TonyClient(
            tpu_conf(tmp_path, {"tony.worker.instances": "4",
                                "tony.worker.slices": "2"}),
            f'bash -c "echo $TONY_SLICE_ID/$TONY_NUM_SLICES '
            f'> {proof}-$TASK_INDEX"')
        assert client.run() == 0
        ops = [c.split()[3] for c in calls(fake_gcloud)]
        assert ops.count("create") == 2          # one VM per gang
        creates = [c.split()[4] for c in calls(fake_gcloud)
                   if c.split()[3] == "create"]
        assert {n[-3:] for n in creates} == {"-s0", "-s1"}
        for idx, want in ((0, "0/2"), (1, "0/2"), (2, "1/2"), (3, "1/2")):
            assert open(f"{proof}-{idx}").read().strip() == want

    def test_staged_framework_is_importable(self, fake_gcloud, tmp_path):
        """Executors must run from the STAGED tony_tpu copy (no install on
        hosts): the user task prints tony_tpu.__file__ and it must resolve
        inside ~/tony-job/.tony-framework."""
        proof = tmp_path / "whereis"
        client = TonyClient(
            tpu_conf(tmp_path, {"tony.worker.instances": "1",
                                "tony.worker.tpu.topology": "2x4"}),
            f'bash -c "{sys.executable} -c '
            f"'import tony_tpu; print(tony_tpu.__file__)'"
            f' > {proof}"')
        assert client.run() == 0
        where = open(proof).read().strip()
        assert "tony-job/.tony-framework/tony_tpu" in where

    @staticmethod
    def _preemption_command(tmp_path, marker):
        """User command for preemption choreography: announce this task
        started (a sentinel the test waits on — ssh launch lines hit
        calls.log BEFORE the executor process runs, so polling those
        races task startup), then exit 0 on the retry attempt or hang."""
        return (f'bash -c "touch {tmp_path}/started-$JOB_NAME-$TASK_INDEX; '
                f'if [ -f {marker} ]; then exit 0; else sleep 60; fi"')

    @staticmethod
    def _wait_tasks_started(tmp_path, n, timeout_s=60):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            started = [f for f in os.listdir(tmp_path)
                       if f.startswith("started-")]
            if len(started) >= n:
                return
            time.sleep(0.2)
        raise AssertionError("first-generation tasks never started")

    @staticmethod
    def _preempt(fleet, slice_name):
        with open(os.path.join(fleet, slice_name, "state"), "w") as f:
            f.write("PREEMPTED")

    def test_preemption_reprovisions_and_restages(self, fake_gcloud,
                                                  tmp_path):
        """Slice goes PREEMPTED mid-run: the coordinator retries from the
        preemption budget and the backend deletes + recreates + RESTAGES
        the slice; the relaunched attempt succeeds."""
        marker = tmp_path / "attempt2.marker"
        client = TonyClient(tpu_conf(tmp_path),
                            self._preemption_command(tmp_path, marker))
        result = {}
        t = threading.Thread(target=lambda: result.update(
            code=client.run()))
        t.start()
        try:
            self._wait_tasks_started(tmp_path, 2)
            marker.write_text("go")
            slice_name = [d for d in os.listdir(fake_gcloud)
                          if d.startswith("tony-")][0]
            self._preempt(fake_gcloud, slice_name)
        finally:
            t.join(timeout=120)
        assert result.get("code") == 0
        ops = [c.split()[3] for c in calls(fake_gcloud)]
        assert ops.count("create") == 2      # reprovisioned
        assert ops.count("scp") == 2         # re-staged
        assert ops.count("delete") >= 2      # dead slice + final teardown

    def test_multi_slice_preemption_reprovisions_only_that_gang(
            self, fake_gcloud, tmp_path):
        """2 gangs; one goes PREEMPTED mid-run. The session retries, the
        dead gang is deleted + recreated + restaged, and the surviving
        gang's VM is NOT reprovisioned."""
        marker = tmp_path / "attempt2.marker"
        client = TonyClient(
            tpu_conf(tmp_path, {"tony.worker.instances": "4",
                                "tony.worker.slices": "2"}),
            self._preemption_command(tmp_path, marker))
        result = {}
        t = threading.Thread(target=lambda: result.update(
            code=client.run()))
        t.start()
        try:
            self._wait_tasks_started(tmp_path, 4)
            marker.write_text("go")
            victim = [d for d in os.listdir(fake_gcloud)
                      if d.endswith("-s1")][0]
            self._preempt(fake_gcloud, victim)
        finally:
            t.join(timeout=120)
        assert result.get("code") == 0

        def gang_ops(op, suffix):
            return sum(1 for c in calls(fake_gcloud)
                       if c.split()[3] == op
                       and (c.split()[4].endswith(suffix) if op != "scp"
                            else suffix in c.split()[5]))
        # gang s1: deleted, recreated, RE-STAGED; gang s0 untouched
        assert gang_ops("create", "-s1") == 2
        assert gang_ops("delete", "-s1") >= 1
        assert gang_ops("scp", "-s1") >= 2      # initial + restage
        assert gang_ops("create", "-s0") == 1

    def test_topology_instances_mismatch_rejected_at_submit(self, tmp_path):
        """Review finding 6: instances=4 on a v5e 2x2 slice (1 host) must fail
        in the SUBMITTING process with an actionable message — before any
        coordinator launch, not as a late opaque ssh error."""
        conf = tpu_conf(tmp_path, {"tony.worker.instances": "4",
                                   "tony.worker.tpu.topology": "2x2"})
        client = TonyClient(conf, "true")
        with pytest.raises(ValueError, match="1 host"):
            client.stage()
        # nothing was staged or launched
        assert not os.path.exists(
            os.path.join(client.job_dir, "tony-final.xml"))

    def test_secret_via_file_never_in_ssh_argv(self, fake_gcloud, tmp_path):
        """Security on: executors must authenticate (job succeeds) while
        the secret travels as a chmod-600 staged file — absent from every
        gcloud argv (visible in ps) and from the stage tarball."""
        client = TonyClient(
            tpu_conf(tmp_path,
                     {"tony.application.security.enabled": "true"}),
            "true")
        assert client.run() == 0
        secret = client.secret
        assert secret
        for line in calls(fake_gcloud):
            assert secret not in line
        # the scp plan shipped the secret file + chmod'ed it
        joined = "\n".join(calls(fake_gcloud))
        assert ".tony-secret" in joined
        assert "chmod 600 ~/tony-job/.tony-secret" in joined

    def test_quota_exhausted_create_retries_with_backoff(
            self, fake_gcloud, tmp_path, monkeypatch):
        """The first two creates fail RESOURCE_EXHAUSTED (quota); the
        backend retries with backoff inside the SAME provisioning attempt
        and the job succeeds. No preemption budget is consumed — quota
        wait is not a lost slice."""
        monkeypatch.setenv("FAKE_FAIL_CREATE_N", "2")
        client = TonyClient(
            tpu_conf(tmp_path, {"tony.tpu.retry-backoff-ms": "50",
                                "tony.tpu.preemption-retries": "0"}),
            'bash -c "exit 0"')
        assert client.run() == 0
        ops = [c.split()[3] for c in calls(fake_gcloud)]
        assert ops.count("create") == 3        # 2 failures + 1 success

    def test_quota_budget_exhausted_fails_actionably(
            self, fake_gcloud, tmp_path, monkeypatch):
        monkeypatch.setenv("FAKE_FAIL_CREATE_N", "99")
        client = TonyClient(
            tpu_conf(tmp_path, {"tony.tpu.retry-backoff-ms": "20",
                                "tony.tpu.create-retries": "1"}),
            'bash -c "exit 0"')
        assert client.run() == 1
        ops = [c.split()[3] for c in calls(fake_gcloud)]
        assert ops.count("create") == 2        # initial + 1 retry

    def test_ssh_drop_mid_staging_restages_idempotently(
            self, fake_gcloud, tmp_path, monkeypatch):
        """The staging unpack drops once ('Connection reset by peer');
        the backend re-runs the WHOLE staging sequence (idempotent: rm -rf
        + untar, scp overwrites) and the job succeeds with a complete,
        uncorrupted job dir on every host."""
        monkeypatch.setenv("FAKE_FAIL_UNPACK_N", "1")
        proof = tmp_path / "proof"
        client = TonyClient(
            tpu_conf(tmp_path, {"tony.application.security.enabled":
                                "true"}),
            f'bash -c "ls tony-final.xml >> {proof}-$TASK_INDEX; '
            f'cat $PWD/.tony-secret >> {proof}-$TASK_INDEX"')
        assert client.run() == 0
        # the unpack ran twice (drop + re-stage) and the secret still
        # arrived AFTER the successful unpack
        unpacks = [c for c in calls(fake_gcloud)
                   if "tar -xzf" in c and c.split()[3] == "ssh"]
        assert len(unpacks) == 2
        for idx in (0, 1):
            body = open(f"{proof}-{idx}").read()
            assert "tony-final.xml" in body
            assert client.secret in body

    def test_describe_flakiness_does_not_fail_job(
            self, fake_gcloud, tmp_path, monkeypatch):
        """Transient describe failures map to state UNKNOWN — tasks keep
        running, nothing is treated as preempted, the job succeeds."""
        monkeypatch.setenv("FAKE_FAIL_DESCRIBE_N", "50")
        client = TonyClient(
            tpu_conf(tmp_path, {"tony.tpu.state-refresh-ms": "100",
                                "tony.tpu.preemption-retries": "0"}),
            'bash -c "sleep 2; exit 0"')
        assert client.run() == 0
        ops = [c.split()[3] for c in calls(fake_gcloud)]
        assert ops.count("describe") >= 2      # the poller really polled
