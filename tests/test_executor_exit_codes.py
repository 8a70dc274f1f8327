"""Executor exit-code semantics: lost-coordinator is distinct from user
failure (round-1 review, weak point 6 — the reference folds both into -1,
TaskExecutor.java:264-268, losing the triage signal)."""

import os
import subprocess
import sys
import threading
import time

import pytest

from tony_tpu import constants
from tony_tpu.rpc.server import ApplicationRpcServer
from tony_tpu.rpc.service import (ApplicationRpc, ApplicationStatus, TaskUrl,
                                  WorkerSpecResponse)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class OneWorkerImpl(ApplicationRpc):
    """Single-worker gang: barrier releases on first registration."""

    def __init__(self):
        self.heartbeats = []
        self.lock = threading.Lock()

    def get_task_urls(self):
        return []

    def get_cluster_spec(self, task_id):
        return '{"worker": ["h0:1"]}'

    def register_worker_spec(self, worker, spec):
        return WorkerSpecResponse(
            spec='{"worker": ["h0:1"]}', coordinator_address="h0:9999",
            process_id=0, num_processes=1, mesh_spec='{"axes": {"dp": 1}}')

    def register_tensorboard_url(self, url):
        return url

    def register_execution_result(self, exit_code, job_name, job_index,
                                  session_id):
        return "RECEIVED"

    def finish_application(self):
        return "SUCCEEDED"

    def task_executor_heartbeat(self, task_id):
        with self.lock:
            self.heartbeats.append(task_id)

    def get_application_status(self):
        return ApplicationStatus(status="RUNNING", session_id=0)


@pytest.mark.e2e
def test_lost_coordinator_exits_distinct_code(tmp_path):
    """A REAL executor process whose coordinator vanishes mid-run must exit
    with EXIT_LOST_COORDINATOR, not a generic failure code."""
    impl = OneWorkerImpl()
    srv = ApplicationRpcServer(impl)
    srv.start()
    conf = tmp_path / "tony-final.xml"
    conf.write_text("")      # kv format: empty + overrides via file
    (tmp_path / "conf.kv").write_text(
        "tony.task.heartbeat-interval-ms=100\n"
        # a short re-attach window: the test is about the EXIT CODE once
        # the window expires, not about riding out a 30s (default) outage
        "tony.coordinator.reattach-timeout-ms=1500\n")
    env = dict(os.environ)
    env.update({
        "JOB_NAME": "worker", "TASK_INDEX": "0", "TASK_NUM": "1",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    proc = subprocess.Popen(
        [sys.executable, "-m", "tony_tpu.cluster.executor",
         "--am_address", f"localhost:{srv.port}",
         "--conf_file", str(tmp_path / "conf.kv"),
         "--task_command", "sleep 60"],
        env=env, cwd=tmp_path,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not impl.heartbeats:
            time.sleep(0.1)
        assert impl.heartbeats, "executor never heartbeat"
        srv.stop(0)          # coordinator vanishes
        out, _ = proc.communicate(timeout=120)
        assert proc.returncode == constants.EXIT_LOST_COORDINATOR, \
            (proc.returncode, out.decode()[-2000:])
        assert b"lost the coordinator" in out
    finally:
        if proc.poll() is None:
            proc.kill()


def test_session_failure_message_distinguishes_lost_coordinator():
    """Session triage: exit 75 is reported as a coordinator-contact loss
    (infra), other codes as user failure — the message lands in
    final-status.json and the history UI."""
    from tony_tpu.cluster.session import Session
    from tony_tpu.conf.config import TonyConfig

    s = Session(TonyConfig({"tony.worker.instances": "2"}))
    s.register_task_spec("worker:0", "h0:1")
    s.on_task_completed("worker", 0, constants.EXIT_LOST_COORDINATOR)
    assert "lost contact with the coordinator" in s.failure_message
    s2 = Session(TonyConfig({"tony.worker.instances": "1"}))
    s2.register_task_spec("worker:0", "h0:1")
    s2.on_task_completed("worker", 0, 1)
    assert "failed with exit code 1" in s2.failure_message
