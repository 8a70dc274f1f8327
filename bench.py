"""Deterministic injected-floor simulations that tier-1 tests drive.

Each ``_*_arm`` below runs a REAL piece of the system (the slice backend
against the fake gcloud, the coordinator's recovery path, the serving
router over simulated or toy-width replicas, the cross-slice pipeline
channel) with an injected latency floor where a cluster or a chip would
put one, and returns a dict a test asserts on: ``tests/`` import this
file as ``bench`` and call the arms by name. Nothing here measures the
chip and no arm runs unless a test calls it. The benchmark is
``benchmark/`` (``BENCHMARK.json``, ``python3 benchmark/run.py``); what
it measured is in ``PERF.md`` and ``PERF_LEDGER.jsonl``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def _launch_arm(num_gangs: int = 4, create_delay_s: float = 0.6,
                scp_delay_s: float = 0.3) -> dict:
    """Job bring-up wall: parallel gang launch + content-addressed staging.

    Drives the REAL TpuSliceBackend against the fake gcloud (tests/
    fake_gcloud.py) with injected per-gang latency D on slice creation
    (plus a smaller scp delay), the hermetic stand-in for the minutes
    real `gcloud create` + scp staging take. Three measurements:

    - cold serial: one launch_task at a time — the pre-change
      schedule_tasks behavior, wall ~= num_gangs * (D + stage);
    - cold parallel: all gangs in flight at once (what the coordinator's
      launch pool now does), wall ~= D + stage — the acceptance bound is
      < 2*D for 4 gangs;
    - warm restart: a FRESH backend over the surviving fleet (the
      coordinator-relaunch case) — create fails fast with ALREADY_EXISTS
      and the slice is adopted, the stage digest probe matches, and ZERO
      tarballs ship (`launch_warm_stage_skip` pins that).

    The deterministic tier-1 / slow test variants live in
    tests/test_launch.py and call this function with scaled delays."""
    import concurrent.futures
    import os
    import shutil
    import sys
    import tempfile

    from tony_tpu.backend.base import LaunchSpec
    from tony_tpu.backend.tpu import TpuSliceBackend
    from tony_tpu.conf.config import TonyConfig

    repo = os.path.dirname(os.path.abspath(__file__))
    fake = os.path.join(repo, "tests", "fake_gcloud.py")
    tmp = tempfile.mkdtemp(prefix="tony-launch-bench-")
    bindir = os.path.join(tmp, "bin")
    os.makedirs(bindir)
    gcloud = os.path.join(bindir, "gcloud")
    with open(gcloud, "w") as f:
        f.write(f"#!/bin/bash\nexec {sys.executable} {fake} \"$@\"\n")
    os.chmod(gcloud, 0o755)
    job_dir = os.path.join(tmp, "job")
    log_dir = os.path.join(job_dir, "logs")
    os.makedirs(log_dir)
    with open(os.path.join(job_dir, "tony-final.xml"), "w") as f:
        f.write("<configuration></configuration>\n")

    saved_env = {k: os.environ.get(k) for k in
                 ("PATH", "FAKE_GCLOUD_ROOT", "FAKE_NUM_WORKERS",
                  "FAKE_DELAY_CREATE_S", "FAKE_DELAY_SCP_S")}
    os.environ["PATH"] = f"{bindir}:{os.environ['PATH']}"
    os.environ["FAKE_NUM_WORKERS"] = "1"
    os.environ["FAKE_DELAY_CREATE_S"] = str(create_delay_s)
    os.environ["FAKE_DELAY_SCP_S"] = str(scp_delay_s)

    conf = TonyConfig({
        "tony.scheduler.backend": "tpu",
        "tony.tpu.project": "bench", "tony.tpu.zone": "z",
        "tony.tpu.accelerator-type": "v5litepod",
        "tony.worker.instances": str(num_gangs),
        "tony.worker.slices": str(num_gangs),
    })

    def specs():
        return [LaunchSpec(task_id=f"worker:{i}", command="true", env={},
                           log_dir=log_dir, cwd=job_dir, tpu_topology="2x4")
                for i in range(num_gangs)]

    def scp_count(fleet):
        path = os.path.join(fleet, "calls.log")
        if not os.path.exists(path):
            return 0
        return sum(1 for line in open(path)
                   if line.split()[3:4] == ["scp"])

    def launch_all(backend, parallel):
        t0 = time.perf_counter()
        if parallel:
            with concurrent.futures.ThreadPoolExecutor(num_gangs) as pool:
                list(pool.map(backend.launch_task, specs()))
        else:
            for s in specs():
                backend.launch_task(s)
        return time.perf_counter() - t0

    try:
        serial_fleet = os.path.join(tmp, "fleet-serial")
        os.makedirs(serial_fleet)
        os.environ["FAKE_GCLOUD_ROOT"] = serial_fleet
        serial_b = TpuSliceBackend(conf, app_id="bench")
        serial_wall = launch_all(serial_b, parallel=False)
        serial_b.stop()

        fleet = os.path.join(tmp, "fleet")
        os.makedirs(fleet)
        os.environ["FAKE_GCLOUD_ROOT"] = fleet
        cold_b = TpuSliceBackend(conf, app_id="bench")
        cold_wall = launch_all(cold_b, parallel=True)
        cold_b.kill_all()            # NOT stop(): the fleet must survive

        # warm restart: a fresh backend (new coordinator attempt) over the
        # surviving fleet
        ships_before = scp_count(fleet)
        warm_b = TpuSliceBackend(conf, app_id="bench")
        warm_wall = launch_all(warm_b, parallel=True)
        warm_ships = scp_count(fleet) - ships_before
        warm_b.stop()
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "launch_gangs": num_gangs,
        "launch_gang_delay_s": create_delay_s,
        "launch_cold_serial_wall_s": round(serial_wall, 2),
        "launch_cold_parallel_wall_s": round(cold_wall, 2),
        # ~num_gangs when bring-up is delay-dominated (the win)
        "launch_cold_wall_vs_serial": round(serial_wall / cold_wall, 2),
        "launch_warm_wall_s": round(warm_wall, 2),
        # 1 = the stamp probe matched on every gang: zero tarball ships
        "launch_warm_stage_skip": int(warm_ships == 0),
        "launch_warm_vs_cold": round(cold_wall / max(warm_wall, 1e-9), 2),
    }


def _elastic_arm(steps: int = 16, step_wait: float = 0.15,
                 kill_at: int = 4, ckpt_every: int = 2) -> dict:
    """Elastic degraded-resume vs stop-the-world session re-run, for the
    SAME injected gang kill.

    Two local-backend jobs (2 workers × 2 gangs) run the jax-free fake
    trainer (tests/fixtures/fake_elastic_trainer.py — fixed step cadence,
    atomic progress checkpoints, marker-gated self-kill at ``kill_at``):

    - **elastic**: tony.elastic.enabled — the lost gang detaches, the
      survivor resyncs over the bumped cluster epoch and resumes from its
      progress file, and the gang regrows in the background;
    - **restart**: the pre-existing behavior — the preemption fails the
      session, everything is killed, and the session re-runs from the
      preemption budget (both workers resume from their progress files).

    Emitted keys: ``elastic_recovery_wall_s`` (jhist ELASTIC_SHRINK →
    ELASTIC_RESUMED), ``elastic_steps_replayed`` /
    ``restart_steps_replayed`` (step lines re-executed after the kill —
    work lost to the recovery strategy), and
    ``elastic_goodput_vs_restart`` (unique-steps-per-wall ratio; > 1
    means the elastic path retained more goodput for the identical kill;
    the gap widens enormously on real TPUs where stop-the-world re-pays
    slice provisioning). The deterministic tier-1 variant lives in
    tests/test_elastic.py."""
    import os
    import re
    import shutil
    import sys
    import tempfile

    from tony_tpu.client.client import TonyClient
    from tony_tpu.conf.config import TonyConfig
    from tony_tpu.events.events import find_job_files, parse_events

    repo = os.path.dirname(os.path.abspath(__file__))
    trainer = os.path.join(repo, "tests", "fixtures",
                           "fake_elastic_trainer.py")
    tmp = tempfile.mkdtemp(prefix="tony-elastic-bench-")

    def run_one(name: str, elastic: bool) -> dict:
        root = os.path.join(tmp, name)
        os.makedirs(root)
        marker = os.path.join(root, "kill.marker")
        cmd = (f"{sys.executable} {trainer} --steps {steps} "
               f"--ckpt {os.path.join(root, 'progress')} "
               f"--ckpt_every {ckpt_every} --step_wait {step_wait} "
               f"--kill {marker}:{kill_at}:1")
        conf = TonyConfig({
            "tony.staging.dir": os.path.join(root, "staging"),
            "tony.history.location": os.path.join(root, "hist"),
            "tony.application.timeout": "120000",
            "tony.worker.instances": "2",
            "tony.worker.slices": "2",
            # fast epoch fan-out so the recovery number measures the
            # machinery, not the default 1s heartbeat cadence
            "tony.task.heartbeat-interval-ms": "250",
            "tony.elastic.enabled": "true" if elastic else "false",
            "tony.elastic.regrow": "true",
            "tony.elastic.regrow-backoff-ms": "300",
        })
        client = TonyClient(conf, cmd, shell_env={
            "TEST_PREEMPT_TASKS": f"worker:1@{marker}",
            "TONY_RESYNC_KILL_GRACE_S": "3",
        })
        t0 = time.perf_counter()
        rc = client.run()
        wall = time.perf_counter() - t0
        assert rc == 0, f"{name} bench job failed"
        total = unique = 0
        log_dir = os.path.join(client.job_dir, "logs")
        for fn in os.listdir(log_dir):
            if fn.startswith("worker-") and fn.endswith(".stdout"):
                found = re.findall(r"^step (\d+)$",
                                   open(os.path.join(log_dir, fn)).read(),
                                   re.M)
                total += len(found)
                unique += len(set(found))
        recovery = None
        events = list(parse_events(find_job_files(
            conf.get("tony.history.location"))[0]))
        shrink = [e.timestamp for e in events
                  if e.event_type == "ELASTIC_SHRINK"]
        resumed = [e for e in events if e.event_type == "ELASTIC_RESUMED"]
        if resumed:
            recovery = resumed[-1].payload.get("recovery_wall_s")
        return {"wall": wall, "replayed": total - unique,
                "unique": unique, "recovery": recovery,
                "shrinks": len(shrink)}

    try:
        el = run_one("elastic", elastic=True)
        rs = run_one("restart", elastic=False)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    assert el["shrinks"] >= 1, "elastic arm never shrank"
    return {
        "elastic_kill_at_step": kill_at,
        "elastic_recovery_wall_s": round(el["recovery"] or 0.0, 3),
        "elastic_wall_s": round(el["wall"], 2),
        "restart_wall_s": round(rs["wall"], 2),
        "elastic_steps_replayed": el["replayed"],
        "restart_steps_replayed": rs["replayed"],
        # unique steps per wall second, elastic vs stop-the-world — the
        # goodput retained for the identical injected kill
        "elastic_goodput_vs_restart": round(
            (el["unique"] / el["wall"]) / (rs["unique"] / rs["wall"]), 2),
    }


def _recovery_arm(steps: int = 36, step_wait: float = 0.25,
                  kill_at: int = 4, ckpt_every: int = 2) -> dict:
    """Coordinator crash recovery (journal re-adoption) vs the cold
    full-job restart the journal-less stack pays for the SAME loss.

    Two local-backend jobs (2 workers) run the jax-free fake trainer
    (tests/fixtures/fake_elastic_trainer.py):

    - **recover**: the chaos path — worker 0 touches a marker at
      ``kill_at``, the backend SIGKILLs the coordinator mid-train, the
      client relaunches it (tony.am.retry-count) on the same job dir,
      and journal replay re-adopts the still-running executors: the
      user processes never stop, zero steps replay, and the headline
      number is the coordinator's own
      ``tony_coordinator_recovery_seconds`` gauge (restart → last
      adopted executor re-attached), read back from the final
      ``METRICS_SNAPSHOT``;
    - **cold**: the pre-journal behavior for the identical loss — the
      whole job is resubmitted and re-runs from the last committed
      checkpoint (a fresh job dir primed with the kill-step progress
      files): full bring-up + every remaining step re-executed +
      teardown.

    Emitted keys: ``coordinator_recovery_wall_s`` (the gauge),
    ``cold_restart_wall_s``, both sides' replayed/re-run step counts,
    and ``recovery_vs_cold_restart`` (cold/recovery, pinned >= 3 —
    slice re-adoption doing the work; the gap widens enormously on real
    TPUs, where the cold path also re-pays minutes of slice
    provisioning while re-adoption pays one probe). The deterministic
    tier-1 chaos variant lives in tests/test_recovery.py."""
    import os
    import re
    import shutil
    import sys
    import tempfile

    from tony_tpu.client.client import TonyClient
    from tony_tpu.cluster import journal as journal_mod
    from tony_tpu.conf.config import TonyConfig
    from tony_tpu.events.events import find_job_files, parse_events

    repo = os.path.dirname(os.path.abspath(__file__))
    trainer = os.path.join(repo, "tests", "fixtures",
                           "fake_elastic_trainer.py")
    tmp = tempfile.mkdtemp(prefix="tony-recovery-bench-")
    workers = 2

    def run_one(name, kill_flags="", extra_conf=None, shell_env=None):
        root = os.path.join(tmp, name)
        os.makedirs(root, exist_ok=True)
        cmd = (f"{sys.executable} {trainer} --steps {steps} "
               f"--ckpt {os.path.join(root, 'progress')} "
               f"--ckpt_every {ckpt_every} --step_wait {step_wait}"
               + (f" {kill_flags}" if kill_flags else ""))
        conf = TonyConfig(dict({
            "tony.staging.dir": os.path.join(root, "staging"),
            "tony.history.location": os.path.join(root, "hist"),
            "tony.application.timeout": "180000",
            "tony.worker.instances": str(workers),
            "tony.task.heartbeat-interval-ms": "250",
            "tony.metrics.snapshot-interval-ms": "1000",
        }, **(extra_conf or {})))
        client = TonyClient(conf, cmd, shell_env=shell_env or {})
        t0 = time.perf_counter()
        rc = client.run()
        wall = time.perf_counter() - t0
        assert rc == 0, f"{name} bench job failed (job dir {client.job_dir})"
        total = unique = 0
        log_dir = os.path.join(client.job_dir, "logs")
        for fn in os.listdir(log_dir):
            if fn.startswith("worker-") and fn.endswith(".stdout"):
                found = re.findall(r"^step (\d+)$",
                                   open(os.path.join(log_dir, fn)).read(),
                                   re.M)
                total += len(found)
                unique += len(set(found))
        return client, wall, total - unique

    # recover: SIGKILL the coordinator once worker 0 starts `kill_at`
    marker = os.path.join(tmp, "recover", "kill.marker")
    os.makedirs(os.path.dirname(marker))
    try:
        client, recover_wall, recover_replayed = run_one(
            "recover", kill_flags=f"--kill {marker}:{kill_at}:0",
            extra_conf={"tony.am.retry-count": "1"},
            shell_env={"TEST_KILL_COORDINATOR": marker})
        assert os.path.exists(marker + ".fired"), "kill hook never fired"
        records = journal_mod.replay(
            journal_mod.journal_path(client.job_dir))
        state = journal_mod.fold(records)
        assert state.incarnation == 2, "coordinator never restarted"
        launches = [r for r in records if r["k"] == "launch"]
        assert len(launches) == workers, "recovery re-provisioned a task"
        # the recovery wall rides am:0 into the restarted generation's
        # final METRICS_SNAPSHOT
        recovery_wall = None
        for f in find_job_files(os.path.join(tmp, "recover", "hist")):
            events = list(parse_events(f))
            if not any(e.event_type == "COORDINATOR_RESTART"
                       for e in events):
                continue
            snaps = [e for e in events
                     if e.event_type == "METRICS_SNAPSHOT"]
            for name, _, value in snaps[-1].payload["tasks"]["am:0"]["g"]:
                if name == "tony_coordinator_recovery_seconds":
                    recovery_wall = value
        assert recovery_wall, "recovery wall gauge never recorded"

        # cold: a fresh submission primed with the kill-step checkpoints
        # — everything re-provisions and every later step re-runs
        primed = (kill_at // ckpt_every) * ckpt_every
        cold_root = os.path.join(tmp, "cold")
        os.makedirs(cold_root)
        for i in range(workers):
            with open(os.path.join(cold_root,
                                   f"progress-worker-{i}"), "w") as f:
                f.write(str(primed))
        _, cold_wall, _ = run_one("cold")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    ratio = cold_wall / max(recovery_wall, 1e-9)
    assert ratio >= 3, (
        f"coordinator recovery ({recovery_wall:.2f}s) not >= 3x better "
        f"than the cold full-job restart ({cold_wall:.2f}s)")
    return {
        "recovery_kill_at_step": kill_at,
        "coordinator_recovery_wall_s": round(recovery_wall, 3),
        # 0: re-adopted trainers never stopped, so nothing re-ran
        "recovery_steps_replayed": recover_replayed,
        "recovery_job_wall_s": round(recover_wall, 2),
        "cold_restart_wall_s": round(cold_wall, 2),
        "cold_restart_steps_rerun": steps - primed,
        "recovery_vs_cold_restart": round(ratio, 2),
    }


def _sched_arm(n_jobs: int = 3, duration_steps: int = 40,
               steps_per_s: float = 1000.0,
               cold_bringup_s: float = 0.30,
               warm_adopt_s: float = 0.02) -> dict:
    """Cluster-daemon warm-pool turnover vs cold sequential bring-up
    (docs/cluster.md §Warm-pool affinity).

    Two identical 3-job back-to-back workloads through a real
    :class:`~tony_tpu.cluster.daemon.ClusterDaemon` (OracleRunner, a
    2-slice pool, every job a 2-slice gang, all submitted at once so
    the pool turns over between them).  WARM: all jobs share one
    staging digest, so jobs 2..n adopt the digest-tagged slices the
    previous job freed (ALREADY_EXISTS warm adoption).  COLD: distinct
    digests — the no-affinity contrast — so every job pays full
    bring-up.  Turnover is the completion-to-completion gap (bring-up +
    run); the bring-up constants are PR 4's measured 9.1s-vs-0.49s
    contrast scaled down to keep the arm under a second.

    Emitted keys: ``sched_warm_turnover_s``, ``sched_cold_turnover_s``,
    ``sched_warm_turnover_vs_cold`` (pinned >= 2 in
    tests/test_cluster.py), ``sched_queue_wait_p99_s`` (bucket-
    interpolated from tony_sched_queue_wait_seconds via
    histogram_quantile), ``sched_warm_hits``."""
    import tempfile

    from tony_tpu.cluster.daemon import ClusterDaemon, OracleRunner
    from tony_tpu.runtime.metrics import MetricsRegistry, \
        histogram_quantile

    def run_arm(warm_affinity: bool) -> tuple[float, MetricsRegistry]:
        registry = MetricsRegistry()
        runner = OracleRunner(cold_bringup_s=cold_bringup_s,
                              warm_adopt_s=warm_adopt_s)
        daemon = ClusterDaemon(
            tempfile.mkdtemp(prefix="tony-sched-bench-"),
            slices=2, runner=runner, registry=registry,
            tick_interval_s=0.005)
        daemon.start()
        try:
            ids = []
            for i in range(n_jobs):
                digest = "bench-dd" if warm_affinity else f"bench-{i}"
                ids.append(daemon.handle_op({
                    "op": "submit", "user": "bench", "slices": 2,
                    "digest": digest,
                    "payload": {"duration_steps": duration_steps,
                                "steps_per_s": steps_per_s}})["job_id"])
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                states = {j["job_id"]: j["state"]
                          for j in daemon.handle_op({"op": "list"})["jobs"]}
                if all(states[i] == "COMPLETED" for i in ids):
                    break
                time.sleep(0.005)
            finished = sorted(daemon.sched.jobs[i].finished_at
                              for i in ids)
            assert all(daemon.sched.jobs[i].state == "COMPLETED"
                       for i in ids), f"bench jobs did not finish: {states}"
            gaps = [b - a for a, b in zip(finished, finished[1:])]
            return sum(gaps) / len(gaps), registry
        finally:
            daemon.stop()

    warm_turnover, registry = run_arm(warm_affinity=True)
    cold_turnover, _ = run_arm(warm_affinity=False)
    hist = registry.histogram("tony_sched_queue_wait_seconds")
    p99 = histogram_quantile(hist, 0.99)
    warm_hits = registry.counter("tony_pool_warm_hits_total").value
    return {
        "sched_warm_turnover_s": round(warm_turnover, 4),
        "sched_cold_turnover_s": round(cold_turnover, 4),
        "sched_warm_turnover_vs_cold": round(
            cold_turnover / max(warm_turnover, 1e-9), 2),
        "sched_queue_wait_p99_s": round(p99, 4),
        "sched_warm_hits": int(warm_hits),
    }


def _streaming_arm(slots: int = 3, n_req: int = 6, prompt_len: int = 8,
                   budget: int = 64, chunk: int = 4,
                   round_trip_s: float = 0.05,
                   fetch_floor_s: float = 0.02) -> dict:
    """Streamed (persistent token-push) serving vs the per-chunk
    request/response wire, under an injected transport round trip D.

    Three runs of the SAME workload, identical tokens asserted across
    all three:

    - **streamed, zero delay**: the floor. ServingServer pushes TOKENS
      frames as each chunk is consumed; client threads drain them off
      one multiplexed connection.
    - **streamed through a LatencyProxy** injecting ``round_trip_s`` of
      round-trip latency: admissions and deltas pipeline through the
      link, so the whole workload pays the round trip ONCE (first admit
      half + last delta half) — wall within ~1.15x of the floor however
      many chunks flow.
    - **request/response baseline**: the same engine driven closed-batch
      and sequentially with the round trip injected INTO the control
      loop — every chunk fetch and every admission wave pays
      ``round_trip_s`` serialized with compute. That is the cost
      model of a client that drives the engine over a network link one
      request/response per sync: wall degrades by ~``(chunks +
      admission waves) x D`` while the streamed wall does not.

    Determinism: a tiny CPU model plus ``fetch_floor_s`` of injected
    per-sync fetch wall standing in for device chunk compute (the
    launch arm's fake-gcloud-delay technique), and a short PLUG request
    submitted first so the engine is provably mid-burst when the real
    admissions arrive — every run executes the same sync schedule, so
    the ratios hold on any rig. ``serving_stream_ttft_s`` is the
    CLIENT-side mean time-to-first-token under the delayed link
    (includes slot-wait for the requests beyond ``slots``). The tier-1
    and @slow test variants (tests/test_serving.py) call this function
    directly."""
    import threading

    import numpy as np

    from tony_tpu.models import transformer as T
    from tony_tpu.models.serve import ContinuousBatcher
    from tony_tpu.runtime import metrics as M
    from tony_tpu.serving.client import StreamingClient
    from tony_tpu.serving.netem import LatencyProxy
    from tony_tpu.serving.server import ServingServer

    cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)

    class FloorFetch(ContinuousBatcher):
        """Injects a fixed per-sync fetch wall: the deterministic
        stand-in for device chunk compute."""

        def _fetch(self, handle):
            if fetch_floor_s > 0:
                time.sleep(fetch_floor_s)
            return super()._fetch(handle)

    class RoundTripFetch(FloorFetch):
        """The request/response wire: a transport round trip serialized
        into every chunk fetch and every admission wave."""

        def _fetch(self, handle):
            time.sleep(round_trip_s)
            return super()._fetch(handle)

        def _admit_batch(self, pairs, prompts):
            time.sleep(round_trip_s)
            return super()._admit_batch(pairs, prompts)

    rs = np.random.RandomState(11)
    prompts = [[int(t) for t in rs.randint(0, cfg.vocab_size,
                                           size=prompt_len)]
               for _ in range(n_req)]
    max_len = prompt_len + budget
    plug_budget = 6 * chunk          # ~6 syncs of cover for admissions
    batcher = FloorFetch(params, cfg, batch=slots, max_len=max_len,
                         chunk=chunk)
    batcher.serve(prompts[:slots], [chunk] * slots)     # compile + warm

    def run_streamed(delay_rt):
        # try/finally over the whole lifecycle: a mid-arm failure must
        # not leak a live engine thread / proxy / client into the
        # calling process (the tier-1 test imports and runs this arm)
        srv = ServingServer(batcher, registry=M.MetricsRegistry())
        proxy = None
        c = None
        try:
            port = srv.start()
            if delay_rt > 0:
                proxy = LatencyProxy("127.0.0.1", port, delay_rt / 2)
                port = proxy.start()
            outs: list = [None] * n_req
            ttfts: list = [0.0] * n_req
            c = StreamingClient("127.0.0.1", port)

            def drain(i, rid, t_submit):
                toks, first = [], None
                for delta in c.deltas(rid):
                    if first is None:
                        first = time.perf_counter()
                    toks.extend(delta)
                outs[i] = toks
                ttfts[i] = (first or time.perf_counter()) - t_submit

            t0 = time.perf_counter()
            # the plug: a short request that keeps the engine mid-burst
            # while the real admissions travel, so they all land in ONE
            # settle — the open-loop schedule becomes deterministic
            plug = c.submit(prompts[0], plug_budget)
            c.next_event(plug, timeout=60)       # its first delta
            threads = []
            for i, p in enumerate(prompts):
                t_submit = time.perf_counter()
                rid = c.submit(p, budget)
                th = threading.Thread(target=drain,
                                      args=(i, rid, t_submit))
                th.start()
                threads.append(th)
            for th in threads:
                th.join()
            wall = time.perf_counter() - t0
            syncs = batcher.phase_times.count("fetch")
            return wall, outs, ttfts, syncs
        finally:
            # closing the client first cancels anything still in
            # flight, so the engine abort below is instant either way
            if c is not None:
                c.close()
            if proxy is not None:
                proxy.stop()
            srv.stop()

    def run_rr():
        tb = RoundTripFetch(params, cfg, batch=slots, max_len=max_len,
                         chunk=chunk, pipeline=False)
        saved = M.set_default(M.MetricsRegistry())
        try:
            tb.serve(prompts[:slots], [chunk] * slots)  # warm (cheap)
            t0 = time.perf_counter()
            outs = tb.serve(prompts, budget)
            wall = time.perf_counter() - t0
        finally:
            M.set_default(saved)
        exchanges = (tb.phase_times.count("fetch")
                     + tb.phase_times.count("admit"))
        return wall, outs, exchanges

    t_s0, outs0, _, syncs0 = run_streamed(0.0)
    t_sd, outs_d, ttfts, syncs_d = run_streamed(round_trip_s)
    t_rr, outs_rr, exchanges = run_rr()
    assert outs0 == outs_d == outs_rr, (
        "transport modes produced different tokens — wire corruption")
    return {
        "serving_stream_round_trip_s": round_trip_s,
        "serving_stream_wall_nodelay_s": round(t_s0, 3),
        "serving_stream_wall_s": round(t_sd, 3),
        # ~1.0-1.15 = the round trip is paid once, pipelined away
        "serving_stream_vs_nodelay": round(t_sd / t_s0, 3),
        "serving_stream_syncs": syncs_d,
        # the plug makes these equal — the determinism guard
        "serving_stream_syncs_nodelay": syncs0,
        "serving_rr_wall_s": round(t_rr, 3),
        "serving_rr_round_trips": exchanges,
        # the tentpole ratio: >= 2 at a 50 ms round trip (tier-1-pinned)
        "serving_stream_vs_rr_wall": round(t_rr / t_sd, 2),
        "serving_stream_ttft_s": round(sum(ttfts) / len(ttfts), 3),
    }


def _disagg_arm(slots: int = 4, n_streams: int = 2, n_admits: int = 6,
                prompt_len: int = 12, stream_budget: int = 60,
                admit_budget: int = 4, chunk: int = 2,
                prefill_floor_s: float = 0.05,
                fetch_floor_s: float = 0.015,
                one_way_s: float = 0.0) -> dict:
    """Disaggregated prefill/decode vs the colocated engine: decode
    inter-token latency under CONCURRENT ADMISSIONS, at equal slot
    count and with token-identical output (asserted).

    The colocated engine interleaves prefill and decode dispatches on
    one device queue: every admission wave's prefill
    (``prefill_floor_s`` — the injected stand-in for real prefill
    compute, tens of ms on hardware) lands between two decode chunks,
    so the live streams' inter-token gap spikes to
    ``fetch_floor_s + prefill_floor_s`` whenever anything is admitted.
    Disaggregated, the SAME floors apply — but prefill burns on the
    prefill gang while the decode gang only scatters the shipped KV
    into a freed slot, so the live streams' p99 gap stays at the
    decode floor. The workload: ``n_streams`` long streams occupy part
    of the slot pool; once all are streaming, ``n_admits`` short
    requests churn through the remaining slots.

    Deterministic: a tiny CPU model plus the injected floors dominate
    scheduling noise; both paths run the same floors, the same ladder,
    and the same greedy workload, and their outputs are asserted
    identical request-for-request. ``one_way_s`` (the @slow variant)
    additionally routes the client connection through a LatencyProxy —
    ITL is produced by push cadence, so an injected WAN hop must not
    change the p99 contrast. ``serving_disagg_handoff_wall_s`` is the
    mean prefill-side KV handoff wall (extract + serialize + channel
    send) off the ``tony_kv_ship_seconds`` histogram — the metrics
    plane's view of the handoff, which the tier-1 test also asserts
    appears in the request trace as the ``kv.ship`` span."""
    import threading

    import numpy as np

    from tony_tpu.models import transformer as T
    from tony_tpu.models.serve import ContinuousBatcher
    from tony_tpu.runtime import metrics as M
    from tony_tpu.serving.client import StreamingClient
    from tony_tpu.serving.disagg import DecodeServer, PrefillServer
    from tony_tpu.serving.netem import LatencyProxy
    from tony_tpu.serving.router import ServingRouter
    from tony_tpu.serving.server import ServingServer

    cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)

    class FloorFetch(ContinuousBatcher):
        """Fixed per-sync fetch wall: the decode-chunk compute floor."""

        def _fetch(self, handle):
            if fetch_floor_s > 0:
                time.sleep(fetch_floor_s)
            return super()._fetch(handle)

    class ColocatedFloor(FloorFetch):
        """Colocated admission pays the prefill floor INSIDE the serve
        loop — the dispatch-interleaving cost disaggregation removes."""

        def _admit_prompts(self, pairs, prompts):
            if prefill_floor_s > 0:
                time.sleep(prefill_floor_s)
            super()._admit_prompts(pairs, prompts)

    class FloorPrefill(PrefillServer):
        """The SAME prefill floor, burned on the prefill gang."""

        def _prefill_group(self, grp, bucket, entry=None):
            if prefill_floor_s > 0:
                time.sleep(prefill_floor_s)
            super()._prefill_group(grp, bucket, entry)

    rs = np.random.RandomState(17)
    stream_prompts = [[int(t) for t in rs.randint(
        0, cfg.vocab_size, size=prompt_len)] for _ in range(n_streams)]
    admit_prompts = [[int(t) for t in rs.randint(
        0, cfg.vocab_size, size=prompt_len)] for _ in range(n_admits)]
    max_len = prompt_len + stream_budget

    def run_workload(port):
        """Streams first (wait until every one delivered a delta —
        measurement starts with the pool provably mid-decode), then the
        admission churn; returns (outputs, long-stream per-token
        gaps)."""
        outs: dict = {}
        gaps: list[float] = []
        with StreamingClient("127.0.0.1", port) as c:
            # warm every program (admit/land bucket, step chunk) so no
            # compile lands inside a measured gap
            toks, _ = c.result(c.submit(stream_prompts[0], admit_budget),
                               timeout=120)
            srids = [c.submit(p, stream_budget) for p in stream_prompts]
            events = {r: c.next_event(r, timeout=120) for r in srids}

            def drain(rid, first_ev):
                toks = list(first_ev[1])
                last = time.perf_counter()
                while True:
                    ev = c.next_event(rid, timeout=120)
                    if ev[0] == "retired":
                        break
                    assert ev[0] == "tokens", ev
                    now = time.perf_counter()
                    gaps.append((now - last) / len(ev[1]))
                    last = now
                    toks.extend(ev[1])
                outs[rid] = toks

            threads = [threading.Thread(target=drain, args=(r, events[r]))
                       for r in srids]
            for th in threads:
                th.start()
            arids = []
            for p in admit_prompts:
                arids.append(c.submit(p, admit_budget))
                time.sleep(2 * fetch_floor_s)   # churn, not one burst
            for r in arids:
                outs[r] = c.result(r, timeout=120)[0]
            for th in threads:
                th.join()
            ordered = ([outs[r] for r in srids]
                       + [outs[r] for r in arids])
        return ordered, gaps

    def p99(xs):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(0.99 * len(xs)))]

    def run_colocated():
        srv = ServingServer(
            ColocatedFloor(params, cfg, batch=slots, max_len=max_len,
                           chunk=chunk),
            registry=M.MetricsRegistry())
        proxy = None
        try:
            port = srv.start()
            if one_way_s > 0:
                proxy = LatencyProxy("127.0.0.1", port, one_way_s)
                port = proxy.start()
            return run_workload(port)
        finally:
            if proxy is not None:
                proxy.stop()
            srv.stop()

    def run_disagg():
        regp = M.MetricsRegistry()
        pre = FloorPrefill(params, cfg, max_len=max_len,
                           max_batch=slots, registry=regp)
        dec = DecodeServer(
            FloorFetch(params, cfg, batch=slots, max_len=max_len,
                       chunk=chunk),
            registry=M.MetricsRegistry())
        router = ServingRouter([f"127.0.0.1:{pre.start()}"],
                               decode_replicas=[f"127.0.0.1:{dec.start()}"],
                               registry=M.MetricsRegistry())
        proxy = None
        try:
            port = router.start()
            if one_way_s > 0:
                proxy = LatencyProxy("127.0.0.1", port, one_way_s)
                port = proxy.start()
            outs, gaps = run_workload(port)
            ship = regp.histogram("tony_kv_ship_seconds")
            assert ship.count > 0, \
                "kv handoff wall missing from the metrics plane"
            return outs, gaps, ship.sum / ship.count, ship.count
        finally:
            if proxy is not None:
                proxy.stop()
            router.stop()
            pre.stop()
            dec.stop()

    outs_colo, gaps_colo = run_colocated()
    outs_dis, gaps_dis, handoff_wall, handoffs = run_disagg()
    assert outs_colo == outs_dis, (
        "disaggregated serving diverged from the colocated engine — "
        "KV shipment corruption")
    itl_colo, itl_dis = p99(gaps_colo), p99(gaps_dis)
    return {
        "serving_disagg_prefill_floor_s": prefill_floor_s,
        "serving_disagg_fetch_floor_s": fetch_floor_s,
        "serving_colocated_itl_p99_s": round(itl_colo, 4),
        "serving_disagg_itl_p99_s": round(itl_dis, 4),
        # the tentpole ratio: admissions stall colocated decode chunks
        # by the prefill floor; disaggregated decode never sees it
        # (>= 2 tier-1-pinned)
        "serving_disagg_itl_p99_vs_colocated": round(
            itl_colo / max(itl_dis, 1e-9), 2),
        "serving_disagg_handoff_wall_s": round(handoff_wall, 4),
        "serving_disagg_handoffs": handoffs,
    }


def _fleet_arm(n_replicas: int = 4, n_streams: int = 8,
               max_new: int = 80, itl_s: float = 0.003) -> dict:
    """Planned drain under live load, on the simulated fleet: SimFleet
    stands up ``n_replicas`` oracle-token replicas behind a real
    router, ``n_streams`` sessions stream concurrently, and the most-
    loaded replica is drained mid-stream. Every session's final token
    list is compared against the ``sim_token`` oracle — dup/drop
    during migration shows up as a positional mismatch, so
    ``serving_migration_token_gap`` is an exact count, pinned == 0 by
    tier-1 (tests/test_fleet.py). ``serving_drain_wall_s`` is the
    wall from fence to last migrated ACK: with migration implemented
    as re-prefill-on-survivor it is bounded by placement latency, not
    by any session's remaining stream length."""
    import threading

    from tony_tpu.runtime.metrics import MetricsRegistry
    from tony_tpu.serving.client import StreamingClient
    from tony_tpu.serving.simfleet import SimFleet, sim_token

    reg = MetricsRegistry()
    fleet = SimFleet(n_replicas, itl_s=itl_s, slots=16, registry=reg)
    outs: dict = {}

    def pump(client, rid):
        toks = []
        for delta in client.deltas(rid):
            toks.extend(delta)
        outs[rid] = toks

    try:
        port = fleet.start()
        with StreamingClient("127.0.0.1", port) as client:
            seeds = {}
            threads = []
            for i in range(n_streams):
                seed = 1000 + 17 * i
                rid = client.submit([seed, 1, 2, 3], max_new)
                seeds[rid] = seed
                t = threading.Thread(target=pump, args=(client, rid),
                                     daemon=True)
                t.start()
                threads.append(t)
            # let every stream get past first tokens so the drain
            # migrates genuinely mid-flight sessions
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                reps = client.stats()["replicas"]
                if all(s["assigned"] > 0 for s in reps.values()):
                    break
                time.sleep(0.01)
            victim = max(reps, key=lambda a: reps[a]["assigned"])
            res = client.drain_replica(victim)
            assert res.get("drained"), f"drain failed: {res}"
            for t in threads:
                t.join(timeout=60)
            gap = 0
            for rid, toks in outs.items():
                oracle = [sim_token(seeds[rid], p) for p in range(max_new)]
                gap += abs(len(toks) - max_new)
                gap += sum(1 for a, b in zip(toks, oracle) if a != b)
    finally:
        fleet.stop()
    return {
        "serving_fleet_replicas": n_replicas,
        "serving_fleet_streams": n_streams,
        "serving_drain_wall_s": round(res["wall_s"], 4),
        "serving_drain_migrated": res["migrated"],
        # dup/drop token count across every migrated session vs the
        # oracle (== 0 tier-1-pinned)
        "serving_migration_token_gap": gap,
    }


def _qos_arm(n_replicas: int = 1, slots: int = 4, itl_s: float = 0.004,
             ttft_s: float = 0.01, max_new: int = 16, n_req: int = 36,
             one_way_s: float = 0.0) -> dict:
    """SLO-tiered serving under 2x overload, on the simulated fleet.

    An open-loop mixed workload (1 interactive : 1 standard : 2 batch)
    arrives at twice the fleet's service rate — open-loop, so the
    backlog genuinely builds instead of the clients self-throttling.
    Run once with classes (interactive admissions jump the queue and
    preempt decoding batch rows) and once classless (identical
    arrivals, FIFO service): the interactive p99 TTFT ratio between
    the two runs is the tentpole number,
    ``serving_qos_interactive_ttft_p99_vs_classless`` (>= 2
    tier-1-pinned, tests/test_qos.py). Every preempted batch row is
    evicted-to-queue and later resumes via rng-offset re-prefill, so
    comparing every completed stream against the ``sim_token`` oracle
    makes ``serving_qos_preempt_token_gap`` an exact dup/drop count
    (== 0 tier-1-pinned). TTFT p99s come from
    ``histogram_quantile`` over fine-bucket local histograms — the
    same estimator the dashboards use. ``one_way_s`` (the @slow
    variant) pushes the whole workload through a LatencyProxy WAN
    hop: priority is a queue-order property, so the ratio must
    survive transport latency."""
    from tony_tpu.runtime.metrics import (MetricsRegistry,
                                          histogram_quantile)
    from tony_tpu.serving.netem import LatencyProxy
    from tony_tpu.serving.simfleet import (SimFleet, open_loop_load,
                                           sim_token)

    # 2x overload: one arrival every half mean per-request service time
    interval_s = (itl_s * max_new) / (slots * n_replicas) / 2.0
    mix = [("interactive", "standard", "batch", "batch")[i % 4]
           for i in range(n_req)]

    def run(classes):
        fleet = SimFleet(n_replicas, itl_s=itl_s, ttft_s=ttft_s,
                         slots=slots, max_queue_depth=10 * n_req,
                         registry=MetricsRegistry())
        proxy = None
        try:
            port = fleet.start()
            if one_way_s > 0:
                proxy = LatencyProxy("127.0.0.1", port, one_way_s)
                port = proxy.start()
            recs = open_loop_load(port, classes, interval_s=interval_s,
                                  max_new=max_new)
            preempts = sum(r.preemptions
                           for r in fleet.replicas.values())
        finally:
            if proxy is not None:
                proxy.stop()
            fleet.stop()
        return recs, preempts

    classed, preempts = run(mix)
    classless, _ = run([""] * n_req)

    def p99(recs, idxs):
        reg = MetricsRegistry()
        hist = reg.histogram(
            "tony_bench_qos_ttft_seconds",
            help="client-side TTFT samples for the qos arm",
            buckets=tuple(0.002 * i for i in range(1, 400)))
        for i in idxs:
            if recs[i]["ttft_s"] is not None:
                hist.observe(recs[i]["ttft_s"])
        return histogram_quantile(hist, 0.99)

    inter_idx = [i for i, c in enumerate(mix) if c == "interactive"]
    classed_p99 = p99(classed, inter_idx)
    # the SAME arrival positions in the classless run: any difference
    # is the scheduling discipline, not the arrival pattern
    classless_p99 = p99(classless, inter_idx)
    gap = 0
    for i, r in enumerate(classed):
        if r["shed"]:
            continue
        want = [sim_token(1000 + i, p) for p in range(max_new)]
        gap += abs(len(r["tokens"]) - max_new)
        gap += sum(1 for a, b in zip(r["tokens"], want) if a != b)
    return {
        "serving_qos_requests": n_req,
        "serving_qos_preemptions": preempts,
        "serving_qos_interactive_ttft_p99_s": round(classed_p99, 4),
        "serving_qos_classless_ttft_p99_s": round(classless_p99, 4),
        # classed interactive p99 holds under 2x overload while the
        # classless baseline blows through it (>= 2 tier-1-pinned)
        "serving_qos_interactive_ttft_p99_vs_classless": round(
            classless_p99 / max(classed_p99, 1e-9), 2),
        # dup/drop token count across every preemption eviction vs the
        # oracle (== 0 tier-1-pinned)
        "serving_qos_preempt_token_gap": gap,
    }


def _weight_ship_arm(n_replicas: int = 8, mb: int = 8,
                     load_s: float = 0.5, trace_s: float = 0.25,
                     ship_s: float = 0.05) -> dict:
    """Warm scale-up vs cold start, two measurements:

    1. One replica's time-to-serving: a REAL chunked weight ship over a
       localhost channel (pack -> send_bytes -> digest-verified land of
       an ``mb``-megabyte artifact) vs the cold path's injected
       storage-load + XLA-trace floors (a warmed replica lands
       pre-traced via the shipped compile cache, so it pays neither).
       Tier-1 pins ``serving_scaleup_warm_vs_cold >= 2``
       (tests/test_weightstore.py).
    2. The 8-replica rolling-upgrade wall on the simulated fleet: the
       warmer spends ONE storage load to mint a seed, then fans out in
       O(log N) ship waves (wave count pinned == 1 + ceil(log2 N)),
       vs the old path's N serial storage loads."""
    import math

    import numpy as np

    from tony_tpu.channels.channel import ChannelHub, ChannelSender
    from tony_tpu.runtime.metrics import MetricsRegistry
    from tony_tpu.serving.simfleet import SimFleet, SimProvider, SimWarmer
    from tony_tpu.serving.weightstore import (WEIGHT_CHANNEL, pack_weights,
                                              tree_digest, unpack_weights)
    from tony_tpu.serving.fleet import FleetController

    # -- 1. one replica: real ship vs injected cold floors -------------------
    rng = np.random.RandomState(7)
    params = {"layer": {"w": rng.randn(mb * 262144).astype(np.float32),
                        "b": rng.randn(256).astype(np.float32)}}
    blob = pack_weights(params, version="bench")
    reg = MetricsRegistry()
    hub = ChannelHub(registry=reg)
    port = hub.start()
    recv = hub.receiver(WEIGHT_CHANNEL)
    try:
        sender = ChannelSender(f"127.0.0.1:{port}", WEIGHT_CHANNEL,
                               window=8, registry=reg)
        t0 = time.monotonic()
        sender.send_bytes(blob, sync=True, timeout=60)
        landed = recv.recv_bytes(timeout=60)
        meta, got = unpack_weights(landed)     # digest-verified landing
        warm_s = time.monotonic() - t0
        sender.close()
        assert tree_digest(got) == meta["digest"]
    finally:
        hub.stop()
    cold_s = load_s + trace_s                  # injected cold-start floors

    # -- 2. rolling upgrade: one seed + fan-out vs N serial loads ------------
    fleet = SimFleet(n_replicas, itl_s=0.002, slots=4,
                     weights_version="v-old", registry=MetricsRegistry())
    try:
        fleet.start()
        warmer = SimWarmer(fleet, "v-new", ship_s=ship_s, load_s=load_s)
        provider = SimProvider(fleet, weights_version=None)
        ctrl = FleetController(fleet.router, provider,
                               registry=MetricsRegistry(), warmer=warmer)
        new_addrs = [fleet.spawn(weights_version=None)
                     for _ in range(n_replicas)]
        t0 = time.monotonic()
        results = ctrl.rolling_upgrade(new_addrs)
        upgrade_wall = time.monotonic() - t0
        assert all(r.get("drained") for r in results.values()), results
        warm = ctrl.last_warm
        assert warm is not None and not warm["failed"], warm
        # O(log N) fan-out: 1 fallback wave mints the seed, then the
        # seeder pool doubles every ship wave
        assert warm["waves"] == 1 + math.ceil(math.log2(n_replicas)), warm
        assert warmer.loads == 1, warmer.loads
    finally:
        fleet.stop()
    serial_wall = n_replicas * load_s          # old path: N storage loads

    return {
        "serving_scaleup_to_first_token_s": round(warm_s, 4),
        "serving_scaleup_storage_load_s": round(cold_s, 4),
        # warm replica ready-to-serve speedup over cold start (pinned
        # >= 2 tier-1)
        "serving_scaleup_warm_vs_cold": round(cold_s / warm_s, 2),
        "serving_weight_ship_bytes": len(blob),
        "serving_upgrade_wall_s": round(upgrade_wall, 4),
        # one-seed + O(log N) fan-out vs N serial storage loads
        # (pinned > 1 tier-1)
        "serving_upgrade_wall_vs_serial_loads": round(
            serial_wall / upgrade_wall, 2),
        "serving_warm_waves": warm["waves"],
        "serving_warm_storage_loads": warmer.loads,
    }


def _prefix_arm(slots: int = 2, n_req: int = 8, prefix_len: int = 40,
                suffix_len: int = 8, budget: int = 4, chunk: int = 2,
                prefill_s_per_token: float = 0.002,
                fetch_floor_s: float = 0.01,
                one_way_s: float = 0.0) -> dict:
    """Prefix-aware routing + shared prefix tier vs prefix-blind
    placement, at ``n_req``x reuse of one shared prefix: time-to-first-
    token and prefill compute (forward tokens — the FLOPs proxy) across
    a 2-replica fleet behind the router, with token-identical output
    asserted between the two placements.

    Deterministic: a tiny CPU model plus injected floors — a prefill
    floor of ``prefill_s_per_token`` per token RUN THROUGH A FORWARD
    (so a prefix-hit admission's floor is O(suffix) while a blind
    admission's is O(prefix+suffix), exactly the compute shape on
    hardware) and a fixed per-sync fetch floor; a warm-up round
    compiles every program before anything is measured. The AWARE arm
    is the full tentpole path: the prefix is registered with the
    router, computed ONCE on replica A (``install``), and replica B
    warms in ONE template ship (``publish`` — zero prefix forwards on
    B, asserted); every session then admits only its suffix. The BLIND
    arm runs the same fleet with no prefix anywhere — every admission
    pays the full prefill floor. ``one_way_s`` (the @slow variant)
    routes the client through a LatencyProxy — the TTFT contrast is
    produced by admission compute, so a WAN hop shifts both arms
    equally."""
    import threading

    import numpy as np

    from tony_tpu.models import transformer as T
    from tony_tpu.models.serve import ContinuousBatcher
    from tony_tpu.runtime import metrics as M
    from tony_tpu.serving.client import StreamingClient
    from tony_tpu.serving.netem import LatencyProxy
    from tony_tpu.serving.router import ServingRouter
    from tony_tpu.serving.server import ServingServer

    cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32, remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)

    class FloorBatcher(ContinuousBatcher):
        """Prefill floor proportional to tokens actually run through a
        forward (the host-side accounting the engine also folds into
        the metrics plane), plus a fixed per-sync fetch floor."""

        def _admit_prompts(self, pairs, prompts):
            before = self.prefill_forward_tokens
            super()._admit_prompts(pairs, prompts)
            time.sleep(prefill_s_per_token
                       * (self.prefill_forward_tokens - before))

        def _fetch(self, handle):
            if fetch_floor_s > 0:
                time.sleep(fetch_floor_s)
            return super()._fetch(handle)

    rs = np.random.RandomState(23)
    prefix = [int(t) for t in rs.randint(0, cfg.vocab_size,
                                         size=prefix_len)]
    prompts = [prefix + [int(t) for t in rs.randint(
        0, cfg.vocab_size, size=suffix_len)] for _ in range(n_req)]
    max_len = prefix_len + suffix_len + budget

    def run(aware: bool):
        regr = M.MetricsRegistry()
        batchers = [FloorBatcher(params, cfg, batch=slots,
                                 max_len=max_len, chunk=chunk)
                    for _ in range(2)]
        servers = [ServingServer(b, registry=M.MetricsRegistry())
                   for b in batchers]
        router = None
        proxy = None
        c = None
        try:
            addrs = [f"127.0.0.1:{s.start()}" for s in servers]
            pid = None
            if aware:
                # the tentpole path: compute ONCE on A, warm B in one
                # template ship, register with the router
                pid = servers[0].install_prefix(prefix, prefix_id="sys")
                ship_bytes = servers[0].publish_prefix(
                    pid, f"127.0.0.1:{servers[1].prefix_port}")
                deadline = time.time() + 10
                while (pid not in batchers[1].resident_prefixes()
                       and time.time() < deadline):
                    time.sleep(0.02)
                assert pid in batchers[1].resident_prefixes(), \
                    "template ship did not land"
            else:
                ship_bytes = 0
            router = ServingRouter(addrs, registry=regr,
                                   health_interval_s=0.1)
            if aware:
                router.register_prefix(prefix, prefix_id=pid)
            port = router.start()
            if one_way_s > 0:
                proxy = LatencyProxy("127.0.0.1", port, one_way_s)
                port = proxy.start()
            c = StreamingClient("127.0.0.1", port)
            # warm round: compile every admission/step program on both
            # replicas before anything is measured
            for p in prompts:
                c.result(c.submit(p, budget), timeout=120)
            fwd0 = sum(b.prefill_forward_tokens for b in batchers)
            outs: list = [None] * n_req
            ttfts: list = [0.0] * n_req

            def drain(i, rid, t_submit):
                toks, first = [], None
                for delta in c.deltas(rid, timeout=120):
                    if first is None:
                        first = time.perf_counter()
                    toks.extend(delta)
                outs[i] = toks
                ttfts[i] = (first or time.perf_counter()) - t_submit

            threads = []
            for i, p in enumerate(prompts):
                rid = c.submit(p, budget)
                th = threading.Thread(target=drain,
                                      args=(i, rid, time.perf_counter()))
                th.start()
                threads.append(th)
            for th in threads:
                th.join()
            fwd = sum(b.prefill_forward_tokens for b in batchers) - fwd0
            hits = regr.counter("tony_router_prefix_hits_total").value
            misses = regr.counter(
                "tony_router_prefix_misses_total").value
            if aware:
                # B warmed by the SHIP: every B admission was a hit, so
                # its lifetime forward tokens are suffixes only — zero
                # prefill forwards for the shipped prefix
                assert batchers[1].prefill_forward_tokens == \
                    suffix_len * batchers[1].prefix_admits, \
                    "cold replica ran a prefix forward despite the ship"
            return (outs, sum(ttfts) / len(ttfts), fwd, hits, misses,
                    ship_bytes)
        finally:
            if c is not None:
                c.close()
            if proxy is not None:
                proxy.stop()
            if router is not None:
                router.stop()
            for s in servers:
                s.stop()

    outs_blind, ttft_blind, fwd_blind, _, _, _ = run(aware=False)
    outs_aware, ttft_aware, fwd_aware, hits, misses, ship_bytes = run(
        aware=True)
    assert outs_blind == outs_aware, (
        "prefix-aware serving diverged from prefix-blind — template "
        "corruption")
    return {
        "serving_prefix_reuse": n_req,
        "serving_prefix_prefill_s_per_token": prefill_s_per_token,
        "serving_prefix_ttft_blind_s": round(ttft_blind, 4),
        "serving_prefix_ttft_aware_s": round(ttft_aware, 4),
        # the tentpole ratio: at >=8x reuse, placing sessions where the
        # prefix KV lives cuts TTFT by the prefill share the suffix
        # no longer pays (>= 2 tier-1-pinned)
        "serving_prefix_ttft_vs_blind": round(
            ttft_blind / max(ttft_aware, 1e-9), 2),
        # the FLOPs story: forward tokens in the measured round
        "serving_prefix_forward_tokens_blind": int(fwd_blind),
        "serving_prefix_forward_tokens_aware": int(fwd_aware),
        "serving_prefix_forward_vs_blind": round(
            fwd_blind / max(fwd_aware, 1), 2),
        # every prefix session landed on a resident replica
        "serving_prefix_hit_rate": round(
            hits / max(hits + misses, 1), 3),
        "serving_prefix_ship_bytes": int(ship_bytes),
    }




def _pipeline_arm(num_microbatches: int = 8, one_way_s: float = 0.05,
                  fwd_floor_s: float = 0.015, bwd_floor_s: float = 0.03,
                  dim: int = 8, mb_rows: int = 4,
                  window: int = 10, lookahead: int = 5) -> dict:
    """Cross-slice 1F1B over DCN: overlapped vs serialized stage
    execution, deterministically.

    Two in-process stage "gangs" (threads) train one 2-stage model over
    REAL loopback tensor channels, each hub fronted by a LatencyProxy
    injecting ``one_way_s`` of one-way link latency (RT = 2x) — the
    netem technique of the streaming arm, modeling DCN links, not
    serialization. Device compute is a fixed per-microbatch floor
    injected AROUND the (tiny) jitted stage programs, so both runs
    execute the identical schedule on any rig:

    - **overlapped**: channel sends enqueue into the bounded window and
      return; transport of microbatch m±1 rides the wire while m
      computes. ``lookahead`` extra in-flight microbatches keep the
      steady-state loop (2 one-way hops + both stages' compute) full —
      Little's law: in-flight must exceed cycle/compute for throughput
      to be compute-bound, the MPMD-paper latency-tolerance knob. Wall
      ~ pipeline fill + M x max-stage-compute.
    - **serialized**: every send blocks until the peer's ack
      (``sync_transport=True``) — each activation/cotangent hop pays
      the full round trip serialized with compute, the cost model of
      stage execution WITHOUT a framework transport primitive.

    Loss and both stages' grads are asserted identical across the two
    runs (the schedule changes walls, never math). Emits
    ``pipeline_overlap_vs_serialized_wall`` (the tentpole ratio,
    tier-1-pinned >= 1.5) and ``pipeline_bubble_fraction`` (stage 0's
    1 - busy/wall under the overlapped run). The latency-realistic
    variant (tests/test_channels.py @slow) raises the delay and drops
    the floors."""
    import threading

    import numpy as np

    from tony_tpu.channels import open_local_pipeline
    from tony_tpu.parallel.pipeline import CrossSlicePipeline
    from tony_tpu.runtime import metrics as M
    from tony_tpu.serving.netem import LatencyProxy

    rs = np.random.RandomState(7)

    def stage_fn(p, x):
        return x + jnp.tanh(x @ p["w"] + p["b"])

    def loss_head(hp, out, tgt):
        return jnp.mean((out @ hp["wo"] - tgt) ** 2)

    p0 = {"w": jnp.asarray(rs.randn(dim, dim).astype(np.float32) * 0.3),
          "b": jnp.asarray(rs.randn(dim).astype(np.float32) * 0.1)}
    p1 = {"w": jnp.asarray(rs.randn(dim, dim).astype(np.float32) * 0.3),
          "b": jnp.asarray(rs.randn(dim).astype(np.float32) * 0.1)}
    head = {"wo": jnp.asarray(rs.randn(dim, dim).astype(np.float32) * 0.2)}
    m = num_microbatches
    xs = jnp.asarray(rs.randn(m, mb_rows, dim).astype(np.float32))
    tgts = jnp.asarray(rs.randn(m, mb_rows, dim).astype(np.float32))

    class FloorPipeline(CrossSlicePipeline):
        """Fixed per-microbatch device-compute floors: the deterministic
        stand-in for real stage compute (same technique as the
        streaming arm's FloorFetch)."""

        def _forward_compute(self, params, x):
            out = super()._forward_compute(params, x)
            jax.block_until_ready(out)
            time.sleep(fwd_floor_s)
            return out

        def _backward_compute(self, params, saved, cot):
            out = super()._backward_compute(params, saved, cot)
            jax.block_until_ready(out)
            time.sleep(bwd_floor_s)
            return out

        def _last_compute(self, params, head_params, saved, head_mb):
            out = super()._last_compute(params, head_params, saved,
                                        head_mb)
            jax.block_until_ready(out)
            time.sleep(fwd_floor_s + bwd_floor_s)
            return out

    def run_mode(sync: bool):
        reg = M.MetricsRegistry()
        proxies: list[LatencyProxy] = []

        def endpoint_map(stage_idx: int, port: int) -> str:
            proxy = LatencyProxy("127.0.0.1", port, one_way_s)
            proxies.append(proxy)
            return f"127.0.0.1:{proxy.start()}"

        links = open_local_pipeline(2, window=window, registry=reg,
                                    endpoint_map=endpoint_map)
        out: dict = {}
        try:
            pls = [
                FloorPipeline(stage_fn, links[0], registry=reg,
                              lookahead=lookahead, sync_transport=sync),
                FloorPipeline(stage_fn, links[1], loss_head=loss_head,
                              registry=reg, lookahead=lookahead,
                              sync_transport=sync),
            ]

            def run0():
                out[0] = pls[0].value_and_grad(
                    p0, num_microbatches=m, microbatches=xs)

            def run1():
                out[1] = pls[1].value_and_grad(
                    p1, num_microbatches=m, head_params=head,
                    head_batches=tgts)

            def one_round():
                ts = [threading.Thread(target=run0),
                      threading.Thread(target=run1)]
                t0 = time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=120)
                return time.perf_counter() - t0

            one_round()                     # compile + connect warmup
            wall = one_round()
            bubble = reg.gauge("tony_pipeline_bubble_fraction",
                               stage="0").value
            return wall, out, bubble, reg
        finally:
            for link in links:
                link.close()
            for proxy in proxies:
                proxy.stop()

    wall_ov, out_ov, bubble, reg_ov = run_mode(sync=False)
    wall_sr, out_sr, _, _ = run_mode(sync=True)

    def flat(res):
        loss = res[1][0]
        return ([np.asarray(loss)]
                + [np.asarray(v) for v in jax.tree.leaves(res[0][1])]
                + [np.asarray(v) for v in jax.tree.leaves(res[1][1])])

    for a, b in zip(flat(out_ov), flat(out_sr)):
        assert np.array_equal(a, b), \
            "overlapped vs serialized produced different math"
    # channel walls + queue depths must be VISIBLE on the metrics plane
    wire = reg_ov.to_wire()
    series = {name for name, _, _ in wire["h"]} \
        | {name for name, _, _ in wire["g"]}
    assert {"tony_channel_send_seconds", "tony_channel_recv_wait_seconds",
            "tony_channel_send_queue_depth",
            "tony_pipeline_step_seconds"} <= series, series
    return {
        "pipeline_one_way_delay_s": one_way_s,
        "pipeline_microbatches": m,
        "pipeline_overlap_wall_s": round(wall_ov, 3),
        "pipeline_serialized_wall_s": round(wall_sr, 3),
        # the tentpole ratio: DCN round trips overlapped under compute
        "pipeline_overlap_vs_serialized_wall": round(wall_sr / wall_ov, 2),
        "pipeline_bubble_fraction": round(float(bubble), 3),
    }


def _pipeline_dcn_arm(num_microbatches: int = 24, one_way_s: float = 0.05,
                      fwd_floor_s: float = 0.02,
                      bwd_floor_s: float = 0.04,
                      bytes_dim: int = 256, bytes_rows: int = 8,
                      dim: int = 8, mb_rows: int = 4,
                      window: int = 16) -> dict:
    """DCN bytes as a resource: wire compression + interleaved 1F1B.

    Two deterministic sub-arms, both over REAL loopback channels:

    - **bytes-on-wire**: one 2-stage int8-codec training step with
      dim-256 activations; ratio = logical (decoded) send bytes /
      encoded wire bytes, both straight off the channel counters
      (``tony_channel_bytes_total`` vs the codec-only
      ``tony_channel_compressed_bytes_total``). The header is a fixed
      ~100B JSON cost per frame, so the ratio approaches the dtype
      ratio (4x for f32→int8) as tensors grow — at dim 256 it sits
      ~3.9x, tier-1-pinned >= 1.9x.
    - **interleaved vs flat wall**: the SAME 4-block model placed two
      ways across 2 gangs under ``one_way_s`` injected latency
      (LatencyProxy) and fixed per-block compute floors. Flat: gang s
      runs blocks 2s,2s+1 as one stage (one virtual stage per gang,
      in-flight = S). Interleaved (v=2): gang s runs blocks s, s+2 as
      two chunks (looping placement, in-flight = S*v). Little's law:
      steady per-mb rate ≈ max(per-gang compute, cycle/in-flight)
      while latency-bound, where cycle = total compute C + hop
      latencies — flat (0.24+2h)/2 = 0.17 s/mb vs interleaved
      (0.24+6h)/4 = 0.135 s/mb at h = 0.05. C sits a notch below the
      6h crossover so the interleaved rate keeps slack over its 0.12
      s/mb compute floor (thread-scheduling overhead lands in that
      slack, not on the wall); the interleaved fill is ~0.3s longer
      (3 act hops vs 1). Each placement is timed at TWO microbatch
      counts (M and M/3): the marginal rate (wall_big - wall_small)
      / (M - M/3) cancels the fill term exactly, giving the
      steady-state per-mb wall — measured ~1.13x flat/interleaved,
      and stable under load because host jitter inflates both
      placements' rates together. The absolute M-microbatch walls are
      also reported (measured ~1.03-1.07x, fill drag included).
      Losses agree across modes (allclose, not bit-equal: jit
      granularity differs; the BIT pin lives in tests against the
      in-slice V-stage schedule).

    Emits ``pipeline_bytes_on_wire_vs_raw``,
    ``pipeline_interleaved_vs_flat_steady_rate`` and
    ``pipeline_interleaved_vs_flat_wall`` (all tier-1-pinned)."""
    import threading

    import numpy as np

    from tony_tpu.channels import open_local_pipeline
    from tony_tpu.parallel.pipeline import CrossSlicePipeline
    from tony_tpu.runtime import metrics as M
    from tony_tpu.serving.netem import LatencyProxy

    rs = np.random.RandomState(11)
    m = num_microbatches

    def block_fn(p, x):
        return x + jnp.tanh(x @ p["w"] + p["b"])

    def loss_head(hp, out, tgt):
        return jnp.mean((out @ hp["wo"] - tgt) ** 2)

    def mk_block(d):
        return {"w": jnp.asarray(rs.randn(d, d).astype(np.float32) * 0.3),
                "b": jnp.asarray(rs.randn(d).astype(np.float32) * 0.1)}

    # -- sub-arm 1: bytes on the wire under int8 ------------------------
    def run_bytes():
        reg = M.MetricsRegistry()
        links = open_local_pipeline(2, window=window, registry=reg,
                                    compression="int8")
        blocks = [mk_block(bytes_dim) for _ in range(2)]
        head = {"wo": jnp.asarray(
            rs.randn(bytes_dim, bytes_dim).astype(np.float32) * 0.2)}
        xs = jnp.asarray(
            rs.randn(4, bytes_rows, bytes_dim).astype(np.float32))
        tgts = jnp.asarray(
            rs.randn(4, bytes_rows, bytes_dim).astype(np.float32))
        res: dict = {}
        try:
            pls = [CrossSlicePipeline(block_fn, links[0], registry=reg),
                   CrossSlicePipeline(block_fn, links[1],
                                      loss_head=loss_head, registry=reg)]
            ts = [threading.Thread(target=lambda: res.update(
                      a=pls[0].value_and_grad(blocks[0],
                                              num_microbatches=4,
                                              microbatches=xs))),
                  threading.Thread(target=lambda: res.update(
                      b=pls[1].value_and_grad(blocks[1],
                                              num_microbatches=4,
                                              head_params=head,
                                              head_batches=tgts)))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120)
            assert "a" in res and "b" in res
        finally:
            for link in links:
                link.close()
        wire = reg.to_wire()
        logical = sum(v for n, lb, v in wire["c"]
                      if n == "tony_channel_bytes_total"
                      and lb.get("direction") == "send")
        encoded = sum(v for n, lb, v in wire["c"]
                      if n == "tony_channel_compressed_bytes_total"
                      and lb.get("direction") == "send")
        # the codec-only series must be VISIBLE on the metrics plane
        assert encoded > 0, "tony_channel_compressed_bytes_total missing"
        return logical / encoded

    bytes_ratio = run_bytes()

    # -- sub-arm 2: interleaved (v=2) vs flat placement under latency ---
    blocks = [mk_block(dim) for _ in range(4)]
    head = {"wo": jnp.asarray(rs.randn(dim, dim).astype(np.float32) * 0.2)}
    xs = jnp.asarray(rs.randn(m, mb_rows, dim).astype(np.float32))
    tgts = jnp.asarray(rs.randn(m, mb_rows, dim).astype(np.float32))

    def make_floor(fwd_s, bwd_s):
        class FloorPipeline(CrossSlicePipeline):
            def _forward_compute(self, params, x):
                out = super()._forward_compute(params, x)
                jax.block_until_ready(out)
                time.sleep(fwd_s)
                return out

            def _backward_compute(self, params, saved, cot):
                out = super()._backward_compute(params, saved, cot)
                jax.block_until_ready(out)
                time.sleep(bwd_s)
                return out

            def _last_compute(self, params, head_params, saved, head_mb):
                out = super()._last_compute(params, head_params, saved,
                                            head_mb)
                jax.block_until_ready(out)
                time.sleep(fwd_s + bwd_s)
                return out
        return FloorPipeline

    def run_placement(interleave: int):
        reg = M.MetricsRegistry()
        proxies: list[LatencyProxy] = []

        def endpoint_map(stage_idx: int, port: int) -> str:
            proxy = LatencyProxy("127.0.0.1", port, one_way_s)
            proxies.append(proxy)
            return f"127.0.0.1:{proxy.start()}"

        links = open_local_pipeline(2, window=window, capacity=window,
                                    interleave=interleave, registry=reg,
                                    endpoint_map=endpoint_map)
        if interleave == 1:
            # flat: gang s runs blocks 2s,2s+1 fused as ONE stage — its
            # per-mb floor is both blocks' compute
            def stage_fn(p, x):
                return block_fn(p["hi"], block_fn(p["lo"], x))
            Floor = make_floor(2 * fwd_floor_s, 2 * bwd_floor_s)
            gang_params = [{"lo": blocks[0], "hi": blocks[1]},
                           {"lo": blocks[2], "hi": blocks[3]}]
        else:
            # looping placement: gang s chunk j = block j*2+s
            stage_fn = block_fn
            Floor = make_floor(fwd_floor_s, bwd_floor_s)
            gang_params = [[blocks[0], blocks[2]],
                           [blocks[1], blocks[3]]]
        res: dict = {}
        try:
            pls = [Floor(stage_fn, links[0], registry=reg),
                   Floor(stage_fn, links[1], loss_head=loss_head,
                         registry=reg)]

            def one_round(m_run: int) -> float:
                def run0():
                    res[0] = pls[0].value_and_grad(
                        gang_params[0], num_microbatches=m_run,
                        microbatches=xs[:m_run])

                def run1():
                    res[1] = pls[1].value_and_grad(
                        gang_params[1], num_microbatches=m_run,
                        head_params=head, head_batches=jax.tree.map(
                            lambda a: a[:m_run], tgts))
                ts = [threading.Thread(target=run0),
                      threading.Thread(target=run1)]
                t0 = time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=120)
                return time.perf_counter() - t0

            one_round(4)                    # compile + connect warmup
            wall_small = one_round(m_small)
            wall_big = one_round(m)
            return wall_small, wall_big, res
        finally:
            for link in links:
                link.close()
            for proxy in proxies:
                proxy.stop()

    m_small = max(4, m // 3)
    fl_small, fl_big, res_flat = run_placement(1)
    il_small, il_big, res_il = run_placement(2)
    # same model, two placements: the schedule moves walls, not math
    # (allclose, not bit-equal — flat jits two blocks per stage program)
    np.testing.assert_allclose(np.asarray(res_flat[1][0]),
                               np.asarray(res_il[1][0]),
                               rtol=1e-5, atol=1e-6)
    # steady-state per-microbatch wall: the two-point marginal rate
    # (wall_big - wall_small)/(m - m_small) cancels the pipeline fill —
    # the interleaved fill is ~3x longer (3 act hops vs 1), so the
    # absolute-wall ratio understates the throughput gap and converges
    # to the rate ratio only as M grows
    rate_flat = (fl_big - fl_small) / (m - m_small)
    rate_il = (il_big - il_small) / (m - m_small)
    return {
        "pipeline_bytes_on_wire_vs_raw": round(bytes_ratio, 2),
        "pipeline_flat_wall_s": round(fl_big, 3),
        "pipeline_interleaved_wall_s": round(il_big, 3),
        # the second tentpole ratio: latency hidden by v=2's doubled
        # in-flight, fill excluded (steady-state rates)...
        "pipeline_interleaved_vs_flat_steady_rate":
            round(rate_flat / rate_il, 2),
        # ...and the end-to-end wall at M microbatches, fill included
        "pipeline_interleaved_vs_flat_wall": round(fl_big / il_big, 2),
    }
