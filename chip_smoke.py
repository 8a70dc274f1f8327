"""Does the system still start on the chip? One orchestrated training job
and one serving replica at ``large`` width (1536d/24L/16 heads x 96, 32k
vocab, 1.0B params, bf16), through the entry points a user calls.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the sharded step on a dp=2,tp=2 mesh

One chip, in order, each phase a child process (the parent never imports
jax — a process that has initialised a backend holds the chip, and the
next child would fail or hang):

0. *probe*: JAX must report a TPU, and as many chips as asked for.
1. *train*: ``python -m tony_tpu.client.cli local ... train_lm.py --preset
   large --batch_size 4 --seq_len 1024 --steps 25 --lr 3e-3 --data_files
   <a small learnable token file>`` — client, coordinator, executor, user
   script. The task log must show the TPU, bf16, a finite loss that fell,
   and Mosaic kernels in the lowered step program.
2. *serve*: ``serve_lm.py --preset large --slots 8 --listen`` as a
   background replica; a JAX-free ``StreamingClient`` sends greedy requests
   of mixed prompt lengths. Every stream must end with the tokens asked
   for, the same request twice must give the same tokens, ``stats()`` must
   show the admits, and the replica must drain and exit 0.

``--chips 4`` runs only the four-chip path and what it is compared with:
the same job with ``tony.application.mesh=dp=2,tp=2`` (one worker drives
the host's four chips), then — in a child of its own — one sharded train
step's loss and global grad norm against the unsharded single-device
evaluation of the same params and batch, with per-device memory and the
device set of one sharded parameter printed.

The last line of stdout is ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}`` as the probe child read it from
``jax.devices()``. Any failed phase ends the script non-zero before that
line; there is no CPU mode.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
from array import array

PLATFORM = "tpu"
REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".tony", "chip_smoke")   # .gitignore lists .tony/
PRESET, BATCH, SEQ, STEPS = "large", 4, 1024, 25
# A loss that "went down" has to fall by more than one random batch
# differs from the next (~0.02 at this size). train_lm.py's synthetic
# source is uniform noise, where 25 warm-up steps move the loss by about
# that (chip runs: 10.8624, 10.8807, 10.8687 at the default lr; 10.8624,
# 10.8089, 10.8473 at 3e-3). So the job trains on a token file with
# something to learn — 64 symbols, each the last plus one — fed through
# the sharded data layer (--data_files), at an lr that peaks at 7e-4.
LR, MIN_FALL = 3e-3, 0.1
VOCAB = 32000                                      # PRESETS["large"].vocab_size
SLOTS, MAX_PROMPT, NEW_TOKENS = 8, 512, 64

_children: list[subprocess.Popen] = []


def _env() -> dict[str, str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = PLATFORM     # no chip -> backend init fails
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH", "")) if p)
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _spawn(argv: list[str], log_path: str) -> subprocess.Popen:
    """Start a child in its own process group, output to ``log_path``."""
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, cwd=WORK, env=_env(), stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    _children.append(proc)
    return proc


def _stop_children() -> None:
    for proc in _children:
        try:
            os.killpg(proc.pid, signal.SIGKILL)   # stragglers of its group
        except ProcessLookupError:
            pass
        proc.wait()


def _fail(msg: str, log_path: str | None = None):
    if log_path and os.path.exists(log_path):
        with open(log_path, errors="replace") as f:
            sys.stdout.write(f.read()[-6000:])
    raise SystemExit(f"chip_smoke: FAILED — {msg}")


def _run(name: str, argv: list[str], timeout: float) -> str:
    """Run one phase's child to its end; returns its output. A non-zero
    exit or a timeout fails the script."""
    log_path = os.path.join(WORK, f"{name}.log")
    t0 = time.perf_counter()
    proc = _spawn(argv, log_path)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        _fail(f"{name}: no exit within {timeout:.0f} s", log_path)
    if rc != 0:
        _fail(f"{name}: exit code {rc}", log_path)
    print(f"[{name}] exit 0 in {time.perf_counter() - t0:.1f} s", flush=True)
    with open(log_path, errors="replace") as f:
        return f.read()


def _self(phase: str, *args: str) -> list[str]:
    return [sys.executable, os.path.abspath(__file__), "--phase", phase,
            *args]


def _device_of(text: str, where: str) -> dict:
    """The ``rt.device_line`` a child printed, as a dict."""
    m = re.search(r"platform (\S+) kind '([^']*)' devices (\d+) "
                  r"dtype (\S+)", text)
    if not m:
        _fail(f"{where}: no device line in the output")
    return {"platform": m.group(1), "kind": m.group(2),
            "count": int(m.group(3)), "dtype": m.group(4)}


def _echo(name: str, text: str, pattern: str) -> None:
    for line in text.splitlines():
        if re.search(pattern, line):
            print(f"[{name}] {line.strip()}", flush=True)


# ---------------------------------------------------------------- phases
def phase_probe(chips: int) -> dict:
    out = _run("probe", _self("probe", "--chips", str(chips)), timeout=180)
    device = json.loads(out.strip().splitlines()[-1])
    print(f"[probe] {device}", flush=True)
    return device


def _token_file() -> str:
    """64 records of SEQ+1 int32 ids: record r counts up from 7r, mod 64."""
    path = os.path.join(WORK, "tokens.bin")
    with open(path, "wb") as f:
        for r in range(64):
            array("i", ((7 * r + i) % 64 for i in range(SEQ + 1))).tofile(f)
    return path


def phase_train(chips: int, mesh: str) -> None:
    """Client -> coordinator -> executor -> train_lm.py, as a user submits."""
    name = f"train-{mesh.replace(',', '-').replace('=', '')}"
    staging = os.path.join(WORK, name)
    out = _run(name, [
        sys.executable, "-m", "tony_tpu.client.cli", "local",
        "--src_dir", os.path.join(REPO, "examples"),
        "--executes",
        f"{sys.executable} examples/lm/train_lm.py --preset {PRESET} "
        f"--batch_size {BATCH} --seq_len {SEQ} --steps {STEPS} --lr {LR} "
        f"--data_files {_token_file()}",
        "--conf", "tony.worker.instances=1",
        "--conf", f"tony.application.mesh={mesh}",
        "--conf", f"tony.staging.dir={staging}"], timeout=900)
    logs = ""
    for root, _, files in os.walk(staging):
        if os.path.basename(root) == "logs":
            for fn in sorted(files):
                with open(os.path.join(root, fn), errors="replace") as f:
                    logs += f.read()
    if "step program" not in logs:
        sys.stdout.write(out[-3000:])
        _fail(f"{name}: no task log with a step program under {staging}")
    _echo(name, logs, r"platform \S+ kind|mesh=|step program|^step \d+ loss"
                      r"|compile cache|compile seconds|done: final loss")
    dev = _device_of(logs, name)
    if (dev["platform"], dev["count"], dev["dtype"]) != (
            PLATFORM, chips, "bfloat16"):
        _fail(f"{name}: task ran on {dev}, wanted {chips} x {PLATFORM} "
              f"in bfloat16")
    kernels = int(re.search(r"step program: (\d+) Mosaic", logs).group(1))
    if kernels < 2:             # at least flash forward and backward
        _fail(f"{name}: {kernels} Mosaic kernel calls in the step program")
    losses = [float(x) for x in re.findall(r"^step \d+ loss (\S+)", logs,
                                           re.M)]
    if len(losses) < 3 or not all(0.0 < x < 100.0 for x in losses):
        _fail(f"{name}: losses {losses} — want >= 3 finite log lines")
    if not losses[-1] < losses[0] - MIN_FALL:
        _fail(f"{name}: loss did not fall by {MIN_FALL}: {losses}")
    print(f"[{name}] ok: {kernels} Mosaic kernels in the step, loss "
          f"{losses[0]} -> {losses[-1]}", flush=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def phase_serve() -> None:
    """A ``large`` replica behind the streaming wire; this (JAX-free)
    process is its client."""
    sys.path.insert(0, REPO)
    from tony_tpu.serving.client import StreamingClient
    if "jax" in sys.modules:
        _fail("serve: the serving client imported jax into the parent")

    port = _free_port()
    log_path = os.path.join(WORK, "serve.log")
    t0 = time.perf_counter()
    proc = _spawn([
        sys.executable, os.path.join(REPO, "examples", "lm", "serve_lm.py"),
        "--preset", PRESET, "--slots", str(SLOTS),
        "--prompt_len", str(MAX_PROMPT), "--max_new_tokens", str(NEW_TOKENS),
        "--listen", f"127.0.0.1:{port}"], log_path)

    def log_text() -> str:
        with open(log_path, errors="replace") as f:
            return f.read()

    while f"on 127.0.0.1:{port}" not in log_text():
        if proc.poll() is not None:
            _fail(f"serve: replica exited {proc.returncode} before "
                  f"listening", log_path)
        if time.perf_counter() - t0 > 600:
            _fail("serve: replica not listening after 600 s", log_path)
        time.sleep(0.5)
    print(f"[serve] replica listening after {time.perf_counter() - t0:.1f} s",
          flush=True)
    dev = _device_of(log_text(), "serve")
    if (dev["platform"], dev["dtype"]) != (PLATFORM, "bfloat16"):
        _fail(f"serve: replica runs on {dev}")

    rs = random.Random(0)
    lengths = [200, 64, 333, MAX_PROMPT]
    prompts = [[rs.randrange(VOCAB) for _ in range(n)] for n in lengths]
    with StreamingClient("127.0.0.1", port) as client:
        def ask(prompt):
            return client.submit(prompt, NEW_TOKENS)

        def answer(rid):
            toks, reason = client.result(rid, timeout=600.0)
            if len(toks) != NEW_TOKENS or not all(
                    0 <= t < VOCAB for t in toks):
                _fail(f"serve: request {rid} ended ({reason}) with "
                      f"{len(toks)} tokens, wanted {NEW_TOKENS}", log_path)
            return toks

        # the same greedy request twice, each alone in the engine: the
        # same programs at the same shapes must give the same tokens
        t1 = time.perf_counter()
        first = answer(ask(prompts[0]))
        t2 = time.perf_counter()
        again = answer(ask(prompts[0]))
        t3 = time.perf_counter()
        if first != again:
            _fail(f"serve: identical greedy requests differ:\n{first}\n"
                  f"{again}", log_path)
        # then the mixed lengths together, through shared slots
        rids = [ask(p) for p in prompts]
        mixed = [answer(r) for r in rids]
        t4 = time.perf_counter()
        stats = client.stats()
    n_req = 2 + len(prompts)
    want_prefill = 2 * lengths[0] + sum(lengths)
    print(f"[serve] {n_req} requests x {NEW_TOKENS} tokens: first (cold) "
          f"{t2 - t1:.2f} s, repeat {t3 - t2:.2f} s, {len(prompts)} mixed "
          f"{t4 - t3:.2f} s; repeat identical: True; same prompt among "
          f"others identical: {mixed[0] == first}", flush=True)
    print(f"[serve] stats {stats}", flush=True)
    if (stats["prefill_tokens"], stats["active"], stats["queue_depth"],
            stats["slots"]) != (want_prefill, 0, 0, SLOTS):
        _fail(f"serve: stats do not show {n_req} admits of "
              f"{want_prefill} prompt tokens", log_path)

    os.kill(proc.pid, signal.SIGINT)            # ^C: drain and exit
    try:
        rc = proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        _fail("serve: replica did not exit within 120 s of SIGINT", log_path)
    if rc != 0 or "draining" not in log_text():
        _fail(f"serve: replica exit code {rc} on drain", log_path)
    _echo("serve", log_text(), r"platform \S+ kind|serving |draining"
                               r"|compile cache|compile seconds")
    print(f"[serve] ok: drained, exit 0, {time.perf_counter() - t0:.1f} s "
          f"in all", flush=True)


def phase_compare(chips: int) -> None:
    out = _run("compare", _self("compare", "--chips", str(chips)),
               timeout=900)
    _echo("compare", out, r"^(platform|device \d|param|unsharded|sharded"
                          r"|compile cache|compile seconds)")


# ------------------------------------------- children that hold the chip
def _child_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != PLATFORM or len(devices) != chips:
        raise SystemExit(
            f"wanted {chips} {PLATFORM} device(s); JAX found {len(devices)} "
            f"of platform {devices[0].platform!r}")
    return devices


def child_probe(chips: int) -> None:
    d = _child_devices(chips)
    print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                      "count": len(d)}))


def child_compare(chips: int) -> None:
    """One sharded ``large`` train step on dp=2,tp=2 against the unsharded
    single-device loss and grad norm of the same params and batch — what
    ``__graft_entry__._dryrun_body`` does at toy width on the CPU mesh."""
    devices = _child_devices(chips)
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp

    import tony_tpu.runtime as rt
    from __graft_entry__ import (_GNORM_RTOL, _LOSS_RTOL, _assert_close,
                                 _loss_gnorm)
    from tony_tpu.models import transformer as T
    from tony_tpu.models.train import (batch_sharding, default_optimizer,
                                       init_state, make_train_step)
    from tony_tpu.parallel import make_mesh, shard_pytree
    from tony_tpu.runtime import compile_cache

    compile_cache.enable()
    print(rt.device_line(jnp.bfloat16), flush=True)
    cfg = T.PRESETS[PRESET].scaled(dtype=jnp.bfloat16)
    mesh = make_mesh({"dp": 2, "tp": chips // 2}, devices=devices)
    params0 = T.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ + 1), 0,
                                cfg.vocab_size)
    batch = {"inputs": tokens[:, :SEQ], "targets": tokens[:, 1:]}

    params = shard_pytree(params0, T.logical_axes(cfg), mesh)
    ref_loss, ref_gnorm = _loss_gnorm(params0, batch, cfg, None)
    del params0
    print(f"unsharded (device 0): loss {ref_loss!r} grad norm "
          f"{ref_gnorm!r}", flush=True)

    opt = default_optimizer(total_steps=STEPS)
    step = make_train_step(lambda p, b: T.lm_loss(p, b, cfg, mesh), opt,
                           mesh)
    state = init_state(params, opt)
    sharded_batch = jax.device_put(
        batch, batch_sharding(mesh, logical=("batch", "seq")))
    if step.lower(state, sharded_batch).as_text().count(
            "tpu_custom_call") < 2:
        raise SystemExit("no flash kernels in the sharded step program")
    state, metrics = step(state, sharded_batch)
    loss, gnorm = float(metrics["loss"]), float(metrics["grad_norm"])
    print(f"sharded step (dp=2,tp={chips // 2}): loss {loss!r} grad norm "
          f"{gnorm!r} — rtol loss {_LOSS_RTOL} grad norm {_GNORM_RTOL}",
          flush=True)

    wq = state["params"]["blocks"]["wq"]
    shard_devices = sorted(s.device.id for s in wq.addressable_shards)
    print(f"param blocks/wq {wq.shape} {wq.sharding.spec}: shards "
          f"{sorted({tuple(s.data.shape) for s in wq.addressable_shards})} "
          f"on devices {shard_devices}", flush=True)
    in_use = []
    for d in devices:
        stats = d.memory_stats()
        in_use.append(stats["bytes_in_use"])
        print(f"device {d.id}: bytes_in_use {stats['bytes_in_use']} peak "
              f"{stats.get('peak_bytes_in_use')}", flush=True)
    if (shard_devices != sorted(d.id for d in devices)
            or min(in_use) < 0.5 * max(in_use)):
        raise SystemExit(f"state is not spread over the devices: shards on "
                         f"{shard_devices}, bytes_in_use {in_use}")
    # the dryrun's own tolerances hold in bf16 at this width: tp=2 only
    # reassociates two contractions per layer (first four-chip run: loss
    # off by 3e-6, grad norm — which optax rounds to bf16 — by 3e-4)
    _assert_close("dp×tp train-step loss", loss, ref_loss, _LOSS_RTOL)
    _assert_close("dp×tp train-step grad norm", gnorm, ref_gnorm,
                  _GNORM_RTOL)
    print(compile_cache.stats(), flush=True)
    print(compile_cache.seconds_line(), flush=True)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    parser.add_argument("--phase", choices=("probe", "compare"),
                        help=argparse.SUPPRESS)     # the script's own children
    args = parser.parse_args()
    if args.phase:
        {"probe": child_probe, "compare": child_compare}[args.phase](
            args.chips)
        return 0

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    t0 = time.perf_counter()
    try:
        device = phase_probe(args.chips)
        if args.chips == 1:
            phase_train(1, "dp=-1")
            phase_serve()
        else:
            phase_train(args.chips, f"dp=2,tp={args.chips // 2}")
            phase_compare(args.chips)
    finally:
        _stop_children()
    print(f"chip_smoke: all phases ok in {time.perf_counter() - t0:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
