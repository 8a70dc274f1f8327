"""Benchmark: flagship transformer train-step throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference publishes no performance numbers (BASELINE.md: "published":
{}), so ``vs_baseline`` is measured in-run against the naive formulation of
the same model — dense O(S²) attention and no fused kernels — i.e. what a
line-for-line port of a CUDA/torch-style model to jax would do. Values > 1
mean the framework's TPU-first path (flash-attention pallas kernels, bf16
MXU matmuls, fused norms) beats the naive port on the same hardware.
"""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp

# Peak dense bf16 FLOP/s per chip, keyed by substring of device_kind.
# Order matters: more specific names first ("v5 lite" before "v5").
_PEAK_FLOPS = (
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
)


def _peak_flops() -> float:
    """Peak dense bf16 FLOP/s of the attached chip. A device that is not
    in the table is an error, not a default: an MFU against a guessed
    peak is not a measurement."""
    kind = jax.devices()[0].device_kind
    for name, peak in _PEAK_FLOPS:
        if name in kind.lower():
            return peak
    raise ValueError(f"no peak FLOP/s on record for device_kind {kind!r}; "
                     f"add it to _PEAK_FLOPS with its source")


def _bench_step(step, state, batch, iters: int, reps: int = 3) -> float:
    """Median-of-windows step time. One window can record a bad moment
    of a host shared with other work as the framework's throughput, so
    each config is timed over ``reps`` windows and the median wins. Each
    window ends in ``jax.block_until_ready``: dispatch is asynchronous,
    and a window that does not wait for the device times the enqueue."""
    state, m = step(state, batch)            # compile + warm
    jax.block_until_ready((state, m))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = step(state, batch)
        jax.block_until_ready((state, m))
        times.append((time.perf_counter() - t0) / iters)
    times.sort()
    return times[len(times) // 2]


def main() -> None:
    from tony_tpu.runtime import compile_cache
    # ~2/3 of a cold bench run is XLA compilation (6 jitted programs); the
    # persistent cache makes repeat runs start measuring immediately.
    compile_cache.enable()

    from tony_tpu.models import transformer as T
    from tony_tpu.models.train import (default_optimizer, init_state,
                                       make_train_step)

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # no chip, no number: a timing from XLA's CPU backend or the
        # Pallas interpreter is not a slower version of the chip's
        raise SystemExit(f"bench.py measures on a TPU; JAX found "
                         f"platform {platform!r}")
    # 512d/8L bf16, seq 1024. remat off (this size fits HBM comfortably
    # on one chip, ~7% faster), layers fully unrolled (drops the scan's
    # activation-stacking DUS ops, ~6% faster; compile cost is paid
    # once), batch 32 (+12% over 16 in interleaved A/B once bf16 logits
    # storage freed the headroom).
    cfg = T.PRESETS["small"].scaled(remat=False, scan_unroll=8)
    batch, seq, iters = 32, 1024, 20

    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0,
                                cfg.vocab_size)
    data = {"inputs": tokens[:, :seq], "targets": tokens[:, 1:]}

    def run(config, run_data, run_iters, reps=3) -> float:
        params = T.init_params(jax.random.PRNGKey(0), config)
        opt = default_optimizer(lr=1e-3)
        state = init_state(params, opt)
        step = make_train_step(
            lambda p, b: T.lm_loss(p, b, config), opt)
        return _bench_step(step, state, run_data, run_iters, reps=reps)

    t_framework = run(cfg, data, iters)

    # Naive port baseline: f32 params/compute, dense attention (remat off so
    # it is the straight autodiff graph a naive port gets). Run at batch 8 —
    # the naive formulation's own best config: at batch 16 its f32 dense
    # attention residuals blow past HBM and it collapses pathologically,
    # which would flatter vs_baseline. Compare per-token throughput.
    import tony_tpu.models.transformer as tmod
    naive_cfg = cfg.scaled(dtype=jnp.float32, remat=False)
    n_batch = min(batch, 8)
    n_data = {k: v[:n_batch] for k, v in data.items()}
    orig = tmod._attention
    tmod._attention = lambda q, k, v, *a: tmod.reference_attention(
        q, k, v, causal=True)
    try:
        # 2 windows: the RATIO tolerates drift better than absolute numbers
        t_naive = run(naive_cfg, n_data, iters, reps=2)
    finally:
        tmod._attention = orig

    tokens_per_sec = batch * seq / t_framework
    naive_tokens_per_sec = n_batch * seq / t_naive
    out = {
        "metric": "flagship_lm_train_throughput",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / naive_tokens_per_sec, 3),
    }

    peak = _peak_flops()
    flops_tok = T.train_flops_per_token(cfg, seq)
    out["mfu"] = round(tokens_per_sec * flops_tok / peak, 4)
    out["device"] = jax.devices()[0].device_kind

    # Secondary: KV-cache autoregressive decode throughput (the serving
    # path: prefill + scan-decode as one compiled program).
    from tony_tpu.models.decode import generate
    d_batch, d_prompt, d_new = 16, 128, 256
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3),
                                (d_batch, d_prompt), 0, cfg.vocab_size)
    # generate is already jit-compiled (static cfg/lengths)
    gen = functools.partial(generate, cfg=cfg, max_new_tokens=d_new,
                            temperature=0.0)
    dec = gen(params, prompt, rng=jax.random.PRNGKey(4))
    int(dec.tokens[0, 0])                    # compile + warm
    t0 = time.perf_counter()
    for i in range(3):
        dec = gen(params, prompt, rng=jax.random.PRNGKey(5 + i))
    int(dec.tokens[0, 0])
    t_dec = (time.perf_counter() - t0) / 3
    decode_tps = round(d_batch * d_new / t_dec, 1)
    # GQA decode (n_kv_heads=2): the grouped cache read + GQA-native
    # prefill kernels cut the decode-roofline HBM traffic — recorded
    # as its own arm since the model differs from the MHA flagship.
    gqa_cfg = cfg.scaled(n_kv_heads=2)
    gqa_params = T.init_params(jax.random.PRNGKey(0), gqa_cfg)
    gqa_gen = functools.partial(generate, cfg=gqa_cfg,
                                max_new_tokens=d_new, temperature=0.0)
    dec = gqa_gen(gqa_params, prompt, rng=jax.random.PRNGKey(4))
    int(dec.tokens[0, 0])                    # compile + warm
    t0 = time.perf_counter()
    for i in range(3):
        dec = gqa_gen(gqa_params, prompt, rng=jax.random.PRNGKey(9 + i))
    int(dec.tokens[0, 0])
    decode_gqa_tps = round(d_batch * d_new * 3
                           / (time.perf_counter() - t0), 1)
    out["decode_gqa_tokens_per_s"] = decode_gqa_tps
    del gqa_params, gqa_gen
    del params, prompt, dec, gen   # free HBM before the tight base run

    def secondary(name, config, s_batch, s_seq, s_iters, key,
                  with_mfu=True):
        toks = jax.random.randint(jax.random.PRNGKey(key),
                                  (s_batch, s_seq + 1), 0,
                                  config.vocab_size)
        s_data = {"inputs": toks[:, :s_seq], "targets": toks[:, 1:]}
        tps = s_batch * s_seq / run(config, s_data, s_iters, reps=2)
        out[f"{name}_tokens_per_s"] = round(tps, 1)
        if with_mfu:
            out[f"{name}_mfu"] = round(
                tps * T.train_flops_per_token(config, s_seq) / peak, 4)

    # GQA flagship (n_kv_heads=2): the grouped-query training win the
    # GQA-native kernels buy (K/V projections + attention K/V reads
    # ÷4). MFU accounting is GQA-aware (train_flops_per_token).
    secondary("gqa", cfg.scaled(n_kv_heads=2), batch, seq, 15, key=8)
    # "base" preset (768d/12L, BERT-base scale) at seq 2048 — stresses
    # framework overheads the small preset doesn't. remat off fits at
    # batch 8 on 16G HBM and is ~25% faster than remat at b=4.
    secondary("base", T.PRESETS["base"].scaled(remat=False,
                                               scan_unroll=12),
              8, 2048, 10, key=2)
    out["decode_tokens_per_s"] = decode_tps
    # "large" preset (1536d/24L, 1.0B params) — remat on (the optimizer
    # state already takes ~8 GB of HBM); the bigger matmuls give the
    # best MFU of any preset.
    secondary("large", T.PRESETS["large"], 4, 1024, 8, key=7)
    # long context (seq 8192) — the regime where attention dominates
    # layer FLOPs. Batch 4 is ~4% over 2 (interleaved A/B) and fits.
    # MFU recorded so the fused-vs-two-pass backward budget decision
    # (ops/attention.py _FUSED_PARTIALS_BYTES) has an efficiency
    # number to regress against.
    secondary("seq8k", cfg, 4, 8192, 10, key=6)
    # sliding-window attention at the same shape: the kernels triage
    # out-of-window blocks like above-diagonal ones (skip + DMA
    # elision), so attention cost goes O(seq·window). Measured 1.34x
    # over full causal at this shape when introduced (round 5).
    secondary("seq8k_win1k", cfg.scaled(attn_window=1024), 4, 8192,
              10, key=6)
    # extreme context (seq 32768, b1) under the attention-output-save
    # remat policy (round 5): saving the flash o/lse lets the
    # backward skip re-running the O(S²) forward kernel — +19%
    # measured over remat="full" at this shape.
    secondary("seq32k", T.PRESETS["small"].scaled(
        remat=True, remat_policy="attn"), 1, 32768, 5, key=9)
    # sliding window at extreme context — the regime where the
    # quadratic attention term dominates and the window pays most
    # (2.16x over full causal when introduced; MFU is the honest
    # windowed-FLOPs ratio, so it DROPS while tokens/s rises)
    secondary("seq32k_win4k", T.PRESETS["small"].scaled(
        remat=True, remat_policy="attn", attn_window=4096),
        1, 32768, 5, key=9)
    # ring-attention flash-chunk arm (cp=1 degenerate, 2 chunks on one
    # chip): runs flash_attention_with_lse + the logsumexp hop merge —
    # the exact per-hop compute of the cp ring — on real hardware, and
    # checks it against the monolithic kernel. Reported as fwd+bwd
    # tokens/s so the differentiated-lse path is exercised too.
    out.update(_ring_flash_arm())
    # serving-shape decode: a cache padded to realistic serving
    # max_len (2k / 8k) with a short generated length — the arm the
    # length-aware block-wise cache attention exists for. Cost should
    # be ~flat in max_len (vs linear for the dense full-cache read,
    # recorded as the contrast).
    out.update(_serving_decode_arm(cfg))
    # continuous batching at mixed generation budgets: step
    # utilization (useful tokens per slot-step) vs the static-batch
    # baseline that rides every batch to its longest request, with
    # the pipelined (double-buffered dispatch) loop against the
    # sequential contrast.
    out.update(_continuous_batching_arm(cfg))
    # admission latency: bucketed+batched admission (one program per
    # power-of-two length bucket, one dispatch per freed-slot wave)
    # vs the per-length per-row path it replaced.
    out.update(_admission_arm(cfg))
    # metrics-plane overhead: the serve loop is instrumented
    # unconditionally (runtime/metrics.py), so this arm pins that
    # registry observations stay within noise — instrumented vs
    # NullRegistry serve on the same workload, plus a hard assert
    # that per-sync observation cost is < 1% of chunk wall.
    out.update(_metrics_overhead_arm(cfg))
    # tracing-plane overhead: the serve engine opens TTFT-
    # decomposition spans per request (runtime/tracing.py), so this
    # arm pins sampled-on tracing within the same budget discipline
    # as the metrics arm (< 1% of chunk wall, A/B within noise) and
    # asserts the exported trace is schema-valid Chrome trace JSON.
    out.update(_trace_overhead_arm(cfg))
    # speculative decoding with a GENUINELY smaller draft: both models
    # are first trained on a learnable sequence so the draft actually
    # predicts the target (acceptance is what buys wall-clock; with a
    # random draft speculation is a correctness demo only).
    out.update(_speculative_arm())

    # job bring-up wall against the fake gcloud fleet: cold 4-gang launch
    # parallel vs the serial baseline (max-of-gangs vs sum-of-gangs), and
    # the warm-restart wall where surviving slices are adopted and the
    # content-stamp probe skips the tarball ship entirely. Hardware-free.
    out.update(_launch_arm())

    # elastic recovery: the same injected gang kill absorbed by the
    # degraded-resume loop (survivors resync + resume; the lost gang
    # regrows) vs the stop-the-world session re-run. Hardware-free and
    # jax-free (fake trainer): the numbers measure ORCHESTRATION — loss
    # detection, resync, relaunch — not model compile walls.
    out.update(_elastic_arm())

    # coordinator crash recovery: SIGKILL the coordinator mid-train and
    # let journal replay re-adopt the live executors (user processes
    # never stop, zero re-provisions) vs the cold full-job restart the
    # journal-less stack pays — resubmit, re-provision, re-run every
    # step since the last checkpoint. Hardware-free and jax-free; the
    # recovery number is the coordinator's own recovery-wall gauge and
    # the ratio is pinned >= 3x (tests/test_recovery.py runs the arm).
    out.update(_recovery_arm())

    # goodput ledger: interval-accounting overhead vs a NullRegistry
    # ledger (< 1% of the measured step wall asserted inside the arm)
    # plus goodput_fraction_train read off a real local-backend run's
    # final GOODPUT jhist event — the job page's headline number.
    # Hardware-free and jax-free.
    out.update(_goodput_arm())

    # tonylint full-repo analysis wall: the static gate must stay cheap
    # enough to run in tier-1 on every PR (< 10 s asserted inside the
    # arm), and the shipped tree must carry zero non-baselined findings.
    # Hardware-free and jax-free.
    out.update(_lint_arm())

    # cluster daemon: back-to-back 3-job turnover through the warm
    # slice pool (digest-affinity ALREADY_EXISTS adoption) vs cold
    # sequential bring-up. Real daemon + oracle jobs, no hardware; the
    # tier-1 pin (tests/test_cluster.py) asserts
    # sched_warm_turnover_vs_cold >= 2.
    out.update(_sched_arm())

    # streaming serving data plane: the persistent token-push wire vs a
    # request/response round trip per chunk, through an injected-latency
    # transport (LatencyProxy). Deterministic: a tiny CPU model with a
    # fixed per-sync fetch floor standing in for device compute, so the
    # ratio measures TRANSPORT shape, not rig noise. The tier-1 pin
    # (tests/test_serving.py) asserts stream-vs-rr >= 2 at a 50 ms round
    # trip and streamed wall within 1.15x of the zero-delay wall.
    out.update(_streaming_arm())

    # disaggregated prefill/decode: a prefill gang ships KV packages to
    # a decode gang over a tensor channel, so concurrent admissions
    # never stall in-flight decode chunks — decode ITL p99 under
    # admission churn vs the colocated engine at equal slots, with
    # token-identical output asserted. Deterministic: injected prefill/
    # decode compute floors (the streaming arm's technique); tier-1
    # pins serving_disagg_itl_p99_vs_colocated >= 2 and the handoff
    # wall visible on the metrics plane (tests/test_disagg.py).
    out.update(_disagg_arm())

    # live fleet operations on the simulated fleet: drain the most-
    # loaded replica under concurrent streams; every migrated session's
    # tokens are checked against the sim oracle, so the migration
    # dup/drop gap is an exact count (== 0 tier-1-pinned,
    # tests/test_fleet.py) and the drain wall is bounded by placement
    # latency, not stream length.
    out.update(_fleet_arm())

    # SLO-tiered serving: identical 2x-overload open-loop mixed
    # workload with and without QoS classes; interactive p99 TTFT
    # holds under priority admission + batch-row preemption while the
    # classless FIFO baseline blows through it (ratio >= 2
    # tier-1-pinned) and every preemption eviction resumes
    # token-identically (gap == 0 tier-1-pinned, tests/test_qos.py).
    out.update(_qos_arm())

    # warm scale-up: content-addressed weights shipped peer-to-peer
    # over the channel plane vs cold storage load + retrace, plus the
    # 8-replica rolling upgrade as one seed load + O(log N) fan-out vs
    # N serial loads. Tier-1 pins warm_vs_cold >= 2 and the wave count
    # (tests/test_weightstore.py).
    out.update(_weight_ship_arm())

    # prefix-aware routing + shared KV prefix tier: sessions placed
    # where the prefix KV already lives (one replica computes the
    # prefix once, the other warms in one template ship), suffix-only
    # admission vs prefix-blind full prefill at 8x prefix reuse.
    # Deterministic: a prefill floor per forward token + fetch floors;
    # tier-1 pins serving_prefix_ttft_vs_blind >= 2 and the FLOPs
    # reduction (tests/test_prefix.py).
    out.update(_prefix_arm())

    # cross-slice MPMD pipeline: the overlapped 1F1B schedule (channel
    # sends ride the bounded window while the device computes the next
    # microbatch) vs serialized stage execution (every tensor hop waits
    # for its delivery ack) through an injected-DCN-latency transport.
    # Deterministic: tiny stage blocks + fixed compute floors; the
    # tier-1 pin (tests/test_channels.py) asserts overlap >= 1.5x.
    out.update(_pipeline_arm())

    # DCN bytes as a resource: int8 wire codec bytes ratio + interleaved
    # (v=2) vs flat placement walls under injected latency. Tier-1 pins:
    # bytes >= 1.9x, interleaved beats flat (tests/test_channels.py).
    out.update(_pipeline_dcn_arm())

    # device-prefetched vs synchronous train feed: with nonzero decode
    # cost the pipelined loop's step wall should approach the
    # pure-compute wall (decode + H2D overlap the device step) while the
    # synchronous loop pays decode + compute serially; the data-wait
    # histogram is the direct input-boundedness signal.
    out.update(_input_pipeline_arm(cfg, batch, seq, steps=20))

    print(json.dumps(out))


def _input_pipeline_arm(cfg, batch, seq, steps: int = 20):
    """Prefetched vs synchronous train feed (the train-path twin of the
    serve loop's pipelined-vs-sequential arm).

    Three loops over the SAME jitted step and batch shape:

    - pure-compute: one preassembled device batch re-fed every step — the
      floor the pipelined loop must approach;
    - synchronous: each step decodes on the host (an emulated IO/decode
      stall of 0.6x the compute wall, plus a real bytes→ndarray decode)
      then assembles/transfers inline — steady-state wall >= decode +
      compute, the pre-change ``global_batch``-inline behavior;
    - prefetched: the same source behind a depth-2 DevicePrefetcher
      driven by run_training — decode + H2D overlap device compute, so
      step wall should sit within ~1.1x of pure compute and
      ``tony_data_wait_seconds`` near zero.

    The sleep-based stall is deliberate: reader decode is IO-dominated
    (GIL released), so overlap potential is real, and the arm stays
    deterministic across rigs."""
    import numpy as np

    from tony_tpu.io.prefetch import DevicePrefetcher
    from tony_tpu.models import transformer as T
    from tony_tpu.models.loop import run_training
    from tony_tpu.models.train import (default_optimizer, init_state,
                                       make_train_step)
    from tony_tpu.runtime import metrics as M

    opt = default_optimizer(lr=1e-3)
    step = make_train_step(lambda p, b: T.lm_loss(p, b, cfg), opt)

    def fresh_state():
        return init_state(T.init_params(jax.random.PRNGKey(0), cfg), opt)

    rs = np.random.RandomState(0)
    raw = rs.randint(0, cfg.vocab_size,
                     size=(batch, seq + 1)).astype(np.int32).tobytes()

    # pure-compute floor: preassembled device batch, step in a tight loop
    tokens = jnp.asarray(np.frombuffer(raw, np.int32).reshape(batch,
                                                              seq + 1))
    dev_batch = {"inputs": tokens[:, :seq], "targets": tokens[:, 1:]}
    state = fresh_state()
    state, m = step(state, dev_batch)            # compile + warm
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, dev_batch)
    float(m["loss"])
    t_compute = (time.perf_counter() - t0) / steps

    decode_s = 0.6 * t_compute      # nonzero decode cost, under compute

    def host_batches():
        while True:
            time.sleep(decode_s)                 # emulated IO stall
            arr = np.frombuffer(raw, np.int32).reshape(batch, seq + 1)
            yield {"inputs": arr[:, :seq], "targets": arr[:, 1:]}

    def sync_batches():
        # inline decode + H2D on the step critical path (the contrast)
        for hb in host_batches():
            yield jax.tree.map(jnp.asarray, hb)

    def timed(data):
        saved = M.set_default(M.MetricsRegistry())
        try:
            st = fresh_state()
            st, wm = step(st, dev_batch)         # warm (same shapes/jit)
            float(wm["loss"])
            t0 = time.perf_counter()
            st, wm = run_training(step, st, data, steps)
            float(wm["loss"])
            wall = (time.perf_counter() - t0) / steps
            wait = M.get_default().histogram("tony_data_wait_seconds").sum
        finally:
            M.set_default(saved)
        return wall, wait

    t_sync, wait_sync = timed(sync_batches())
    t_pre, wait_pre = timed(DevicePrefetcher(host_batches(), depth=2))

    return {
        "train_feed_compute_ms_per_step": round(t_compute * 1e3, 2),
        "train_feed_decode_ms_per_batch": round(decode_s * 1e3, 2),
        "train_feed_sync_ms_per_step": round(t_sync * 1e3, 2),
        "train_feed_prefetch_ms_per_step": round(t_pre * 1e3, 2),
        # <= 1.1 = pipelined feed reaches the pure-compute floor
        "train_feed_prefetch_vs_compute": round(t_pre / t_compute, 3),
        # ~1 + decode share (1.6 here) = synchronous feed pays serially
        "train_feed_sync_vs_compute": round(t_sync / t_compute, 3),
        "train_feed_data_wait_s_sync": round(wait_sync, 4),
        # ~0 = the prefetcher stays ahead of the step loop
        "train_feed_data_wait_s_prefetch": round(wait_pre, 4),
    }


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


def _goodput_arm(steps: int = 12, step_wait: float = 0.1) -> dict:
    """Goodput-ledger overhead + a real attributed training run.

    (a) Microbench: one enter/exit interval through the ledger, mirrored
    into a live MetricsRegistry vs a NullRegistry (the metrics arm's A/B
    discipline — snapshot-per-"step" included so the mirror path is in
    the measurement). The train loop opens <= 4 intervals per step
    (data_wait / step / checkpoint / eval), so 4x the per-interval cost
    is asserted < 1% of the REAL mean step wall measured in (b) — the
    issue's hard bound: attribution must be free.

    (b) A 2-worker local-backend run of the jax-free fake trainer whose
    final (cumulative) GOODPUT jhist event yields
    ``goodput_fraction_train`` — the same headline the history job page
    renders — plus the mean step wall used by (a)'s bound.

    Emitted keys: ``goodput_interval_ns``, ``goodput_ledger_frac_of_step``
    (< 0.01 asserted), ``goodput_ledger_live_vs_null`` (~1.0),
    ``goodput_fraction_train``, ``goodput_step_wall_mean_s``."""
    import os
    import shutil
    import sys
    import tempfile

    from tony_tpu.client.client import TonyClient
    from tony_tpu.conf.config import TonyConfig
    from tony_tpu.events.events import find_job_files, parse_events
    from tony_tpu.runtime import goodput as goodput_mod
    from tony_tpu.runtime import metrics as M

    # (a) per-interval cost through the real enter/exit path; a snapshot
    # every `per_snap` intervals models the trainer's publish cadence
    n, per_snap = 100_000, 100

    def timed(reg) -> float:
        led = goodput_mod.GoodputLedger(registry=reg)
        t0 = time.perf_counter()
        for i in range(n):
            with led.enter("step"):
                pass
            if i % per_snap == 0:
                led.snapshot()
        return (time.perf_counter() - t0) / n

    live = timed(M.MetricsRegistry())
    null = timed(M.NullRegistry())

    # (b) the real run: step walls + the headline fraction from the
    # final GOODPUT event
    tmp = tempfile.mkdtemp(prefix="tony-goodput-bench-")
    repo = os.path.dirname(os.path.abspath(__file__))
    trainer = os.path.join(repo, "tests", "fixtures",
                           "fake_elastic_trainer.py")
    try:
        cmd = (f"{sys.executable} {trainer} --steps {steps} "
               f"--ckpt {os.path.join(tmp, 'progress')} "
               f"--ckpt_every 2 --step_wait {step_wait} --tail_wait 0:1.5")
        conf = TonyConfig({
            "tony.staging.dir": os.path.join(tmp, "staging"),
            "tony.history.location": os.path.join(tmp, "hist"),
            "tony.application.timeout": "120000",
            "tony.worker.instances": "2",
            "tony.task.heartbeat-interval-ms": "100",
            "tony.metrics.snapshot-interval-ms": "300",
        })
        rc = TonyClient(conf, cmd).run()
        assert rc == 0, "goodput bench job failed"
        final = None
        for f in find_job_files(os.path.join(tmp, "hist")):
            for e in parse_events(f):
                if e.event_type == "GOODPUT":
                    final = e
        assert final is not None, "no GOODPUT event reached the jhist"
        fraction = final.payload["fraction"]
        assert 0 < fraction <= 1, fraction
        sw_c = sw_s = 0.0
        for tid, entry in final.payload["tasks"].items():
            if tid.startswith("worker:"):
                sw_c += entry["sw"]["c"]
                sw_s += entry["sw"]["s"]
        assert sw_c >= 2 * steps, "trainer ledgers never reached the jhist"
        step_wall = sw_s / sw_c
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # the hard bound: <= 4 ledger intervals per train step must cost
    # < 1% of the step wall they attribute
    frac = 4 * live / step_wall
    assert frac < 0.01, (
        f"goodput ledger costs {frac:.2%} of the step wall — interval "
        f"accounting is no longer free on the train loop")
    return {
        "goodput_interval_ns": round(live * 1e9, 1),
        "goodput_ledger_frac_of_step": round(frac, 6),
        "goodput_ledger_live_vs_null": round(live / max(null, 1e-12), 3),
        "goodput_fraction_train": round(fraction, 4),
        "goodput_step_wall_mean_s": round(step_wall, 4),
    }


def _lint_arm() -> dict:
    """tonylint full-repo analysis wall (docs/static-analysis.md).

    Runs every checker — per-file AST passes over all of tony_tpu/ plus
    the repo-wide proto/frame/observability checks — and asserts the
    whole sweep lands under 10 s, the budget that keeps the gate cheap
    enough for tier-1 (tests/test_lint.py runs the same self-check).
    Also asserts the shipped tree is clean: zero findings outside the
    committed ratchet baseline.

    Emitted keys: ``lint_full_repo_s`` (< 10 asserted),
    ``lint_files_scanned``, ``lint_findings_unbaselined`` (== 0
    asserted), ``lint_baseline_entries``."""
    import os

    from tony_tpu.devtools import lint

    pkg = os.path.join(lint.REPO_ROOT, "tony_tpu")
    t0 = time.perf_counter()
    findings = lint.run([pkg])
    wall = time.perf_counter() - t0
    left, _suppressed, _stale = lint.apply_baseline(
        findings, lint.load_baseline(
            os.path.join(lint.REPO_ROOT, lint.DEFAULT_BASELINE)))
    n_files = len(lint.scan_paths([pkg]))
    assert wall < 10.0, f"tonylint full sweep took {wall:.1f}s (>= 10s)"
    assert not left, "tonylint found unbaselined findings:\n" + \
        "\n".join(f.render() for f in left)
    return {
        "lint_full_repo_s": round(wall, 3),
        "lint_files_scanned": n_files,
        "lint_findings_unbaselined": len(left),
        "lint_baseline_entries": len(lint.load_baseline(
            os.path.join(lint.REPO_ROOT, lint.DEFAULT_BASELINE))),
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
            super()._admit_batch(pairs, prompts)

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


def _ring_flash_arm(b=4, s=8192, h=8, d=64, iters=8):
    from tony_tpu.ops.attention import (flash_attention,
                                        flash_attention_with_lse)

    half = s // 2
    q = jax.random.normal(jax.random.PRNGKey(11), (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(12), (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(13), (b, s, h, d), jnp.bfloat16)

    def two_chunk(q, k, v):
        # q0 sees only the diagonal chunk; q1 sees one past hop (full)
        # merged with its diagonal chunk — the 2-device causal ring,
        # laid out sequentially on one chip
        q0, q1 = q[:, :half], q[:, half:]
        k0, k1 = k[:, :half], k[:, half:]
        v0, v1 = v[:, :half], v[:, half:]
        o0, _ = flash_attention_with_lse(q0, k0, v0, causal=True)
        o10, lse10 = flash_attention_with_lse(q1, k0, v0, causal=False)
        o11, lse11 = flash_attention_with_lse(q1, k1, v1, causal=True)
        lse1 = jnp.logaddexp(lse10, lse11)
        to_bshd = lambda w: w.transpose(0, 2, 1)[..., None]
        o1 = (o10.astype(jnp.float32) * to_bshd(jnp.exp(lse10 - lse1))
              + o11.astype(jnp.float32) * to_bshd(jnp.exp(lse11 - lse1)))
        return jnp.concatenate([o0.astype(jnp.float32), o1], axis=1)

    mono = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    ring = jax.jit(two_chunk)
    err = float(jnp.max(jnp.abs(ring(q, k, v)
                                - mono(q, k, v).astype(jnp.float32))))
    grad = jax.jit(jax.grad(lambda q, k, v: two_chunk(q, k, v).sum(),
                            argnums=(0, 1, 2)))
    g = grad(q, k, v)
    float(g[0][0, 0, 0, 0].astype(jnp.float32))         # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        g = grad(q, k, v)
    float(g[0][0, 0, 0, 0].astype(jnp.float32))
    tps = b * s * iters / (time.perf_counter() - t0)
    return {"ringflash_tokens_per_s": round(tps, 1),
            "ringflash_vs_mono_maxerr": round(err, 5)}


def _serving_decode_arm(cfg, batch: int = 8, prompt_len: int = 128,
                        steps: int = 256):
    """Decode throughput vs padded cache size at FIXED generated length.

    A serving cache is sized for the longest request (2k-32k), while most
    requests finish far shorter; the dense cached-attention einsum pays
    for every padded row anyway. This arm prefills+scans ``steps`` greedy
    tokens into caches padded to 2048 and 8192 positions (live length
    <= 384 throughout) and reports tokens/s at each — ~flat under the
    block-wise length-aware path — plus a dense-forced 2048 contrast
    (the pre-round-5 behavior, linear in max_len)."""
    from tony_tpu.models import decode as D
    from tony_tpu.models import transformer as T

    params = T.init_params(jax.random.PRNGKey(0), cfg)

    def make_fns(max_len, run_cfg):
        # fresh closures per variant: the blockwise/dense dispatch happens
        # at trace time off D._BLOCKWISE_MIN_LEN, so variants must not
        # share a jit cache entry
        @jax.jit
        def do_prefill(p, toks):
            return D.prefill(p, toks, run_cfg, max_len)

        @functools.partial(jax.jit, static_argnames=("n",))
        def scan_decode(p, logits, cache, n):
            def step(carry, _):
                lg, c = carry
                token = jnp.argmax(lg, axis=-1)
                lg, c = D.decode_step(p, token, c, c["length"], run_cfg)
                return (lg, c), token

            (_, _), gen = jax.lax.scan(step, (logits, cache), None,
                                       length=n)
            return gen

        return do_prefill, scan_decode

    def time_one(max_len, force_dense=False, b=batch, run_cfg=cfg,
                 p_len=prompt_len, p=params):
        prompt = jax.random.randint(jax.random.PRNGKey(17),
                                    (b, p_len), 0, cfg.vocab_size)
        saved = D._BLOCKWISE_MIN_LEN
        if force_dense:
            D._BLOCKWISE_MIN_LEN = 1 << 30
        try:
            do_prefill, scan_decode = make_fns(max_len, run_cfg)
            # prefill (incl. the O(max_len) cache zero-init) runs OUTSIDE
            # the timed region — the metric is decode-step cost vs padded
            # max_len, and the fixed prefill would pull the ratio toward 1
            # while the init's max_len-scaled writes pull it away
            logits, cache = do_prefill(p, prompt)
            gen = scan_decode(p, logits, cache, steps)
            int(gen[0, 0])                       # compile + warm
            reps = []
            for _ in range(3):
                t0 = time.perf_counter()
                gen = scan_decode(p, logits, cache, steps)
                int(gen[0, 0])
                reps.append(time.perf_counter() - t0)
            return b * steps / sorted(reps)[1]
        finally:
            D._BLOCKWISE_MIN_LEN = saved

    tps2k = time_one(2048)
    tps8k = time_one(8192)
    tps2k_dense = time_one(2048, force_dense=True)
    # serving-batch amortization: the b8 step is per-op-overhead-bound
    # (~25 us/layer of fori_loop glue vs ~5 us of cache traffic —
    # docs/performance.md flash-decode negative result), so a wider
    # serving batch amortizes the fixed cost across 4x the rows; the
    # per-slot ratio (wide/base throughput over the batch ratio) is the
    # overhead share a batching queue can reclaim
    wide = 4 * batch
    tps2k_wide = time_one(2048, b=wide)
    # int8 KV cache: HBM footprint and cache read traffic halve vs bf16
    # (a serving host fits ~2x the slots or 2x max_len); throughput at
    # the SAME shape should hold near parity — the b8 step is per-op-
    # overhead-bound, not bandwidth-bound (docs/performance.md) — so the
    # ratio below is a regression guard for the capacity win, not a
    # speed claim
    qcfg = cfg.scaled(kv_cache_dtype="int8")
    tps8k_quant = time_one(8192, run_cfg=qcfg)
    tps2k_wide_quant = time_one(2048, b=wide, run_cfg=qcfg)
    # sliding-window decode at DEEP history (7k-token prompt): full
    # attention walks every live cache block per token; a window-1024
    # model walks ~4 blocks regardless of history — per-token serving
    # cost O(window), the decode-side claim of attn_window.
    deep = 7168
    tps_deep_full = time_one(8192, p_len=deep)
    tps_deep_win = time_one(8192, p_len=deep,
                            run_cfg=cfg.scaled(attn_window=1024))
    # rolling ring-buffer cache (kv_cache_capacity): same windowed math
    # over a capacity-row ring instead of the max_len buffer — 8x less
    # cache memory at this shape, measured speed parity; the length
    # ceiling disappears (requests may run past max_len)
    tps_deep_ring = time_one(8192, p_len=deep,
                             run_cfg=cfg.scaled(attn_window=1024,
                                                kv_cache_capacity=1024))
    # weight-only int8 (models/quantize.py): halves the matmul weights'
    # HBM read (the parameter-bound share of small-batch decode); the
    # all-int8 arm composes it with the int8 KV cache at the wide batch
    from tony_tpu.models.quantize import quantize_weights_int8
    wq = quantize_weights_int8(params)
    tps2k_wq = time_one(2048, p=wq)
    tps2k_wide_all8 = time_one(2048, b=wide, run_cfg=qcfg, p=wq)

    # quantized PREFILL: prefill over a long prompt is compute-bound
    # (the opposite regime from decode), so _weinsum's prefill-shaped
    # path converts the int8 weights to bf16 once per call and runs the
    # dots at bf16 MXU throughput — both-operands-f32 (the decode trade)
    # measured far below bf16 there. The ratio below pins the win; a
    # regression back toward all-f32 prefill shows up directly.
    def time_prefill(p, b_p=8, s_p=1024):
        toks = jax.random.randint(jax.random.PRNGKey(21), (b_p, s_p), 0,
                                  cfg.vocab_size)
        fn = jax.jit(lambda pp, tk: D.prefill(pp, tk, cfg, 2048)[0])
        float(fn(p, toks)[0, 0])                 # compile + warm
        reps = []
        for _ in range(3):
            t0 = time.perf_counter()
            float(fn(p, toks)[0, 0])
            reps.append(time.perf_counter() - t0)
        return b_p * s_p / sorted(reps)[1]

    tps_prefill = time_prefill(params)
    tps_prefill_wq = time_prefill(wq)
    return {
        "decode_maxlen2k_tokens_per_s": round(tps2k, 1),
        "decode_maxlen8k_tokens_per_s": round(tps8k, 1),
        "decode_maxlen2k_dense_tokens_per_s": round(tps2k_dense, 1),
        # ~1.0 = cost flat in padded max_len (the done-criterion)
        "decode_maxlen_8k_vs_2k": round(tps8k / tps2k, 3),
        f"decode_maxlen2k_b{wide}_tokens_per_s": round(tps2k_wide, 1),
        f"decode_b{wide}_vs_b{batch}_per_slot": round(
            tps2k_wide / tps2k / (wide / batch), 2),
        "decode_quant8_maxlen8k_tokens_per_s": round(tps8k_quant, 1),
        "decode_quant8_vs_bf16_8k": round(tps8k_quant / tps8k, 2),
        f"decode_quant8_maxlen2k_b{wide}_tokens_per_s": round(
            tps2k_wide_quant, 1),
        f"decode_quant8_vs_bf16_2k_b{wide}": round(
            tps2k_wide_quant / tps2k_wide, 2),
        "decode_deep7k_tokens_per_s": round(tps_deep_full, 1),
        "decode_deep7k_win1k_tokens_per_s": round(tps_deep_win, 1),
        "decode_win1k_vs_full_deep7k": round(
            tps_deep_win / tps_deep_full, 2),
        "decode_ring1k_deep7k_tokens_per_s": round(tps_deep_ring, 1),
        "decode_ring_vs_linear_win_deep7k": round(
            tps_deep_ring / tps_deep_win, 2),
        "decode_wq8_maxlen2k_tokens_per_s": round(tps2k_wq, 1),
        "decode_wq8_vs_bf16_2k": round(tps2k_wq / tps2k, 2),
        f"decode_all_int8_b{wide}_tokens_per_s": round(
            tps2k_wide_all8, 1),
        f"decode_all_int8_vs_bf16_b{wide}": round(
            tps2k_wide_all8 / tps2k_wide, 2),
        "prefill_b8_1k_tokens_per_s": round(tps_prefill, 1),
        "prefill_wq8_b8_1k_tokens_per_s": round(tps_prefill_wq, 1),
        # near 1.0 = quantized serving no longer pays an f32-prefill
        # latency tax (the pre-change all-f32 path sat well below it)
        "prefill_wq8_vs_bf16": round(tps_prefill_wq / tps_prefill, 2),
    }


def _continuous_batching_arm(cfg, slots: int = 8, prompt_len: int = 64):
    """Continuous batching vs static batches at mixed generation budgets.

    24 requests, budgets cycling 32..256 (mean 144) through 8 slots. The
    static baseline runs batches of 8 to each batch's LONGEST budget —
    what plain generate() serving does; finished rows ride dead until
    the stragglers finish. Reported both ways: wall-clock useful-token
    throughput (includes the per-chunk host sync the continuous loop
    pays) and step utilization = useful tokens / (decode steps x slots),
    the host-independent number."""
    import numpy as np

    from tony_tpu.models import transformer as T
    from tony_tpu.models.decode import generate
    from tony_tpu.models.serve import ContinuousBatcher

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(5)
    # one 256 per batch-of-8 keeps the static arm at ONE compile
    base = [256, 32, 64, 96, 128, 160, 192, 224]
    budgets = sum(([*rs.permutation(base)] for _ in range(3)), [])
    budgets = [int(b) for b in budgets]
    prompts = [list(rs.randint(0, cfg.vocab_size, size=prompt_len))
               for _ in budgets]
    useful = sum(budgets)
    max_len = prompt_len + 256

    # pipelined (default) loop: chunk N+1 dispatched before chunk N's
    # fetch, so the host's fetch + bookkeeping overlap device compute
    batcher = ContinuousBatcher(params, cfg, batch=slots, max_len=max_len,
                                chunk=16)
    batcher.serve(prompts[:slots], [16] * slots)      # compile + warm
    t0 = time.perf_counter()
    batcher.serve(prompts, budgets)
    t_cb = time.perf_counter() - t0
    cb_steps = batcher.steps_executed
    cb_phases = batcher.phase_times

    # sequential contrast (the pre-pipelining loop): every fetch
    # serializes the round trip with compute — the overlap win is the
    # ratio between these two on identical workload and device programs
    seq = ContinuousBatcher(params, cfg, batch=slots, max_len=max_len,
                            chunk=16, pipeline=False)
    seq.serve(prompts[:slots], [16] * slots)          # compile + warm
    t0 = time.perf_counter()
    seq.serve(prompts, budgets)
    t_cb_seq = time.perf_counter() - t0

    gen = functools.partial(generate, cfg=cfg, max_new_tokens=256,
                            temperature=0.0)
    warm_prompt = jnp.asarray(prompts[:slots], jnp.int32)
    out = gen(params, warm_prompt, rng=jax.random.PRNGKey(0))
    int(out.tokens[0, 0])                             # compile + warm
    static_steps = 0
    t0 = time.perf_counter()
    for i in range(0, len(prompts), slots):
        batch_prompts = jnp.asarray(prompts[i:i + slots], jnp.int32)
        out = gen(params, batch_prompts, rng=jax.random.PRNGKey(0))
        int(out.tokens[0, 0])
        static_steps += max(budgets[i:i + slots])
    t_static = time.perf_counter() - t0

    return {
        # step utilization is the host-independent serving metric
        # (useful tokens per slot-step); the wall numbers include every
        # chunk/admit host sync — the pipelined loop overlaps each sync
        # with the NEXT chunk's device compute (fetch + bookkeeping
        # hidden behind compute).
        # On a budget-only workload the pipelined loop runs the same
        # chunk count as the sequential loop (admission events process
        # synchronously — serve.py defer_issue), so the util numbers
        # are directly comparable across rounds.
        "serving_cb_step_util": round(useful / (cb_steps * slots), 3),
        "serving_static_step_util": round(
            useful / (static_steps * slots), 3),
        "serving_cb_tokens_per_s": round(useful / t_cb, 1),
        "serving_cb_sequential_tokens_per_s": round(
            useful / t_cb_seq, 1),
        # the overlap win, same programs and workload both sides
        "serving_cb_pipelined_vs_sequential": round(t_cb_seq / t_cb, 2),
        "serving_static_tokens_per_s": round(useful / t_static, 1),
        "serving_cb_vs_static_wall": round(t_static / t_cb, 2),
        # per-sync host phases (pipelined run): fetch is the blocking
        # transport+compute wait the overlap hides; dispatch is pure
        # host-side enqueue cost
        "serving_cb_fetch_ms_per_sync": round(
            1e3 * cb_phases.total("fetch")
            / max(1, cb_phases.count("fetch")), 1),
        "serving_cb_dispatch_ms_per_sync": round(
            1e3 * cb_phases.total("dispatch")
            / max(1, cb_phases.count("dispatch")), 1),
    }


def _admission_arm(cfg, slots: int = 8, n_req: int = 32,
                   budget: int = 8):
    """Admission cost: bucketed+batched vs per-length admission.

    A churn-heavy workload (short budgets → admission-dominated): 32
    requests over 12 DISTINCT prompt lengths (33..121) spanning two
    power-of-two buckets (64, 128). Wall time per admission INCLUDES
    each path's compiles — the per-length path recompiles for every new
    prompt length, which IS its cost on real traffic (a serving host
    sees arbitrary lengths forever), while the bucketed path compiles
    once per bucket and pads. (12 distinct lengths, not 30+, keeps the
    legacy arm's compile bill bounded on a cold cache while still
    making the retrace cost unmistakable.) The admit phase is taken
    from the batcher's own PhaseTimes, so the number excludes
    decode/fetch time on both sides."""
    import numpy as np

    from tony_tpu.models import transformer as T
    from tony_tpu.models.serve import ContinuousBatcher

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(9)
    distinct = [33 + 8 * i for i in range(12)]            # 33..121
    prompts = [list(rs.randint(0, cfg.vocab_size, size=int(n)))
               for n in rs.choice(distinct, size=n_req)]
    max_len = 128 + 2 * budget

    def admit_ms_per_req(bucketed):
        b = ContinuousBatcher(params, cfg, batch=slots, max_len=max_len,
                              chunk=budget, bucketed_admission=bucketed)
        b.serve(prompts, budget)
        return 1e3 * b.phase_times.total("admit") / n_req

    ms_bucketed = admit_ms_per_req(True)
    ms_perlen = admit_ms_per_req(False)
    return {
        "serving_admit_ms_per_req_bucketed": round(ms_bucketed, 2),
        "serving_admit_ms_per_req_perlength": round(ms_perlen, 2),
        "serving_admission_speedup": round(ms_perlen / ms_bucketed, 2),
    }


def _metrics_overhead_arm(cfg, slots: int = 8, prompt_len: int = 64,
                          budget: int = 128):
    """Metrics-registry overhead on the serve hot loop.

    The continuous batcher observes a handful of counters/gauges per host
    SYNC (not per token) and folds PhaseTimes once per serve() call —
    this arm verifies that stays free. Two measurements: (a) the same
    mixed workload served with the default registry vs a NullRegistry
    (whole-loop A/B — the ratio should be ~1.0, i.e. within the rig's
    run-to-run noise); (b) a direct microbench of one observation through
    the registry's get-or-create fast path, asserted to be < 1% of the
    measured per-sync chunk wall (the issue's hard bound — registry cost
    must never show up in serving latency)."""
    import numpy as np

    from tony_tpu.models import transformer as T
    from tony_tpu.models.serve import ContinuousBatcher
    from tony_tpu.runtime import metrics as M

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(3)
    prompts = [list(rs.randint(0, cfg.vocab_size, size=prompt_len))
               for _ in range(2 * slots)]

    def timed_serve():
        b = ContinuousBatcher(params, cfg, batch=slots,
                              max_len=prompt_len + budget, chunk=16)
        b.serve(prompts[:slots], [16] * slots)       # compile + warm
        t0 = time.perf_counter()
        b.serve(prompts, budget)
        return time.perf_counter() - t0, b

    saved = M.set_default(M.MetricsRegistry())
    try:
        t_on, b_on = timed_serve()
        syncs = max(1, b_on.phase_times.count("fetch"))
        M.set_default(M.NullRegistry())
        t_off, _ = timed_serve()
    finally:
        M.set_default(saved)

    # one observation through the exact serve call shape (lookup + inc)
    reg = M.MetricsRegistry()
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        reg.counter("bench_obs_total").inc()
    per_obs_s = (time.perf_counter() - t0) / n
    # the serve loop makes <~8 registry touches per sync (admit/retire/
    # token/queue-depth counters) plus one TTFT-or-ITL histogram observe
    # per DELTA (<= slots per sync) plus an O(#phases) fold per CALL
    obs_per_sync = 8 + 2 * slots
    frac = per_obs_s * obs_per_sync / (t_on / syncs)
    assert frac < 0.01, (
        f"registry observations are {frac:.2%} of per-sync chunk wall — "
        f"the metrics plane is no longer free on the serve loop")
    return {
        "serving_metrics_obs_ns": round(per_obs_s * 1e9, 1),
        "serving_metrics_obs_frac_of_chunk": round(frac, 6),
        # ~1.0 = instrumented serve within noise of uninstrumented
        "serving_metrics_instrumented_vs_null": round(t_on / t_off, 3),
    }


def _trace_overhead_arm(cfg, slots: int = 8, prompt_len: int = 64,
                        budget: int = 128):
    """Tracing-plane overhead on the serve hot loop + export validity.

    The engine opens ~3 spans per request (engine.request / .queued /
    .first_token — the TTFT decomposition) when sampling is on. Two
    measurements, the metrics arm's discipline: (a) the same workload
    served with sampling ON (rate 1.0) vs tracing OFF — the whole-loop
    A/B should sit within run noise; (b) a direct microbench of one
    start+end span through the tracer, asserted < 1 % of per-sync chunk
    wall at the engine's spans-per-sync worst case. The bench job's own
    exported trace must round-trip as schema-valid Chrome trace JSON
    (every event a complete ``X`` with name/ts/dur/pid/tid, or an ``M``
    metadata record)."""
    import numpy as np

    from tony_tpu.models import transformer as T
    from tony_tpu.models.serve import ContinuousBatcher
    from tony_tpu.runtime import metrics as M
    from tony_tpu.runtime import tracing

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(7)
    prompts = [list(rs.randint(0, cfg.vocab_size, size=prompt_len))
               for _ in range(2 * slots)]

    def timed_serve():
        b = ContinuousBatcher(params, cfg, batch=slots,
                              max_len=prompt_len + budget, chunk=16)
        b.serve(prompts[:slots], [16] * slots)       # compile + warm
        t0 = time.perf_counter()
        b.serve(prompts, budget)
        return time.perf_counter() - t0, b

    saved_reg = M.set_default(M.MetricsRegistry())
    saved_tr = tracing.set_tracer(
        tracing.Tracer(proc="bench:0", sample_rate=1.0, ring_size=8192))
    try:
        t_on, b_on = timed_serve()
        syncs = max(1, b_on.phase_times.count("fetch"))
        spans = tracing.get_tracer().recent()
        tracing.set_tracer(tracing.Tracer(proc="bench:0", enabled=False))
        t_off, _ = timed_serve()
    finally:
        tracing.set_tracer(saved_tr)
        M.set_default(saved_reg)

    # schema-valid Chrome trace from the sampled run's spans: JSON
    # round-trip + the invariants a viewer depends on
    assert spans, "sampled serve recorded no spans"
    chrome = json.loads(json.dumps(tracing.to_chrome(spans)))
    assert isinstance(chrome["traceEvents"], list) and chrome["traceEvents"]
    for e in chrome["traceEvents"]:
        assert e["ph"] in ("X", "M"), e
        if e["ph"] == "X":
            assert isinstance(e["name"], str) and e["name"]
            for key in ("ts", "dur"):
                assert isinstance(e[key], (int, float)) and e[key] >= 0, e
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    names = {e["name"] for e in chrome["traceEvents"] if e["ph"] == "X"}
    assert {"engine.request", "engine.queued",
            "engine.first_token"} <= names, names

    # one start+end through the tracer, the exact engine call shape
    tr = tracing.Tracer(proc="bench:0", sample_rate=1.0, ring_size=512)
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        tr.start_span("bench.span").end()
    per_span_s = (time.perf_counter() - t0) / n
    # worst case per host sync: every slot retires and readmits — one
    # request span ends + queued/first spans cycle (~3 span ops/slot)
    spans_per_sync = 3 * slots
    frac = per_span_s * spans_per_sync / (t_on / syncs)
    assert frac < 0.01, (
        f"span records are {frac:.2%} of per-sync chunk wall — the "
        f"tracing plane is no longer free on the serve loop")
    return {
        "serving_trace_span_ns": round(per_span_s * 1e9, 1),
        "serving_trace_span_frac_of_chunk": round(frac, 6),
        # ~1.0 = sampled-on serve within noise of tracing-off
        "serving_trace_sampled_vs_off": round(t_on / t_off, 3),
        "serving_trace_spans_recorded": len(spans),
    }


def _speculative_arm(new: int = 256, k: int = 10):
    """Batch-1 greedy vs device-loop speculative decoding, same target.

    Speculation only pays when the draft predicts the target, so the arm
    first trains target (base preset) and draft (1 layer, d128 — ~4% of
    the target's step cost) on a deterministic affine token chain both
    learn quickly; the measured ratio is then a REAL acceptance-driven
    win, not a fixture. Token match vs greedy is reported (bf16 chunk-vs-
    step near-ties can flip occasional tokens, as documented in
    models/decode.py)."""
    from tony_tpu.models import transformer as T
    from tony_tpu.models.decode import (generate,
                                        speculative_generate_device)
    from tony_tpu.models.train import (default_optimizer, init_state,
                                       make_train_step)

    cfg_t = T.PRESETS["base"].scaled(remat=False)
    cfg_d = T.PRESETS["base"].scaled(n_layers=1, d_model=128, n_heads=2,
                                     d_ff=512, remat=False)

    def make_data(rng, batch, seq):
        x0 = jax.random.randint(rng, (batch, 1), 0, 4099)

        def step(carry, _):
            nxt = (13 * carry + 7) % 4099
            return nxt, nxt

        _, xs = jax.lax.scan(step, x0, None, length=seq)
        toks = jnp.concatenate([x0, xs.squeeze(-1).T], axis=1)
        return {"inputs": toks[:, :seq], "targets": toks[:, 1:]}

    def train(cfg, steps, seed, snapshots=()):
        """Returns final params, plus params snapshotted at the requested
        step counts — one run covers a whole draft-quality sweep (the
        weaker drafts are exact prefixes of the deterministic stream)."""
        params = T.init_params(jax.random.PRNGKey(seed), cfg)
        opt = default_optimizer(lr=1e-3)
        state = init_state(params, opt)
        step = make_train_step(lambda p, b: T.lm_loss(p, b, cfg), opt)
        snaps = {}
        for i in range(steps):
            if i in snapshots:
                # deep-copy: the train step DONATES its state, so a bare
                # reference would be a deleted buffer one step later
                snaps[i] = jax.tree.map(jnp.copy, state["params"])
            state, _ = step(state,
                            make_data(jax.random.PRNGKey(1000 + i), 16, 256))
        return (state["params"], snaps) if snapshots else state["params"]

    p_t = train(cfg_t, 120, 0)
    # draft quality sweep: 400 steps ≈ near-perfect acceptance on this
    # task; 100/25 are the mediocre/weak drafts the acceptance sweep
    # below measures (the regime where min-commit decayed)
    p_d, snaps = train(cfg_d, 400, 1, snapshots=(25, 100))
    p_d_weak, p_d_mid = snaps[25], snaps[100]
    prompt = make_data(jax.random.PRNGKey(7), 1, 65)["inputs"][:, :64]
    greedy = functools.partial(generate, cfg=cfg_t, max_new_tokens=new,
                               temperature=0.0)
    spec = jax.jit(functools.partial(
        speculative_generate_device, cfg=cfg_t, draft_cfg=cfg_d,
        max_new_tokens=new, num_speculative=k))
    out_g = greedy(p_t, prompt, rng=jax.random.PRNGKey(0))
    out_s = spec(p_t, p_d, prompt)
    match = float((out_g.tokens[0, -new:] == out_s[0, -new:]).mean())
    ts_g, ts_s = [], []
    for rep in range(4):                    # interleaved, median wins
        t0 = time.perf_counter()
        for i in range(3):
            out_g = greedy(p_t, prompt, rng=jax.random.PRNGKey(i))
        int(out_g.tokens[0, -1])
        ts_g.append((time.perf_counter() - t0) / 3)
        t0 = time.perf_counter()
        for _ in range(3):
            out_s = spec(p_t, p_d, prompt)
        int(out_s[0, -1])
        ts_s.append((time.perf_counter() - t0) / 3)
    tg = sorted(ts_g)[len(ts_g) // 2]
    tsp = sorted(ts_s)[len(ts_s) // 2]
    out = {"spec_decode_tokens_per_s": round(new / tsp, 1),
           "greedy_b1_tokens_per_s": round(new / tg, 1),
           "spec_vs_greedy": round(tg / tsp, 2),
           "spec_token_match": round(match, 3)}
    # batch>1 acceptance sweep (per-row frontiers vs the min-commit
    # baseline): per-row commits let each row keep its own acceptance,
    # so the b8 ratio should hold up as the draft weakens — min-commit
    # decays with the batch MINIMUM. tokens/round recorded for both.
    # DISTINCT prompts per row: tiling one prompt would sync the rows'
    # acceptances and flatter both policies.
    b8 = make_data(jax.random.PRNGKey(8), 8, 64)["inputs"]
    og = greedy(p_t, b8, rng=jax.random.PRNGKey(0)); int(og.tokens[0, -1])
    t0 = time.perf_counter()
    for i in range(3):
        og = greedy(p_t, b8, rng=jax.random.PRNGKey(i))
    int(og.tokens[0, -1])
    t_g8 = (time.perf_counter() - t0) / 3

    # ONE jitted fn per commit policy, hoisted out of the draft loop:
    # draft params are runtime args, so all three drafts share a compile
    spec_fns = {
        commit: jax.jit(functools.partial(
            speculative_generate_device, cfg=cfg_t, draft_cfg=cfg_d,
            max_new_tokens=new, num_speculative=k, commit=commit,
            return_rounds=True))
        for commit in ("per_row", "min", "window")
    }

    def time_spec_b8(draft_p, commit):
        fn = spec_fns[commit]
        o, rounds = fn(p_t, draft_p, b8)
        int(o[0, -1])                            # compile + warm
        t0 = time.perf_counter()
        for _ in range(3):
            o, rounds = fn(p_t, draft_p, b8)
        int(o[0, -1])
        return (time.perf_counter() - t0) / 3, int(rounds)

    for name, draft_p in (("", p_d), ("_d100", p_d_mid),
                          ("_d25", p_d_weak)):
        t_pr, r_pr = time_spec_b8(draft_p, "per_row")
        t_mc, r_mc = time_spec_b8(draft_p, "min")
        # bounded-window commit: per-row acceptance, scatter-free writes
        # (one contiguous window slice + MXU one-hot merge per layer)
        t_wd, r_wd = time_spec_b8(draft_p, "window")
        out[f"spec_b8_vs_greedy{name}"] = round(t_g8 / t_pr, 2)
        out[f"spec_b8_mincommit_vs_greedy{name}"] = round(t_g8 / t_mc, 2)
        out[f"spec_b8_window_vs_greedy{name}"] = round(t_g8 / t_wd, 2)
        out[f"spec_b8_tokens_per_round{name}"] = round(new / r_pr, 2)
        out[f"spec_b8_mincommit_tokens_per_round{name}"] = round(
            new / r_mc, 2)
        out[f"spec_b8_window_tokens_per_round{name}"] = round(
            new / r_wd, 2)
    # speculative SAMPLING (temperature > 0): same round machinery with
    # the min(1, p/q) accept test — committed stream distributed as
    # direct target sampling; the win rides the draft's acceptance just
    # like the greedy case. Temperature-only on this task: sampling
    # wanders OFF the deterministic affine chain, and on those
    # out-of-distribution contexts the toy draft's nucleus no longer
    # overlaps the target's — top_p=0.9 measured acceptance collapse
    # (1.17 tokens/round, 0.13x) where temperature-only holds 4.8
    # tokens/round (see docs/performance.md)
    gen_s = functools.partial(generate, cfg=cfg_t, max_new_tokens=new,
                              temperature=0.9)
    spec_s = jax.jit(functools.partial(
        speculative_generate_device, cfg=cfg_t, draft_cfg=cfg_d,
        max_new_tokens=new, num_speculative=k, temperature=0.9))
    og = gen_s(p_t, b8, rng=jax.random.PRNGKey(0)); int(og.tokens[0, -1])
    os_ = spec_s(p_t, p_d, b8, rng=jax.random.PRNGKey(0)); int(os_[0, -1])
    t0 = time.perf_counter()
    for i in range(3):
        og = gen_s(p_t, b8, rng=jax.random.PRNGKey(i))
    int(og.tokens[0, -1])
    t_gs = (time.perf_counter() - t0) / 3
    t0 = time.perf_counter()
    for i in range(3):
        os_ = spec_s(p_t, p_d, b8, rng=jax.random.PRNGKey(i))
    int(os_[0, -1])
    t_ss = (time.perf_counter() - t0) / 3
    out["spec_b8_sampled_vs_sampled"] = round(t_gs / t_ss, 2)

    out.update(_spec_serving_arm(cfg_t, cfg_d, p_t, p_d,
                                 make_data, new=new, k=k))
    return out


def _spec_serving_arm(cfg_t, cfg_d, p_t, p_d, make_data, new, k,
                      slots: int = 8, n_req: int = 16):
    """Continuous batching WITH speculative decoding vs greedy continuous
    batching, same workload and slot count, trained draft (the two
    serving features composed). Both loops pay the same per-sync host
    cost, so the ratio is fair to either. rounds/tokens recorded for the
    speculative side (tokens-per-round = acceptance efficiency inside
    the serving loop)."""
    from tony_tpu.models.serve import (ContinuousBatcher,
                                       SpeculativeContinuousBatcher)

    prompts = [list(map(int, make_data(jax.random.PRNGKey(50 + i), 1, 65)
                        ["inputs"][0, :64])) for i in range(n_req)]
    useful = n_req * new
    max_len = 64 + new

    greedy_b = ContinuousBatcher(p_t, cfg_t, batch=slots, max_len=max_len,
                                 chunk=16)
    greedy_b.serve(prompts[:slots], [16] * slots)        # compile + warm
    t0 = time.perf_counter()
    greedy_b.serve(prompts, new)
    t_greedy = time.perf_counter() - t0

    spec_b = SpeculativeContinuousBatcher(
        p_t, cfg_t, p_d, cfg_d, batch=slots, max_len=max_len,
        num_speculative=k, chunk=2)
    spec_b.serve(prompts[:slots], [16] * slots)          # compile + warm
    t0 = time.perf_counter()
    spec_b.serve(prompts, new)
    t_spec = time.perf_counter() - t0

    return {
        "serving_spec_cb_tokens_per_s": round(useful / t_spec, 1),
        "serving_greedy_cb_tokens_per_s": round(
            useful / t_greedy, 1),
        "serving_spec_cb_vs_greedy_cb": round(t_greedy / t_spec, 2),
        "serving_spec_cb_tokens_per_round": round(
            useful / (slots * spec_b.rounds_executed), 2),
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


if __name__ == "__main__":
    main()
