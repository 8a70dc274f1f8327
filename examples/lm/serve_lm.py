"""Serve a trained LM checkpoint with continuous batching — the serving
half of examples/lm (train_lm.py trains, generate.py decodes one batch,
this serves a QUEUE of requests through a fixed pool of cache slots).

Demonstrates the serving feature matrix on a synthetic workload of
mixed-length requests:

- plain continuous batching (greedy or sampled via --temperature/--top_k/
  --top_p): finished requests release their cache slot to the next
  queued request mid-flight;
- speculative serving (--draft_preset): every slot runs
  draft-propose/target-verify rounds at its own frontier — token-exact
  greedy, or distribution-exact rejection sampling when a temperature is
  set.

Usage:
    python examples/lm/serve_lm.py --preset tiny --requests 12 --slots 4
    python examples/lm/serve_lm.py --preset small --draft_preset tiny \
        --requests 16 --slots 8 --temperature 0.8

Streaming data plane (tony_tpu/serving): the same batcher can serve a
live admission queue over the persistent TONYS1 token-push protocol —

    # a serving replica (model host)
    python examples/lm/serve_lm.py --preset tiny --slots 4 \
        --listen 0.0.0.0:7070
    # a router front-door spreading sessions across replicas (no model)
    python examples/lm/serve_lm.py --listen 0.0.0.0:7000 \
        --route host1:7070,host2:7070
    # a streaming client (no model): submits the synthetic workload and
    # prints client-side TTFT / inter-token latency
    python examples/lm/serve_lm.py --preset tiny --requests 12 \
        --connect host1:7000

Disaggregated prefill/decode (docs/serving.md): prefill gangs ship KV
packages to decode gangs over tensor channels, so admissions never
stall in-flight decode chunks —

    # one prefill host + one decode host (real multi-host shape)
    python examples/lm/serve_lm.py --preset tiny --role prefill \
        --listen 0.0.0.0:7071
    python examples/lm/serve_lm.py --preset tiny --slots 4 \
        --role decode --listen 0.0.0.0:7072
    # the router splits placement: ADMIT -> prefill tier,
    # TOKENS <- decode tier
    python examples/lm/serve_lm.py --listen 0.0.0.0:7000 \
        --route host1:7071 --route_decode host2:7072
    # or spawn all three locally and run the synthetic workload:
    python examples/lm/serve_lm.py --preset tiny --requests 12 \
        --slots 4 --disaggregate

Prefix-aware routing (docs/serving.md §Prefix-aware routing): register
a shared system prompt, compute its KV template ONCE, warm the other
replica in one template ship, and let the router place every session
where the prefix already lives —

    # replica B first, cold. Size --prompt_len to fit prefix+suffix:
    # a replica whose max_len leaves no room for the shipped prefix
    # rejects the template (request-scoped) and serves prefix-blind
    python examples/lm/serve_lm.py --preset tiny --slots 4 \
        --prompt_len 96 --listen 0.0.0.0:7071 &
    # replica A computes the prefix template and warms replica B in
    # ONE template ship (B runs zero prefix forwards)
    python examples/lm/serve_lm.py --preset tiny --slots 4 \
        --prompt_len 96 --listen 0.0.0.0:7070 \
        --shared_prefix_file sys_prompt.txt \
        --publish_prefix host2:7071
    # the router matches prompts against the registered prefix
    python examples/lm/serve_lm.py --listen 0.0.0.0:7000 \
        --route host1:7070,host2:7071 --shared_prefix_file sys_prompt.txt
    # prefix-heavy client traffic (every prompt continues the prefix)
    python examples/lm/serve_lm.py --preset tiny --requests 12 \
        --connect host1:7000 --shared_prefix_file sys_prompt.txt

The reference framework has no serving path (it delegates all compute —
SURVEY.md §2.3); this example exists so a user migrating from it can see
the green-field serving stack end to end.
"""

from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import numpy as np

import tony_tpu.runtime as rt
from tony_tpu.models import transformer as T
from tony_tpu.models.checkpoint import CheckpointManager
from tony_tpu.models.serve import (ContinuousBatcher,
                                   SpeculativeContinuousBatcher)
from tony_tpu.runtime import compile_cache


def _sleep_until_stopped() -> None:
    """Block until SIGINT or SIGTERM. Both handlers are installed here:
    a process started in the background of a non-interactive shell
    inherits SIGINT ignored, and a supervisor stops a replica with
    SIGTERM — either must reach the drain, not kill mid-request."""
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, signal.default_int_handler)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass


def _parse_addr(addr: str) -> tuple[str, int]:
    host, _, port = addr.rpartition(":")
    return host or "127.0.0.1", int(port)


def _load_prefix_tokens(path: str) -> list[int]:
    """Token ids from a whitespace/comma-separated file — the shared
    prefix (system prompt) the prefix-aware demo paths register,
    install, publish, and continue."""
    with open(path) as f:
        toks = [int(t) for t in f.read().replace(",", " ").split()]
    if not toks:
        raise SystemExit(f"{path}: no tokens")
    return toks


def _install_and_publish(args, server) -> None:
    """--shared_prefix_file on a serving host: make the prefix resident
    (ONE local prefill); --publish_prefix additionally warms a peer
    replica in one template ship over its prefix lane (the peer runs
    ZERO prefix forwards — docs/serving.md §Prefix-aware routing)."""
    toks = _load_prefix_tokens(args.shared_prefix_file)
    pid = server.install_prefix(toks)
    if pid is None:
        print("prefix NOT resident (rolling-cache layout); serving "
              "prefix-blind", flush=True)
        return
    print(f"prefix {pid} resident ({len(toks)} tokens)", flush=True)
    if args.publish_prefix:
        from tony_tpu.serving.client import StreamingClient

        host, port = _parse_addr(args.publish_prefix)
        with StreamingClient(host, port) as peer:
            lane = peer.hello.get("prefix_port")
        if lane is None:
            raise SystemExit(f"{args.publish_prefix} advertises no "
                             f"prefix lane")
        n = server.publish_prefix(pid, f"{host}:{lane}")
        print(f"published prefix {pid} to {host}:{lane} ({n} bytes — "
              f"the peer warmed without recomputing)", flush=True)


def _run_server(args, batcher) -> int:
    """--listen: drive the batcher's ServeEngine behind a streaming
    server until interrupted, then drain gracefully."""
    from tony_tpu.serving.server import ServingServer

    host, port = _parse_addr(args.listen)
    server = ServingServer(batcher, bind_host=host, port=port,
                           weights_version=args.weights_version or None)
    bound = server.start()
    if args.shared_prefix_file:
        _install_and_publish(args, server)
    mode = ("speculative " if args.draft_preset else "") + (
        "sampled" if args.temperature > 0 else "greedy")
    print(f"serving {args.preset} ({mode}) on {host}:{bound} with "
          f"{args.slots} slots — ^C drains and exits", flush=True)
    _sleep_until_stopped()
    print("draining in-flight requests ...", flush=True)
    server.stop(drain=True)
    print(compile_cache.stats(), flush=True)
    print(compile_cache.seconds_line(), flush=True)
    return 0


def _run_router(args) -> int:
    """--listen + --route: the model-free front door. With
    --route_decode the router runs DISAGGREGATED placement — --route
    names the prefill tier, --route_decode the decode tier."""
    from tony_tpu.serving.router import ServingRouter

    host, port = _parse_addr(args.listen)
    replicas = [a.strip() for a in args.route.split(",") if a.strip()]
    decodes = [a.strip() for a in args.route_decode.split(",")
               if a.strip()]
    router = ServingRouter(replicas, bind_host=host, port=port,
                           decode_replicas=decodes or None)
    if args.shared_prefix_file:
        pid = router.register_prefix(
            _load_prefix_tokens(args.shared_prefix_file))
        print(f"prefix {pid} registered for tokenized matching",
              flush=True)
    bound = router.start()
    shape = (f"{len(replicas)} prefill + {len(decodes)} decode replicas"
             if decodes else f"{len(replicas)} replicas")
    print(f"routing on {host}:{bound} over {shape} — ^C exits",
          flush=True)
    _sleep_until_stopped()
    router.stop()
    return 0


def _run_prefill(args, params, cfg) -> int:
    """--role prefill --listen: the stateless prefill tier — no cache
    slots, no decode loop; prompts in, KV shipments out."""
    from tony_tpu.serving.disagg import PrefillServer

    host, port = _parse_addr(args.listen)
    shared = (_load_prefix_tokens(args.shared_prefix_file)
              if args.shared_prefix_file else [])
    server = PrefillServer(params, cfg,
                           max_len=(len(shared) + args.prompt_len
                                    + args.max_new_tokens),
                           seed=args.seed, max_batch=args.slots,
                           bind_host=host, port=port,
                           weights_version=args.weights_version or None)
    bound = server.start()
    if args.shared_prefix_file:
        _install_and_publish(args, server)
    print(f"prefill tier ({args.preset}) on {host}:{bound} "
          f"({args.slots}-row waves) — ^C exits", flush=True)
    _sleep_until_stopped()
    server.stop()
    return 0


def _run_decode(args, batcher) -> int:
    """--role decode --listen: the decode tier — admissions arrive as
    KV shipments on the channel hub, never as prompts."""
    from tony_tpu.serving.disagg import DecodeServer

    host, port = _parse_addr(args.listen)
    server = DecodeServer(batcher, bind_host=host, port=port,
                          weights_version=args.weights_version or None)
    bound = server.start()
    mode = "sampled" if args.temperature > 0 else "greedy"
    print(f"decode tier ({args.preset}, {mode}) on {host}:{bound} with "
          f"{args.slots} slots; kv channel on :{server.hub.port} — ^C "
          f"drains and exits", flush=True)
    _sleep_until_stopped()
    print("draining in-flight requests ...", flush=True)
    server.stop(drain=True)
    print(compile_cache.stats(), flush=True)
    print(compile_cache.seconds_line(), flush=True)
    return 0


def _run_disaggregate(args, params, cfg, batcher, prompts,
                      budgets) -> int:
    """--disaggregate: spawn both tiers + the router in-process and
    stream the synthetic workload through the split — the one-command
    demo of the topology (--role is the real multi-host shape)."""
    import threading

    from tony_tpu.runtime import metrics as M
    from tony_tpu.serving.client import StreamingClient
    from tony_tpu.serving.disagg import DecodeServer, PrefillServer
    from tony_tpu.serving.router import ServingRouter

    shared = (_load_prefix_tokens(args.shared_prefix_file)
              if args.shared_prefix_file else [])
    max_len = len(shared) + args.prompt_len + args.max_new_tokens
    reg = M.get_default()
    pre = PrefillServer(params, cfg, max_len=max_len, seed=args.seed,
                        max_batch=args.slots)
    dec = DecodeServer(batcher)
    router = ServingRouter([f"127.0.0.1:{pre.start()}"],
                           decode_replicas=[f"127.0.0.1:{dec.start()}"])
    if shared:
        pid = pre.install_prefix(shared)
        if pid is not None:
            router.register_prefix(shared, prefix_id=pid)
            print(f"prefix {pid} resident at the prefill tier "
                  f"({len(shared)} tokens); suffix-only prefill waves",
                  flush=True)
    rport = router.start()
    print(f"disaggregated: prefill :{pre.port} -> decode :{dec.port} "
          f"(kv channel :{dec.hub.port}), router :{rport}", flush=True)
    outs: list = [None] * args.requests
    ttfts: list = [0.0] * args.requests
    gaps: list[float] = []
    try:
        with StreamingClient("127.0.0.1", rport) as client:
            def drain(i, rid, t_submit):
                toks, last = [], None
                for delta in client.deltas(rid):
                    now = time.perf_counter()
                    if last is None:
                        ttfts[i] = now - t_submit
                    else:
                        gaps.append((now - last) / len(delta))
                    last = now
                    toks.extend(delta)
                outs[i] = toks

            t0 = time.perf_counter()
            threads = []
            for i, p in enumerate(prompts):
                rid = client.submit(p, budgets[i])
                th = threading.Thread(target=drain,
                                      args=(i, rid, time.perf_counter()))
                th.start()
                threads.append(th)
            for th in threads:
                th.join()
            dt = time.perf_counter() - t0
    finally:
        router.stop()
        pre.stop()
        dec.stop()
    useful = sum(len(o) for o in outs if o)
    ship = reg.histogram("tony_kv_ship_seconds")
    print(f"streamed {args.requests} requests ({useful} tokens) in "
          f"{dt:.2f}s — {useful / max(dt, 1e-9):.1f} tok/s")
    ttfts_s = sorted(ttfts)
    print(f"ttft: p50 {ttfts_s[len(ttfts_s) // 2] * 1e3:.0f} ms  "
          f"max {ttfts_s[-1] * 1e3:.0f} ms;  inter-token mean "
          f"{(sum(gaps) / len(gaps) * 1e3) if gaps else 0.0:.1f} ms")
    if ship.count:
        print(f"kv handoff: {ship.count} shipments, mean wall "
              f"{ship.sum / ship.count * 1e3:.1f} ms")
    print("first request tokens:", (outs[0] or [])[:12])
    return 0


def _run_client(args) -> int:
    """--connect: submit the synthetic workload over one persistent
    streaming connection and report client-side TTFT / inter-token
    latency. No model is built — prompt tokens draw from the named
    preset's vocab, which must match the server's."""
    import threading

    from tony_tpu.models import transformer as T
    from tony_tpu.serving.client import ServerBusy, StreamingClient

    host, port = _parse_addr(args.connect)
    if args.drain:
        # operator mode: ask the ROUTER to live-migrate every session
        # off a replica, print the summary, exit (docs/serving.md
        # §Operating the fleet)
        with StreamingClient(host, port) as client:
            res = client.drain_replica(args.drain)
        print(f"drain {args.drain}: {res}")
        return 0 if res.get("ok") else 1
    vocab = T.PRESETS[args.preset].vocab_size
    rs = np.random.RandomState(args.seed)
    # with a shared prefix the workload is PREFIX-HEAVY: every prompt
    # continues the same system prompt (the router's tokenized match
    # finds it — no prefix id is sent; the prefix-aware fleet places
    # each session where the prefix KV already lives)
    shared = (_load_prefix_tokens(args.shared_prefix_file)
              if args.shared_prefix_file else [])
    prompts = [shared + [int(t) for t in rs.randint(0, vocab,
                                                    size=args.prompt_len)]
               for _ in range(args.requests)]
    budgets = [int(b) for b in
               rs.randint(max(1, args.max_new_tokens // 4),
                          args.max_new_tokens + 1, size=args.requests)]
    # QoS classes: one class for every request (--request_class), or
    # the mixed-class mode — a deterministic interactive/standard/batch
    # rotation that exercises replica-side priority, preemption, and
    # shedding, reported per class
    classes = None
    if args.mixed_classes:
        cyc = ("interactive", "standard", "batch")
        classes = [cyc[i % len(cyc)] for i in range(args.requests)]
    elif args.request_class:
        classes = [args.request_class] * args.requests
    outs: list = [None] * args.requests
    ttfts: list = [0.0] * args.requests
    gaps: list = [[] for _ in range(args.requests)]
    shed: list = [False] * args.requests

    with StreamingClient(host, port) as client:
        print(f"connected to {host}:{port}: {client.hello}")

        def drain(i, rid, t_submit):
            toks, last = [], None
            try:
                for delta in client.deltas(rid):
                    now = time.perf_counter()
                    if last is None:
                        ttfts[i] = now - t_submit
                    else:
                        gaps[i].append((now - last) / len(delta))
                    last = now
                    toks.extend(delta)
            except ServerBusy as e:
                # the fleet shed this request even after the client's
                # retry budget — overload said no, and that IS the
                # answer (report it, don't crash the workload)
                shed[i] = True
                print(f"request {i} shed (retry after "
                      f"{e.retry_after_ms}ms)", flush=True)
                return
            outs[i] = toks

        t0 = time.perf_counter()
        threads = []
        for i, p in enumerate(prompts):
            rid = client.submit(
                p, budgets[i],
                request_class=classes[i] if classes else None,
                retries=args.busy_retries)
            th = threading.Thread(target=drain,
                                  args=(i, rid, time.perf_counter()))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        dt = time.perf_counter() - t0

    useful = sum(len(o) for o in outs if o)
    print(f"streamed {args.requests} requests ({useful} tokens) in "
          f"{dt:.2f}s — {useful / max(dt, 1e-9):.1f} tok/s")

    def _report(label, idx):
        tt = sorted(ttfts[i] for i in idx if outs[i] is not None)
        gp = [g for i in idx for g in gaps[i]]
        n_shed = sum(1 for i in idx if shed[i])
        if not tt:
            print(f"{label}: no completed requests"
                  + (f" ({n_shed} shed)" if n_shed else ""))
            return
        line = (f"{label}: ttft p50 {tt[len(tt) // 2] * 1e3:.0f} ms  "
                f"max {tt[-1] * 1e3:.0f} ms;  inter-token mean "
                f"{(sum(gp) / len(gp) * 1e3) if gp else 0.0:.1f} ms")
        if n_shed:
            line += f"  ({n_shed} shed)"
        print(line)

    _report("ttft", range(args.requests))
    if classes:
        for c in ("interactive", "standard", "batch"):
            idx = [i for i in range(args.requests) if classes[i] == c]
            if idx:
                _report(f"  {c} ({len(idx)} reqs)", idx)
    first = next((o for o in outs if o), [])
    print("first request tokens:", first[:12])
    return 0


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", default="tiny", choices=sorted(T.PRESETS))
    parser.add_argument("--ckpt_dir", default="",
                        help="orbax checkpoint dir (empty = random params)")
    parser.add_argument("--draft_preset", default="",
                        help="enable speculative serving with this preset "
                             "as the draft (random params unless the "
                             "target checkpoint shape matches)")
    parser.add_argument("--requests", type=int, default=12)
    parser.add_argument("--slots", type=int, default=4)
    parser.add_argument("--prompt_len", type=int, default=16)
    parser.add_argument("--max_new_tokens", type=int, default=32)
    parser.add_argument("--num_speculative", type=int, default=4)
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--top_k", type=int, default=0)
    parser.add_argument("--top_p", type=float, default=0.0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--kv_cache_dtype", default="model",
                        choices=("model", "int8"),
                        help="int8 = quantized KV cache (half the cache "
                             "HBM per slot; ~2x slots in the same memory)")
    parser.add_argument("--quantize_weights", action="store_true",
                        help="serve with weight-only int8 matmul weights "
                             "(half the weight HBM; see "
                             "models/quantize.py)")
    parser.add_argument("--attn_window", type=int, default=0,
                        help="sliding-window attention (0 = full causal)")
    parser.add_argument("--kv_cache_capacity", type=int, default=0,
                        help="rolling KV cache rows per slot (0 = "
                             "linear cache of max_len rows); requires "
                             "--attn_window, lifts the request-length "
                             "ceiling — O(capacity) memory however "
                             "long the stream")
    parser.add_argument("--warm_from", default="", metavar="HOST:PORT",
                        help="warm boot: pull content-addressed "
                             "weights peer-to-peer from a serving "
                             "replica's weights lane instead of a "
                             "storage load (falls back to "
                             "--ckpt_dir / random params on failure)")
    parser.add_argument("--listen", default="", metavar="HOST:PORT",
                        help="serve a LIVE admission queue over the "
                             "TONYS1 streaming protocol instead of the "
                             "fixed synthetic workload (with --route: "
                             "run the router front-door instead)")
    parser.add_argument("--connect", default="", metavar="HOST:PORT",
                        help="run as a streaming CLIENT against a "
                             "--listen server or router (no local "
                             "model; prints TTFT/ITL)")
    parser.add_argument("--route", default="",
                        metavar="HOST:PORT,HOST:PORT",
                        help="with --listen: route sessions across "
                             "these replica servers by queue depth "
                             "(no local model)")
    parser.add_argument("--route_decode", default="",
                        metavar="HOST:PORT,HOST:PORT",
                        help="with --route: DISAGGREGATED placement — "
                             "--route names the prefill tier, this the "
                             "decode tier (ADMIT to prefill, TOKENS "
                             "from decode)")
    parser.add_argument("--role", default="", choices=("", "prefill",
                                                       "decode"),
                        help="with --listen: run ONE tier of "
                             "disaggregated serving on this host "
                             "instead of a colocated replica")
    parser.add_argument("--disaggregate", action="store_true",
                        help="spawn prefill + decode + router locally "
                             "and stream the synthetic workload "
                             "through the split (the one-command demo; "
                             "--role is the real multi-host shape)")
    parser.add_argument("--shared_prefix_file", default="",
                        metavar="PATH",
                        help="token-id file of a shared prefix (system "
                             "prompt). Server/prefill: install its KV "
                             "template (prefix-hit admissions run only "
                             "their suffix); router: register it for "
                             "tokenized matching; client: prepend it "
                             "to every synthetic prompt (prefix-heavy "
                             "traffic)")
    parser.add_argument("--weights_version", default="",
                        help="with --listen: the weights generation "
                             "this replica advertises (HELLO/STATS). "
                             "Routers pin each session to its first "
                             "placement's version, which is what makes "
                             "drain-by-drain rolling upgrades "
                             "session-transparent (docs/serving.md "
                             "§Operating the fleet)")
    parser.add_argument("--request_class", default="",
                        choices=("", "interactive", "standard", "batch"),
                        help="with --connect: submit every request at "
                             "this QoS tier (empty = classless wire — "
                             "servers default it to standard)")
    parser.add_argument("--mixed_classes", action="store_true",
                        help="with --connect: rotate requests through "
                             "interactive/standard/batch and report "
                             "TTFT/ITL per class (the QoS demo "
                             "workload)")
    parser.add_argument("--busy_retries", type=int, default=0,
                        help="with --connect: transparent re-admissions "
                             "per request when the fleet sheds it with "
                             "BUSY (capped jittered backoff on the "
                             "server's hint)")
    parser.add_argument("--drain", default="", metavar="HOST:PORT",
                        help="with --connect to a ROUTER: fence this "
                             "replica and live-migrate every session "
                             "off it (planned maintenance), print the "
                             "summary, exit")
    parser.add_argument("--publish_prefix", default="",
                        metavar="HOST:PORT",
                        help="with --listen + --shared_prefix_file: "
                             "after installing, warm the peer replica "
                             "at this serving address in ONE template "
                             "ship over its prefix lane (the peer "
                             "recomputes nothing)")
    args = parser.parse_args()
    if args.publish_prefix and not (args.shared_prefix_file
                                    and args.listen):
        parser.error("--publish_prefix requires --listen and "
                     "--shared_prefix_file")
    if args.drain and not args.connect:
        parser.error("--drain requires --connect (a router address)")
    if (args.mixed_classes or args.request_class) and not args.connect:
        parser.error("--request_class/--mixed_classes require "
                     "--connect (they shape CLIENT traffic)")
    if args.mixed_classes and args.request_class:
        parser.error("--mixed_classes and --request_class are "
                     "mutually exclusive")

    if args.connect:
        return _run_client(args)
    if args.route or args.route_decode:
        if not args.listen:
            parser.error("--route requires --listen")
        if args.route_decode and not args.route:
            parser.error("--route_decode requires --route")
        return _run_router(args)
    if (args.role or args.disaggregate) and args.draft_preset:
        parser.error("speculative serving is not supported "
                     "disaggregated (the KV shipment carries no "
                     "draft-model cache)")
    if args.role and not args.listen:
        parser.error("--role requires --listen")

    compile_cache.enable()
    dtype = rt.platform_dtype()
    print(rt.device_line(dtype), flush=True)
    cfg = T.PRESETS[args.preset].scaled(
        dtype=dtype, remat=False,
        kv_cache_dtype=args.kv_cache_dtype,
        attn_window=args.attn_window,
        kv_cache_capacity=args.kv_cache_capacity)
    params = None
    if args.warm_from:
        from tony_tpu.serving.weightstore import pull_weights
        try:
            meta, params = pull_weights(args.warm_from)
            print(f"warm boot: pulled weights "
                  f"{meta['digest'][:12]}… from {args.warm_from}")
        except Exception as e:              # noqa: BLE001 — degrade
            print(f"warm boot from {args.warm_from} failed ({e}); "
                  f"falling back to a storage load")
    if params is None:
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        if args.ckpt_dir:
            with CheckpointManager(args.ckpt_dir) as mgr:
                from tony_tpu.models.train import (default_optimizer,
                                                   init_state)
                state = mgr.restore(
                    template=init_state(params, default_optimizer()))
            params = state["params"]
            print(f"restored step {int(state['step'])} from "
                  f"{args.ckpt_dir}")
    if args.quantize_weights:
        from tony_tpu.models.quantize import quantize_weights_int8
        params = quantize_weights_int8(params)
        print("serving with weight-only int8 matmul weights")

    if args.role == "prefill":
        return _run_prefill(args, params, cfg)

    rs = np.random.RandomState(args.seed)
    shared = (_load_prefix_tokens(args.shared_prefix_file)
              if args.shared_prefix_file else [])
    # mixed lengths and budgets — the workload shape slot reuse exists
    # for; with a shared prefix every prompt continues it
    prompts = [shared + list(rs.randint(0, cfg.vocab_size,
                                        size=args.prompt_len))
               for _ in range(args.requests)]
    budgets = [int(b) for b in
               rs.randint(max(1, args.max_new_tokens // 4),
                          args.max_new_tokens + 1, size=args.requests)]
    max_len = len(shared) + args.prompt_len + args.max_new_tokens

    kw = dict(batch=args.slots, max_len=max_len,
              temperature=args.temperature, top_k=args.top_k,
              top_p=args.top_p, seed=args.seed)
    if args.draft_preset:
        # the draft must share the target's vocabulary (speculation
        # compares token ids), so override the preset's vocab_size
        draft_cfg = T.PRESETS[args.draft_preset].scaled(
            dtype=cfg.dtype, remat=False, vocab_size=cfg.vocab_size,
            kv_cache_dtype=args.kv_cache_dtype,
            attn_window=args.attn_window)
        draft_params = T.init_params(jax.random.PRNGKey(1), draft_cfg)
        if args.quantize_weights:
            from tony_tpu.models.quantize import quantize_weights_int8
            draft_params = quantize_weights_int8(draft_params)
        batcher = SpeculativeContinuousBatcher(
            params, cfg, draft_params, draft_cfg,
            num_speculative=args.num_speculative, **kw)
    else:
        batcher = ContinuousBatcher(params, cfg, **kw)

    if args.role == "decode":
        return _run_decode(args, batcher)
    if args.disaggregate:
        return _run_disaggregate(args, params, cfg, batcher, prompts,
                                 budgets)
    if args.listen:
        return _run_server(args, batcher)

    if shared:
        # the local demo of the admission fast path: resident template,
        # suffix-only admissions, token-identical output
        from tony_tpu.serving.prefix import fingerprint
        if batcher.install_prefix(fingerprint(shared), shared):
            print(f"prefix resident locally ({len(shared)} tokens); "
                  f"prefix-hit admissions run suffix-only")

    t0 = time.perf_counter()
    outputs = batcher.serve(prompts, budgets)
    dt = time.perf_counter() - t0
    useful = sum(len(o) for o in outputs)
    mode = ("speculative " if args.draft_preset else "") + (
        "sampled" if args.temperature > 0 else "greedy")
    print(f"served {args.requests} requests ({useful} tokens) through "
          f"{args.slots} slots in {dt:.2f}s incl. compile — {mode}")
    if args.draft_preset:
        print(f"speculative rounds: {batcher.rounds_executed} "
              f"({useful / max(1, batcher.rounds_executed * args.slots):.2f}"
              f" tokens/slot-round)")
    else:
        print(f"decode steps: {batcher.steps_executed} "
              f"(slot-step utilization "
              f"{useful / max(1, batcher.steps_executed * args.slots):.2f})")
    phases = batcher.phase_times.summary()
    if phases:
        print("host phases:",
              "  ".join(f"{name} {v['total_s']:.2f}s/{v['count']}"
                        for name, v in phases.items()))
    print("first request tokens:", outputs[0][:12])
    return 0


if __name__ == "__main__":
    sys.exit(main())
