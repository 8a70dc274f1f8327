"""Pipeline parallelism: in-slice GPipe/1F1B over ``pp``, and
cross-slice MPMD 1F1B over DCN tensor channels.

Green-field for the TPU build (SURVEY.md §2.3: PP absent from the reference).
Stages live on different devices along the mesh's ``pp`` axis; activations
hop stage→stage with ``lax.ppermute`` (point-to-point, so pp tolerates DCN).

Two schedules:

* **GPipe** (:func:`pipeline_apply`, the default): all-forward-then-backward.
  With S stages and M microbatches the loop runs M+S-1 ticks, bubble
  (S-1)/(M+S-1). Differentiable end-to-end — AD through scan+ppermute yields
  the reverse schedule automatically — which is what makes it drop into any
  ``jax.grad`` without ceremony. The cost is activation memory: the scan
  holds every tick's stage input for the backward, O(M) microbatch
  activations per device.

* **1F1B** (:func:`pipeline_value_and_grad`): each stage starts microbatch
  backwards as soon as the last stage has consumed that microbatch, so at
  most S microbatch activations are ever live per device — O(S) instead of
  O(M), the schedule that lets deep pipelines scale M for bubble without
  scaling memory. The price of starting backwards early is that the loss
  must be computed per microbatch INSIDE the pipeline (at the last stage),
  so this entry point takes the loss head and returns gradients explicitly
  rather than being differentiated through. Backward ticks recompute the
  stage forward from the saved input (one extra forward per microbatch —
  the same trade a remat'd GPipe stage makes).

* **Cross-slice 1F1B** (:class:`CrossSlicePipeline`): the MPMD variant —
  each STAGE runs in its own gang (its own process set, its own slice's
  ICI domain), with activations and cotangents hopping between gangs
  over the typed DCN tensor channels (``tony_tpu.channels``) instead of
  ``lax.ppermute``. The host drives the same non-interleaved 1F1B
  schedule per stage; channel send/recv threads keep microbatch m±1's
  transport in flight while microbatch m computes on the devices — the
  same overlap discipline as the DevicePrefetcher. This is what trains
  models that don't fit one slice's ICI domain.

Constraint (both schedules): the stage function must map activations to
activations of the same shape/dtype (natural for transformer blocks).
Per-stage params are stacked on a leading [S, ...] axis, sharded P("pp") —
each device reads only its own stage's slice.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_local(stage_params: Any, microbatches: jax.Array, *,
                    stage_fn: Callable[[Any, jax.Array], Any],
                    axis_name: str, with_aux: bool,
                    batch_axes: tuple[str, ...]) -> Any:
    """Per-device pipeline body (inside shard_map over ``axis_name``).

    stage_params: this stage's params (leading [1, ...] shard dim squeezed).
    microbatches: [M, mb, ...] — replicated input; stage 0 consumes it.
    Returns [M, mb, ...] final-stage outputs, replicated via psum; with
    ``with_aux`` the stage_fn returns (out, scalar) and the scalar is
    accumulated over VALID ticks only (warmup/drain ticks run the stage on
    garbage state whose aux must not count), summed over stages, and
    averaged over the batch axes.
    """
    s = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    params = jax.tree.map(lambda x: x[0], stage_params)
    m = microbatches.shape[0]
    state = jnp.zeros_like(microbatches[0])
    outputs = jnp.zeros_like(microbatches)
    aux0 = jnp.zeros((), jnp.float32)
    shift = [(i, (i + 1) % s) for i in range(s)]

    def tick(carry, t):
        state, outputs, aux_acc = carry
        # stage 0 ingests microbatch t while t < M; later stages use the
        # activation that arrived from the previous stage last tick
        inp = jnp.where(stage == 0, microbatches[jnp.minimum(t, m - 1)], state)
        res = stage_fn(params, inp)
        out, aux = res if with_aux else (res, aux0)
        # stage s processes microbatch t-s at tick t; anything else is
        # pipeline bubble running on zeros/garbage
        valid = jnp.logical_and(t - stage >= 0, t - stage < m)
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        # the final stage finishes microbatch t-(S-1) at tick t
        widx = t - (s - 1)
        take = jnp.logical_and(stage == s - 1, widx >= 0)
        slot = jnp.clip(widx, 0, m - 1)
        outputs = outputs.at[slot].set(
            jnp.where(take, out, outputs[slot]))
        state = lax.ppermute(out, axis_name, shift)
        return (state, outputs, aux_acc), None

    (_, outputs, aux_acc), _ = lax.scan(
        tick, (state, outputs, aux0), jnp.arange(m + s - 1, dtype=jnp.int32))
    # only the last stage holds real outputs; broadcast around the ring so
    # the result is replicated over pp (out_spec P() below)
    mask = (stage == s - 1).astype(outputs.dtype)
    outputs = lax.psum(outputs * mask, axis_name)
    if not with_aux:
        return outputs
    # stages sum (each holds different layers), microbatches average (the
    # /m outside), batch shards average — replicated on every device
    aux_acc = lax.psum(aux_acc, axis_name)
    for a in batch_axes:
        aux_acc = lax.pmean(aux_acc, a)
    return outputs, aux_acc


def _pipeline_1f1b_local(stage_params: Any, head_params: Any,
                         microbatches: jax.Array, head_batches: Any, *,
                         stage_fn: Callable[[Any, jax.Array], jax.Array],
                         loss_head: Callable[[Any, jax.Array, Any],
                                             jax.Array],
                         axis_name: str,
                         batch_axes: tuple[str, ...],
                         head_specs: Any = None,
                         stage_specs: Any = None,
                         head_reduce_axes: tuple[str, ...] = (),
                         with_aux: bool = False,
                         aux_weight: float = 0.0) -> tuple:
    """Per-device 1F1B body (inside shard_map over ``axis_name``).

    The Megatron non-interleaved schedule in closed form — for stage s of
    S with warmup w(s) = S-1-s, microbatch i runs::

        forward  at tick s + i          (i < w: pipeline warmup)
                 at tick 2i + s         (steady 1F1B cadence)
        backward at tick 2S - 1 - s + 2i

    over T = 2M + 2S - 2 ticks. Forward ticks save ONLY the stage input
    into a depth-S ring (in-flight microbatches per stage never exceed
    S - s); backward ticks re-run the stage forward under ``jax.vjp`` from
    that input (remat-style) and produce param grads plus the input
    cotangent. The last stage seeds its backward from ``loss_head``
    directly — no output cotangent ever enters from outside, which is
    precisely what lets backwards start before the full batch has been
    forwarded. Activations hop forward and cotangents hop backward via
    ppermute OUTSIDE the scheduling conds (collectives must execute
    uniformly on every device every tick; unscheduled devices ship
    zeros that are never read — the closed forms above guarantee a
    consumer tick always directly follows a producer tick).

    Returns (loss_sum, stage_grads, head_grads, dxs) — per-device, not
    yet reduced: loss_sum/head_grads live on the last stage, dxs on stage
    0, stage_grads on their own stage.
    """
    s_count = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    params = jax.tree.map(lambda v: v[0], stage_params)
    m = microbatches.shape[0]
    n_ticks = 2 * m + 2 * s_count - 2

    def fwd_sched(sg, t):
        """(does stage ``sg`` forward at tick ``t``, which microbatch)."""
        w = s_count - 1 - sg
        ts = t - sg
        has = (ts >= 0) & (
            (ts < jnp.minimum(w, m))
            | ((ts % 2 == 0) & (ts // 2 >= w) & (ts // 2 < m)))
        idx = jnp.clip(jnp.where(ts < w, ts, ts // 2), 0, m - 1)
        return has, idx

    carry0 = (
        jnp.zeros_like(microbatches[0]),                 # fwd_state (wire)
        jnp.zeros_like(microbatches[0]),                 # cot_state (wire)
        jnp.zeros((s_count,) + microbatches.shape[1:],
                  microbatches.dtype),                   # in_buf ring
        jnp.zeros((s_count,) + microbatches.shape[1:],
                  microbatches.dtype),                   # resid ring
        jnp.zeros_like(microbatches),                    # dxs (stage 0)
        jax.tree.map(jnp.zeros_like, params),            # stage grads
        jax.tree.map(jnp.zeros_like, head_params),       # head grads
        jnp.zeros((), jnp.float32),                      # loss sum
    )

    def tick(carry, t):
        (fwd_state, cot_state, in_buf, resid, dxs, grads, hgrads,
         loss_acc) = carry
        has_fwd, fwd_i = fwd_sched(stage, t)
        u = t - (2 * s_count - 1 - stage)
        has_bwd = (u >= 0) & (u % 2 == 0) & (u // 2 < m)
        bwd_i = jnp.clip(u // 2, 0, m - 1)

        # file the arrival: the activation on the wire was sent by the
        # previous stage's forward LAST tick; at warmup->steady boundaries
        # its consumption tick here lags the arrival by more than one
        # tick, so a bare register would be overwritten by later sends —
        # the ring holds each microbatch until this stage's schedule
        # reaches it (in-flight never exceeds S, same bound as resid)
        has_in, in_i = fwd_sched((stage - 1) % s_count, t - 1)
        has_in = has_in & (t >= 1)
        in_buf = in_buf.at[in_i % s_count].set(
            jnp.where(has_in, fwd_state, in_buf[in_i % s_count]))

        inp = jnp.where(stage == 0, microbatches[fwd_i],
                        in_buf[fwd_i % s_count])

        def fwd_branch(resid):
            out = stage_fn(params, inp)
            if with_aux:
                out = out[0]        # aux re-derived in the backward tick
            return out, resid.at[fwd_i % s_count].set(inp)

        def fwd_noop(resid):
            return jnp.zeros_like(fwd_state), resid

        out, resid = lax.cond(has_fwd, fwd_branch, fwd_noop, resid)

        saved = resid[bwd_i % s_count]
        head_mb = jax.tree.map(lambda a: a[bwd_i], head_batches)

        def bwd_branch(op):
            grads, hgrads, dxs, loss_acc = op

            def last_case(_):
                # aux-path gradients are REPLICATED across the head's
                # reduce axes (every rank computes the full aux), while
                # CE-path gradients are per-rank partials (psum_rep in
                # the loss head) — the reductions below psum BOTH, so
                # the aux seed pre-divides by the reduce-axes product to
                # come out exact; the reported loss re-applies the true
                # weight via the vjp's aux output
                denom = 1
                for _ax in head_reduce_axes:
                    denom = denom * lax.axis_size(_ax)
                w_eff = aux_weight / denom

                def last_fn(p, hp, x):
                    res = stage_fn(p, x)
                    if with_aux:
                        out, aux = res
                        return (loss_head(hp, out, head_mb)
                                + w_eff * aux), aux
                    return loss_head(hp, res, head_mb), jnp.zeros(())
                lval, vjp_fn, aux_v = jax.vjp(last_fn, params, head_params,
                                              saved, has_aux=True)
                lval = lval + (aux_weight - w_eff) * aux_v.astype(
                    lval.dtype)
                dp, dhp, dinp = vjp_fn(jnp.ones((), lval.dtype))
                # head sharded over head_reduce_axes (tp-vocab shards):
                # each rank's vjp yields the PARTIAL cotangents from its
                # vocab slice's loss paths — the activation cotangent and
                # any head leaf replicated over those axes must sum
                # across them (sharded leaves own their slice's grads
                # outright). Safe inside the conds: the predicates are
                # uniform across the reduce axes, so all participants
                # enter together.
                def _reduce_tree(grads_tree, specs_tree, ax):
                    """psum ``grads_tree`` leaves over ``ax`` EXCEPT those
                    whose spec already shards over ``ax`` (they own their
                    slice's grads outright). Specs are zipped by hand: P
                    is a tuple subclass tree.map would descend into, and
                    a bare None leaf (valid replicated spec) vanishes
                    from tree_leaves without the is_leaf."""
                    flat_g, td = jax.tree_util.tree_flatten(grads_tree)
                    flat_s = jax.tree_util.tree_leaves(
                        specs_tree,
                        is_leaf=lambda x: x is None or isinstance(x, P))

                    def _reduce(g, spec):
                        named = set()
                        for entry in (tuple(spec) if spec is not None
                                      else ()):
                            if isinstance(entry, (tuple, list)):
                                named.update(entry)
                            elif entry is not None:
                                named.add(entry)
                        return g if ax in named else lax.psum(g, ax)

                    return td.unflatten(
                        [_reduce(g, s) for g, s in zip(flat_g, flat_s)])

                for ax in head_reduce_axes:
                    dinp = lax.psum(dinp, ax)
                    # the last STAGE's params also sit upstream of the
                    # partitioned loss paths — their partials sum too,
                    # spec-aware like the head's (an ax-sharded stage
                    # leaf owns its slice)
                    dp = _reduce_tree(dp, stage_specs, ax)
                    dhp = _reduce_tree(dhp, head_specs, ax)
                return dp, dhp, dinp, lval.astype(jnp.float32)

            def mid_case(_):
                if with_aux:
                    (out2, aux_v), vjp_fn = jax.vjp(
                        lambda p, x: stage_fn(p, x), params, saved)
                    # seed the aux cotangent with its loss weight: one
                    # vjp covers both the activation path and the
                    # stage-local aux-loss path
                    dp, dinp = vjp_fn(
                        (cot_state, jnp.asarray(aux_weight, aux_v.dtype)))
                    lval = (aux_weight * aux_v).astype(jnp.float32)
                else:
                    out2, vjp_fn = jax.vjp(
                        lambda p, x: stage_fn(p, x), params, saved)
                    dp, dinp = vjp_fn(cot_state)
                    lval = jnp.zeros((), jnp.float32)
                return (dp, jax.tree.map(jnp.zeros_like, head_params),
                        dinp, lval)

            dp, dhp, dinp, lval = lax.cond(stage == s_count - 1,
                                           last_case, mid_case, None)
            grads = jax.tree.map(jnp.add, grads, dp)
            hgrads = jax.tree.map(jnp.add, hgrads, dhp)
            # dxs is only meaningful on stage 0 (masked at the end)
            dxs = dxs.at[bwd_i].set(
                jnp.where(stage == 0, dinp, dxs[bwd_i]))
            return (grads, hgrads, dxs, loss_acc + lval), dinp

        def bwd_noop(op):
            return op, jnp.zeros_like(cot_state)

        (grads, hgrads, dxs, loss_acc), dinp = lax.cond(
            has_bwd, bwd_branch, bwd_noop, (grads, hgrads, dxs, loss_acc))

        shift_f = [(i, (i + 1) % s_count) for i in range(s_count)]
        shift_b = [(i, (i - 1) % s_count) for i in range(s_count)]
        fwd_state = lax.ppermute(out, axis_name, shift_f)
        cot_state = lax.ppermute(dinp, axis_name, shift_b)
        return (fwd_state, cot_state, in_buf, resid, dxs, grads, hgrads,
                loss_acc), None

    carry, _ = lax.scan(tick, carry0,
                        jnp.arange(n_ticks, dtype=jnp.int32))
    _, _, _, _, dxs, grads, hgrads, loss_acc = carry

    last = (stage == s_count - 1)
    # every stage contributes to loss_acc (mid stages their weighted aux,
    # the last stage CE + aux) — plain psum over pp sums them exactly once
    loss = lax.psum(loss_acc, axis_name) / m
    hgrads = jax.tree.map(
        lambda g: lax.psum(jnp.where(last, g, jnp.zeros_like(g)),
                           axis_name), hgrads)
    first = (stage == 0)
    dxs = jax.tree.map(
        lambda g: lax.psum(jnp.where(first, g, jnp.zeros_like(g)),
                           axis_name), dxs)
    # reduce over the data axes: params (and the head) are replicated
    # across dp/fsdp, so their grads average; loss averages; dx is the
    # cotangent of THIS shard's tokens — scaled, not summed
    d_total = 1
    for a in batch_axes:
        d_total *= lax.axis_size(a)
        loss = lax.pmean(loss, a)
        grads = jax.tree.map(lambda g, _a=a: lax.pmean(g, _a), grads)
        hgrads = jax.tree.map(lambda g, _a=a: lax.pmean(g, _a), hgrads)
    grads = jax.tree.map(lambda g: g[None] / m, grads)
    hgrads = jax.tree.map(lambda g: g / m, hgrads)
    dxs = dxs / (m * d_total)
    return loss, grads, hgrads, dxs


def pipeline_value_and_grad(stage_fn: Callable[[Any, jax.Array], jax.Array],
                            stacked_params: Any, x: jax.Array,
                            head_params: Any, head_batch: Any, mesh: Mesh,
                            *, loss_head: Callable[[Any, jax.Array, Any],
                                                   jax.Array],
                            num_microbatches: int, axis_name: str = "pp",
                            batch_axes: tuple[str, ...] = ("dp", "fsdp"),
                            param_specs: Any = None,
                            head_specs: Any = None,
                            head_reduce_axes: tuple[str, ...] = (),
                            with_aux: bool = False,
                            aux_weight: float = 0.0):
    """1F1B pipeline: loss AND gradients in one schedule.

    Same stage contract as :func:`pipeline_apply` (stacked [S, ...]
    params, shape-preserving ``stage_fn``), plus the loss head the last
    stage applies per microbatch: ``loss_head(head_params, out_mb,
    head_batch_mb) -> scalar`` — the mean loss of the LOCAL microbatch
    shard (so the global loss is exactly the mean of per-microbatch means;
    with masked losses this matches a single global mean only when every
    microbatch shard has the same mask count — the standard 1F1B
    normalization trade).

    ``head_batch``: pytree with leading batch dim [B, ...] (targets etc.),
    microbatched and delivered to ``loss_head`` alongside the activations.

    Returns ``(loss, stage_grads, head_grads, dx)`` where stage_grads
    matches ``stacked_params``, head_grads matches ``head_params``, and
    dx is d(loss)/dx — feed it to the caller's vjp of whatever produced x
    (the embedding) to complete the parameter gradients.

    Activation memory is O(S) microbatches per device (vs GPipe's O(M));
    each microbatch pays one extra stage forward (remat-style recompute in
    the backward tick). Not differentiable through — it IS the
    differentiation.

    ``head_specs`` / ``head_reduce_axes``: by default head_params (and
    their gradients) replicate on every device (in_specs P()) — the
    loss head runs inside the shard_map's Manual context, where GSPMD
    sharding constraints cannot reach. To SHARD the head (a big lm_head
    over tp), pass per-leaf ``head_specs`` and name the sharding axes in
    ``head_reduce_axes``; ``loss_head`` must then combine across those
    axes itself (distributed logsumexp etc. — psum/pmax over the axis
    names), and the pipeline psums the activation cotangent plus any
    still-replicated head leaves' grads across them (sharded leaves own
    their slice's grads). transformer.lm_value_and_grad wires this up
    for the vocab-sharded lm_head.
    """
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible into "
                         f"{num_microbatches} microbatches")
    num_stages = jax.tree.leaves(stacked_params)[0].shape[0]
    m = num_microbatches
    mb = b // m
    xs = x.reshape((m, mb) + x.shape[1:])
    head_xs = jax.tree.map(
        lambda a: a.reshape((m, mb) + a.shape[1:]), head_batch)

    if axis_name not in mesh.shape or mesh.shape[axis_name] == 1:
        # degenerate: no pp axis — same value/grad contract via plain AD
        def total(sp, hp, xs):
            def body(h, p):
                if with_aux:
                    out, aux = stage_fn(p, h)
                    return out, aux
                return stage_fn(p, h), None

            def one_mb(xmb, hmb):
                out, auxes = lax.scan(body, xmb, sp)
                loss = loss_head(hp, out, hmb)
                if with_aux:
                    loss = loss + aux_weight * auxes.sum()
                return loss

            losses = jax.vmap(one_mb)(xs, head_xs)
            return losses.mean()

        (loss, (g_sp, g_hp, g_xs)) = jax.value_and_grad(
            total, argnums=(0, 1, 2))(stacked_params, head_params, xs)
        return loss, g_sp, g_hp, g_xs.reshape(x.shape)

    pp = mesh.shape[axis_name]
    if num_stages != pp:
        raise ValueError(f"{num_stages} stacked stages but pp axis has "
                         f"{pp} ranks — need exactly one stage per rank")
    live = tuple(a for a in batch_axes
                 if a in mesh.shape and mesh.shape[a] > 1)
    data_spec = P(None, live if len(live) > 1 else (live[0] if live else None))
    if param_specs is None:
        param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    if head_specs is None:
        head_specs = jax.tree.map(lambda _: P(), head_params)
    fn = functools.partial(_pipeline_1f1b_local, stage_fn=stage_fn,
                           loss_head=loss_head, axis_name=axis_name,
                           batch_axes=live, head_specs=head_specs,
                           stage_specs=param_specs,
                           head_reduce_axes=head_reduce_axes,
                           with_aux=with_aux, aux_weight=aux_weight)
    loss, g_sp, g_hp, g_xs = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(param_specs, head_specs, data_spec, data_spec),
        out_specs=(P(), param_specs, head_specs, data_spec),
        check_vma=False)(stacked_params, head_params, xs, head_xs)
    return loss, g_sp, g_hp, g_xs.reshape(x.shape)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], Any],
                   stacked_params: Any, x: jax.Array, mesh: Mesh, *,
                   num_microbatches: int, axis_name: str = "pp",
                   batch_axes: tuple[str, ...] = ("dp", "fsdp"),
                   with_aux: bool = False,
                   param_specs: Any = None):
    """Run x through S pipeline stages of ``stage_fn``.

    stacked_params: pytree whose leaves lead with the stage axis [S, ...];
    S must equal the ``pp`` mesh axis size (one stage per pp rank).
    x: [B, ...] global batch; must divide into ``num_microbatches``; the
    microbatch dim stays sharded over the live batch axes (dp/fsdp).
    Returns [B, ...] outputs (replicated over pp).

    ``with_aux``: stage_fn returns (out, scalar); the scalars from valid
    (non-bubble) ticks sum over stages and average over microbatches and
    batch shards — the MoE load-balance loss channel; returns (out, aux).
    ``param_specs``: override the default P(pp) per-leaf placement — how
    MoE expert weights additionally shard over ``ep`` inside the stage
    (leaves then arrive in the body already sliced to the rank's experts).
    """
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible into "
                         f"{num_microbatches} microbatches")
    num_stages = jax.tree.leaves(stacked_params)[0].shape[0]

    if axis_name not in mesh.shape or mesh.shape[axis_name] == 1:
        # degenerate: no pp axis — run stages sequentially via scan
        if with_aux:
            def body_aux(carry, p):
                h, acc = carry
                h, aux = stage_fn(p, h)
                return (h, acc + aux), None
            (out, aux), _ = lax.scan(
                body_aux, (x, jnp.zeros((), jnp.float32)), stacked_params)
            return out, aux

        def body(h, p):
            return stage_fn(p, h), None
        out, _ = lax.scan(body, x, stacked_params)
        return out

    pp = mesh.shape[axis_name]
    if num_stages != pp:
        raise ValueError(f"{num_stages} stacked stages but pp axis has "
                         f"{pp} ranks — need exactly one stage per rank")
    mb = b // num_microbatches
    xs = x.reshape((num_microbatches, mb) + x.shape[1:])

    live = tuple(a for a in batch_axes
                 if a in mesh.shape and mesh.shape[a] > 1)
    data_spec = P(None, live if len(live) > 1 else (live[0] if live else None))
    if param_specs is None:
        param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    fn = functools.partial(_pipeline_local, stage_fn=stage_fn,
                           axis_name=axis_name, with_aux=with_aux,
                           batch_axes=live)
    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(param_specs, data_spec),
        out_specs=(data_spec, P()) if with_aux else data_spec,
        check_vma=False)(stacked_params, xs)
    if with_aux:
        out, aux = out
        # microbatches average: each tick's aux is a per-microbatch mean
        return out.reshape((b,) + out.shape[2:]), aux / num_microbatches
    return out.reshape((b,) + out.shape[2:])


# ---------------------------------------------------------------------------
# Cross-slice MPMD 1F1B over DCN channels
# ---------------------------------------------------------------------------
class CrossSlicePipeline:
    """Host-driven 1F1B for ONE stage gang of an MPMD pipeline.

    Each stage gang constructs one of these around its OWN (unstacked)
    stage function and its :class:`~tony_tpu.channels.StageLinks`; the
    coordinated effect of every gang running :meth:`value_and_grad` on
    the same microbatch count is exactly the Megatron non-interleaved
    1F1B schedule, spread across slices:

    - stage ``s`` runs ``min(S-1-s, M)`` warmup forwards, then steady
      forward/backward pairs, then drains its remaining backwards;
    - activations flow to ``stage+1`` and cotangents back to ``stage-1``
      over the links' channels. Sends enqueue into the sender's bounded
      window and return — DCN transport of microbatch m±1 overlaps the
      device compute of microbatch m (``sync_transport=True`` defeats
      that on purpose: the serialized baseline the bench contrasts).
    - backward ticks recompute the stage forward from the saved input
      under ``jax.vjp`` (remat-style), the same per-microbatch math as
      the in-slice schedule — loss and gradients are BIT-IDENTICAL to
      :func:`pipeline_value_and_grad` on the same params/microbatches
      (test-pinned), so moving a model across slices never changes what
      it learns.

    The LAST stage owns the loss head: its backward seeds from
    ``loss_head(head_params, stage_fn(params, x), head_mb)`` directly.
    Activation memory is O(in-flight) = O(S - stage) microbatches per
    stage, the 1F1B bound. ``with_aux`` stage functions are not
    supported cross-slice yet (MoE balance losses stay in-slice).

    **Interleaved/looped 1F1B** (``links.interleave`` = v > 1): the gang
    holds v model CHUNKS, chunk j acting as virtual stage ``j*S + s`` of
    a V = S*v deep pipeline (the Megatron looping placement — every
    chunk boundary crosses gangs, over the links' per-chunk ring lanes).
    Each chunk runs its own projection of the V-stage 1F1B schedule in
    its own host thread; a per-gang device lock keeps the compute
    serialization honest, so the win is pure bubble shrink (~1/v) plus
    more DCN transfers in flight per tick. ``value_and_grad`` then takes
    ``params`` as a LIST of v per-chunk pytrees and returns grads the
    same shape; chunk j's math is bit-identical to virtual stage
    ``j*S+s`` of the non-interleaved V-stage schedule (test-pinned).

    Observability: per-call wall and bubble fraction land in the default
    registry (``tony_pipeline_step_seconds``,
    ``tony_pipeline_bubble_fraction{stage=}``), alongside the channels'
    own send/recv walls and queue depths.
    """

    def __init__(self, stage_fn: Callable[[Any, jax.Array], jax.Array],
                 links, *,
                 loss_head: Callable[[Any, jax.Array, Any], jax.Array]
                 | None = None,
                 lookahead: int = 0,
                 sync_transport: bool = False,
                 send_timeout_s: float | None = 120.0,
                 recv_timeout_s: float | None = 120.0,
                 registry=None) -> None:
        from tony_tpu.runtime import metrics as metrics_mod
        from tony_tpu.runtime import tracing
        self._tracing = tracing
        self._tracer = tracing.get_tracer()
        # Deterministic per-step trace ids: every stage gang derives the
        # SAME 128-bit id from the job trace (TONY_TRACE_CTX) + its own
        # call ordinal — one microbatch's journey across gangs
        # reconstructs under one trace id with NO new channel frames
        # (the channel seq tags the hops). The session id + cluster
        # epoch join the seed so a RELAUNCHED trainer (stop-the-world
        # retry resuming from checkpoint, ordinal back at 1) can never
        # re-mint a previous generation's ids — every value here is
        # identical across the stage gangs of one generation.
        import os as _os
        from tony_tpu import constants as _c
        _job_ctx = tracing.parse_env_ctx()
        self._trace_seed = (
            (_job_ctx["tid"] if _job_ctx else "local-pipeline")
            + f":{_os.environ.get(_c.SESSION_ID, '0')}"
            + f":{_os.environ.get(_c.CLUSTER_EPOCH, '0')}")
        self._calls = 0
        self.links = links
        self.stage = links.stage
        self.num_stages = links.num_stages
        #: extra in-flight microbatches beyond the 1F1B minimum: each
        #: stage runs that many more warmup forwards, so activations
        #: already in flight cover the DCN round trip instead of the
        #: backward stalling on it every microbatch (the MPMD-paper
        #: latency-tolerance knob). Costs ``lookahead`` extra saved
        #: microbatch inputs of memory per stage; the accumulation ORDER
        #: of backwards never changes, so results stay bit-identical at
        #: any value.
        self.lookahead = lookahead
        self.sync_transport = sync_transport
        self.send_timeout_s = send_timeout_s
        self.recv_timeout_s = recv_timeout_s
        #: virtual stages per gang (looping placement); 1 = classic
        self.interleave = getattr(links, "interleave", 1) or 1
        self.num_virtual = self.num_stages * self.interleave
        # one device per gang: chunk threads must not interleave their
        # compute dispatches (the lock also keeps busy accounting honest)
        self._device_lock = threading.Lock()
        if links.is_last and loss_head is None:
            raise ValueError("the last stage needs the loss head")
        self._fwd = jax.jit(stage_fn)

        def _bwd(params, saved, cot):
            _, vjp_fn = jax.vjp(lambda p, x: stage_fn(p, x), params, saved)
            return vjp_fn(cot)
        self._bwd = jax.jit(_bwd)

        def _last(params, head_params, saved, head_mb):
            def last_fn(p, hp, x):
                return loss_head(hp, stage_fn(p, x), head_mb)
            lval, vjp_fn = jax.vjp(last_fn, params, head_params, saved)
            dp, dhp, dx = vjp_fn(jnp.ones((), lval.dtype))
            return lval.astype(jnp.float32), dp, dhp, dx
        self._last = jax.jit(_last) if links.is_last else None
        reg = registry if registry is not None \
            else metrics_mod.get_default()
        self._step_hist = reg.histogram(
            "tony_pipeline_step_seconds",
            help="wall seconds per cross-slice 1F1B value_and_grad call",
            stage=str(self.stage))
        self._bubble_gauge = reg.gauge(
            "tony_pipeline_bubble_fraction",
            help="1 - device-busy/wall for the last 1F1B call (this "
                 "stage's pipeline bubble + transport stall share)",
            stage=str(self.stage))
        self._mb_counter = reg.counter(
            "tony_pipeline_microbatches_total",
            help="microbatches processed by this stage",
            stage=str(self.stage))

    # The two compute entry points are methods so instrumentation (and
    # the bench's deterministic compute stand-in) can wrap them.
    def _forward_compute(self, params, x):
        return self._fwd(params, x)

    def _backward_compute(self, params, saved, cot):
        return self._bwd(params, saved, cot)

    def _last_compute(self, params, head_params, saved, head_mb):
        return self._last(params, head_params, saved, head_mb)

    def value_and_grad(self, params, *, num_microbatches: int,
                       microbatches: jax.Array | None = None,
                       head_params: Any = None, head_batches: Any = None):
        """Run one global batch through this stage's share of the 1F1B
        schedule; every stage gang must call this with the same
        ``num_microbatches``.

        - stage 0 supplies ``microbatches`` ([M, mb, ...]; later stages
          receive activations off the wire);
        - the last stage supplies ``head_params`` + ``head_batches``
          (pytree with leading [M, mb, ...] batch dims).

        Returns ``(loss, grads, head_grads, dxs)``: ``loss`` (f32
        scalar) and ``head_grads`` are non-None only on the last stage,
        ``dxs`` ([M, mb, ...] input cotangents) only on stage 0;
        ``grads`` matches ``params`` everywhere.

        With ``interleave`` = v > 1, ``params`` is a LIST of v per-chunk
        pytrees and ``grads`` comes back the same shape; the loss head
        fires on the LAST gang (its last chunk is virtual stage V-1) and
        ``dxs`` on the first (its chunk 0 is virtual stage 0).
        """
        import numpy as np

        if self.interleave > 1:
            return self._value_and_grad_interleaved(
                params, num_microbatches=num_microbatches,
                microbatches=microbatches, head_params=head_params,
                head_batches=head_batches)
        links = self.links
        m = num_microbatches
        if links.is_first:
            if microbatches is None:
                raise ValueError("stage 0 must supply microbatches")
            if microbatches.shape[0] != m:
                raise ValueError(
                    f"microbatches leading dim {microbatches.shape[0]} != "
                    f"num_microbatches {m}")
        if links.is_last and (head_batches is None or head_params is None):
            raise ValueError("the last stage must supply head_params and "
                             "head_batches")
        self._calls += 1
        step_tid = self._tracing.deterministic_trace_id(
            f"{self._trace_seed}:step:{self._calls}")
        root_sid = self._tracing.deterministic_span_id(
            f"{step_tid}:root")
        stage_sid = self._tracing.deterministic_span_id(
            f"{step_tid}:s{self.stage}")
        traced = (self._tracer.enabled
                  and self._tracing.deterministic_sample(
                      step_tid, self._tracer.sample_rate))
        t_start = time.perf_counter()
        busy = 0.0
        saved: dict[int, jax.Array] = {}
        grads = jax.tree.map(jnp.zeros_like, params)
        hgrads = (jax.tree.map(jnp.zeros_like, head_params)
                  if links.is_last else None)
        loss_acc = jnp.zeros((), jnp.float32) if links.is_last else None
        dx_list: list[jax.Array] = []

        def _send(sender, arr):
            return sender.send(np.asarray(arr), sync=self.sync_transport,
                               timeout=self.send_timeout_s)

        def _mb_span(name: str, i: int, t0: float, seq_in: int,
                     seq_out: int) -> None:
            # per-microbatch span under this stage's step span: the hop
            # seq(s) let a cross-gang reader stitch microbatch i's
            # journey (sender and receiver tag the SAME seq)
            attrs = {"stage": self.stage, "mb": i}
            if seq_in >= 0:
                attrs["seq"] = seq_in
            if seq_out >= 0:
                attrs.setdefault("seq", seq_out)
                attrs["seq_out"] = seq_out
            self._tracer.record_span(
                name, time.perf_counter() - t0, trace_id=step_tid,
                parent_id=stage_sid, **attrs)

        def do_forward(i: int) -> None:
            nonlocal busy
            tmb = time.perf_counter()
            seq_in = seq_out = -1
            if links.is_first:
                x = microbatches[i]
            else:
                x = jnp.asarray(links.act_in.recv(self.recv_timeout_s))
                seq_in = links.act_in.last_seq
            saved[i] = x
            if links.is_last:
                if traced:
                    _mb_span("pipeline.forward", i, tmb, seq_in, seq_out)
                return      # the last stage folds its forward into _last
            t0 = time.perf_counter()
            out = self._forward_compute(params, x)
            out_host = np.asarray(out)      # device sync: compute wall ends
            busy += time.perf_counter() - t0
            seq_out = _send(links.act_out, out_host)
            if traced:
                _mb_span("pipeline.forward", i, tmb, seq_in, seq_out)

        def do_backward(i: int) -> None:
            nonlocal busy, grads, hgrads, loss_acc
            tmb = time.perf_counter()
            seq_in = seq_out = -1
            if links.is_last:
                head_mb = jax.tree.map(lambda a: a[i], head_batches)
                t0 = time.perf_counter()
                lval, dp, dhp, dx = self._last_compute(
                    params, head_params, saved.pop(i), head_mb)
                loss_acc = loss_acc + lval
                grads = jax.tree.map(jnp.add, grads, dp)
                hgrads = jax.tree.map(jnp.add, hgrads, dhp)
                dx_host = np.asarray(dx)
                busy += time.perf_counter() - t0
            else:
                cot = jnp.asarray(links.grad_in.recv(self.recv_timeout_s))
                seq_in = links.grad_in.last_seq
                t0 = time.perf_counter()
                dp, dx = self._backward_compute(params, saved.pop(i), cot)
                grads = jax.tree.map(jnp.add, grads, dp)
                dx_host = np.asarray(dx)
                busy += time.perf_counter() - t0
            if links.is_first:
                dx_list.append(jnp.asarray(dx_host))
            else:
                seq_out = _send(links.grad_out, dx_host)
            if traced:
                _mb_span("pipeline.backward", i, tmb, seq_in, seq_out)
            self._mb_counter.inc()

        # the non-interleaved 1F1B schedule in host form: warmup
        # forwards, steady F/B pairs, backward drain
        warmup = min(self.num_stages - 1 - self.stage + self.lookahead, m)
        for i in range(warmup):
            do_forward(i)
        for i in range(m):
            j = i + warmup
            if j < m:
                do_forward(j)
            do_backward(i)

        grads = jax.tree.map(lambda g: g / m, grads)
        loss = None
        if links.is_last:
            loss = loss_acc / m
            hgrads = jax.tree.map(lambda g: g / m, hgrads)
        dxs = jnp.stack(dx_list) / m if links.is_first else None
        wall = time.perf_counter() - t_start
        self._step_hist.observe(wall)
        bubble = max(0.0, 1.0 - busy / wall) if wall > 0 else 0.0
        self._bubble_gauge.set(bubble)
        if traced:
            # this stage's step span (deterministic span id, parented on
            # the shared per-step root so every gang's spans nest under
            # ONE trace); stage 0 also emits the root itself
            self._tracer.record_span(
                "pipeline.stage", wall, trace_id=step_tid,
                span_id=stage_sid, parent_id=root_sid,
                stage=self.stage, microbatches=m,
                bubble=round(bubble, 4))
            if links.is_first:
                self._tracer.record_span(
                    "pipeline.step", wall, trace_id=step_tid,
                    span_id=root_sid, step=self._calls,
                    num_stages=self.num_stages, microbatches=m)
        return loss, grads, hgrads, dxs

    def _value_and_grad_interleaved(self, params_list, *,
                                    num_microbatches: int,
                                    microbatches=None,
                                    head_params=None, head_batches=None):
        """The interleaved schedule: chunk j is virtual stage
        ``g = j*S + s`` of the V-stage pipeline, driven by its own host
        thread running exactly the per-stage projection of the V-stage
        non-interleaved 1F1B schedule (warmup ``min(V-1-g+lookahead, m)``
        forwards, then F/B pairs). Recvs block on the per-chunk lanes, so
        global ordering emerges from dataflow — no cross-gang clock.
        Per-chunk grads accumulate in microbatch order, which is what
        makes chunk j bit-identical to stacked stage g of the in-slice
        V-stage schedule."""
        import numpy as np

        links = self.links
        v, S, V = self.interleave, self.num_stages, self.num_virtual
        m = num_microbatches
        if not isinstance(params_list, (list, tuple)) or \
                len(params_list) != v:
            raise ValueError(
                f"interleave={v}: params must be a list/tuple of {v} "
                f"per-chunk pytrees")
        if links.is_first:
            if microbatches is None:
                raise ValueError("stage 0 must supply microbatches")
            if microbatches.shape[0] != m:
                raise ValueError(
                    f"microbatches leading dim {microbatches.shape[0]} "
                    f"!= num_microbatches {m}")
        if links.is_last and (head_batches is None or head_params is None):
            raise ValueError("the last stage must supply head_params and "
                             "head_batches")
        self._calls += 1
        step_tid = self._tracing.deterministic_trace_id(
            f"{self._trace_seed}:step:{self._calls}")
        root_sid = self._tracing.deterministic_span_id(f"{step_tid}:root")
        stage_sid = self._tracing.deterministic_span_id(
            f"{step_tid}:s{self.stage}")
        traced = (self._tracer.enabled
                  and self._tracing.deterministic_sample(
                      step_tid, self._tracer.sample_rate))
        t_start = time.perf_counter()
        busy = [0.0] * v
        results: list = [None] * v
        failures: list = []

        def _send(sender, arr):
            return sender.send(np.asarray(arr), sync=self.sync_transport,
                               timeout=self.send_timeout_s)

        def run_chunk(j: int) -> None:
            g = j * S + self.stage
            params = params_list[j]
            act_in = links.act_ins[j]
            act_out = links.act_outs[j]
            grad_in = links.grad_ins[j]
            grad_out = links.grad_outs[j]
            saved: dict[int, jax.Array] = {}
            grads = jax.tree.map(jnp.zeros_like, params)
            hgrads = (jax.tree.map(jnp.zeros_like, head_params)
                      if g == V - 1 else None)
            loss_acc = jnp.zeros((), jnp.float32) if g == V - 1 else None
            dx_list: list[jax.Array] = []

            def do_forward(i: int) -> None:
                if g == 0:
                    x = microbatches[i]
                else:
                    x = jnp.asarray(act_in.recv(self.recv_timeout_s))
                saved[i] = x
                if g == V - 1:
                    return      # last virtual stage folds fwd into _last
                with self._device_lock:
                    t0 = time.perf_counter()
                    out = self._forward_compute(params, x)
                    out_host = np.asarray(out)
                    busy[j] += time.perf_counter() - t0
                _send(act_out, out_host)

            def do_backward(i: int) -> None:
                nonlocal grads, hgrads, loss_acc
                if g == V - 1:
                    head_mb = jax.tree.map(lambda a: a[i], head_batches)
                    with self._device_lock:
                        t0 = time.perf_counter()
                        lval, dp, dhp, dx = self._last_compute(
                            params, head_params, saved.pop(i), head_mb)
                        loss_acc = loss_acc + lval
                        grads = jax.tree.map(jnp.add, grads, dp)
                        hgrads = jax.tree.map(jnp.add, hgrads, dhp)
                        dx_host = np.asarray(dx)
                        busy[j] += time.perf_counter() - t0
                else:
                    cot = jnp.asarray(grad_in.recv(self.recv_timeout_s))
                    with self._device_lock:
                        t0 = time.perf_counter()
                        dp, dx = self._backward_compute(
                            params, saved.pop(i), cot)
                        grads = jax.tree.map(jnp.add, grads, dp)
                        dx_host = np.asarray(dx)
                        busy[j] += time.perf_counter() - t0
                if g == 0:
                    dx_list.append(jnp.asarray(dx_host))
                else:
                    _send(grad_out, dx_host)
                self._mb_counter.inc()

            warmup = min(V - 1 - g + self.lookahead, m)
            for i in range(warmup):
                do_forward(i)
            for i in range(m):
                k = i + warmup
                if k < m:
                    do_forward(k)
                do_backward(i)
            results[j] = (grads, loss_acc, hgrads, dx_list)

        def chunk_main(j: int) -> None:
            try:
                run_chunk(j)
            except BaseException as exc:   # propagated after join
                failures.append((j, exc))

        threads = [threading.Thread(target=chunk_main, args=(j,),
                                    name=f"tony-pp-chunk{j}", daemon=True)
                   for j in range(v)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if failures:
            j, exc = failures[0]
            raise RuntimeError(
                f"interleaved chunk {j} (virtual stage "
                f"{j * S + self.stage}) failed") from exc

        grads_out = [jax.tree.map(lambda a: a / m, r[0]) for r in results]
        loss = hgrads = dxs = None
        if links.is_last:
            loss = results[v - 1][1] / m
            hgrads = jax.tree.map(lambda a: a / m, results[v - 1][2])
        if links.is_first:
            dxs = jnp.stack(results[0][3]) / m
        wall = time.perf_counter() - t_start
        self._step_hist.observe(wall)
        bubble = max(0.0, 1.0 - sum(busy) / wall) if wall > 0 else 0.0
        self._bubble_gauge.set(bubble)
        if traced:
            self._tracer.record_span(
                "pipeline.stage", wall, trace_id=step_tid,
                span_id=stage_sid, parent_id=root_sid, stage=self.stage,
                microbatches=m, interleave=v, bubble=round(bubble, 4))
            if links.is_first:
                self._tracer.record_span(
                    "pipeline.step", wall, trace_id=step_tid,
                    span_id=root_sid, step=self._calls,
                    num_stages=self.num_stages, microbatches=m)
        return loss, grads_out, hgrads, dxs
