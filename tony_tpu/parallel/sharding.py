"""Logical-axis sharding rules: names in models, meshes at runtime.

Green-field for the TPU build (the reference delegates all parallelism to the
user script — SURVEY.md §2.3). Models annotate arrays with *logical* axis
names ("batch", "embed", "heads", ...); a rule table maps those to mesh axes.
Swapping DP→FSDP→TP+SP is then a rule-table change, not a model change —
the same decoupling the scaling-book recipe prescribes: pick a mesh, annotate
shardings, let XLA insert the collectives.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Sequence[tuple[str, str | tuple[str, ...] | None]]

# Default rule table for transformer-family models. First matching rule wins;
# a mesh axis not present in the mesh resolves to replication.
DEFAULT_RULES: Rules = (
    ("batch", ("dp", "fsdp")),       # batch over dp and fsdp jointly
    ("seq", "cp"),                   # context parallelism: sequence split
    ("embed", "fsdp"),               # FSDP shards params on the embed dim
    ("heads", "tp"),                 # attention heads over tensor axis
    ("kv", None),                    # per-head dim: never sharded
    ("mlp", "tp"),                   # MLP hidden over tensor axis
    ("vocab", "tp"),                 # embedding/logits vocab over tensor axis
    ("expert", "ep"),                # MoE experts over expert axis
    ("stage", "pp"),                 # pipeline stages
    ("norm", None),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_rep(x, axis_name: str):
    """``lax.psum`` whose TRANSPOSE treats the cotangent as replicated.

    Inside a ``shard_map`` body with ``check_vma=False``, the stock
    psum's transpose psums the cotangent again — a replicated seed (the
    usual case: a loss differentiated identically on every rank) comes
    back multiplied by the axis size, so a per-rank ``jax.vjp`` of a
    cross-shard reduction yields axis_size x the true partials
    (measured: seeding 1.0 through a tp=2 psum doubles every upstream
    gradient). This wrapper's backward is the identity, so per-rank
    vjps yield TRUE partials — callers then sum partials across the
    axis exactly once, where they choose to (the 1F1B pipeline's
    head_reduce_axes does). Use for manual-collective loss heads; the
    primal is a plain psum."""
    return jax.lax.psum(x, axis_name)


def _psum_rep_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _psum_rep_bwd(axis_name, _res, ct):
    return (ct,)


psum_rep.defvjp(_psum_rep_fwd, _psum_rep_bwd)


def _auto_axes(mesh) -> set[str]:
    """Mesh axes that sharding constraints may refer to. Inside ``shard_map``
    the ambient AbstractMesh marks its axes Manual and
    ``with_sharding_constraint`` rejects specs naming them — the collective
    layout there is the shard_map's business, so :func:`constrain` must
    resolve those axes to replication (e.g. model code reused as a pipeline
    stage body — parallel/pipeline.py runs blocks under shard_map)."""
    types = getattr(mesh, "axis_types", None)
    if types is None:
        return set(mesh.shape)
    return {name for name, t in zip(mesh.axis_names, types)
            if "Manual" not in str(t)}


def _resolve(logical: str | None, rules: Rules, mesh: Mesh,
             used: set[str], auto: set[str]):
    if logical is None:
        return None
    for name, target in rules:
        if name == logical:
            if target is None:
                return None
            targets = (target,) if isinstance(target, str) else tuple(target)
            # a mesh axis may shard at most one array dim: earlier dims win
            # (e.g. ("batch","embed") on a pure-fsdp mesh → batch gets fsdp,
            # embed replicates instead of raising DuplicateSpecError)
            live = tuple(t for t in targets
                         if t in mesh.shape and mesh.shape[t] > 1
                         and t not in used and t in auto)
            if not live:
                return None
            used.update(live)
            return live if len(live) > 1 else live[0]
    return None


def logical_to_spec(logical_axes: Sequence[str | None], mesh: Mesh,
                    rules: Rules = DEFAULT_RULES) -> P:
    """("batch", "embed") → PartitionSpec(("dp","fsdp"), "fsdp") under rules,
    dropping mesh axes that don't exist, have size 1, are already used by
    an earlier dim of the same array, or are Manual (inside shard_map)."""
    used: set[str] = set()
    auto = _auto_axes(mesh)
    return P(*(_resolve(ax, rules, mesh, used, auto) for ax in logical_axes))


def logical_sharding(logical_axes: Sequence[str | None], mesh: Mesh,
                     rules: Rules = DEFAULT_RULES) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, mesh, rules))


def shard_pytree(tree: Any, logical_tree: Any, mesh: Mesh,
                 rules: Rules = DEFAULT_RULES) -> Any:
    """Device-put every leaf of ``tree`` per its logical axes in
    ``logical_tree`` (same structure, leaves are tuples of axis names)."""
    return jax.tree.map(
        lambda x, ax: jax.device_put(x, logical_sharding(ax, mesh, rules)),
        tree, logical_tree, is_leaf=lambda x: x is None)


def constrain(x, logical_axes: Sequence[str | None], mesh: Mesh | None = None,
              rules: Rules = DEFAULT_RULES):
    """``with_sharding_constraint`` by logical names. With no explicit mesh,
    the ambient mesh context (``jax.sharding.set_mesh`` / trace-time abstract
    mesh) is used; a no-op when neither exists (single-device, plain tests)."""
    if mesh is not None:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, logical_to_spec(logical_axes, mesh, rules)))
    ambient = jax.sharding.get_abstract_mesh()
    if ambient is None or ambient.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, logical_to_spec(logical_axes, ambient, rules))


def _is_axes_leaf(x: Any) -> bool:
    """A leaf of a logical-axes pytree: None, or a tuple of axis names/None.
    Distinguishes the axes tuple ("stage","embed") from structural tuples
    like a ((W_axes, b_axes), ...) params container."""
    return x is None or (isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x))


def param_shardings(logical_tree: Any, mesh: Mesh,
                    rules: Rules = DEFAULT_RULES) -> Any:
    """Map a logical-axes pytree → NamedSharding pytree (for jit in_shardings/
    out_shardings)."""
    return jax.tree.map(
        lambda ax: logical_sharding(ax, mesh, rules),
        logical_tree, is_leaf=_is_axes_leaf)


_BATCH_AXES = dict(DEFAULT_RULES)["batch"]      # ("dp", "fsdp")
_HEAD_AXIS = dict(DEFAULT_RULES)["heads"]       # "tp"


def attention_spec(mesh: Mesh, batch_axes, seq_axis: str | None,
                   head_axis: str | None):
    """PartitionSpec for [B, S, H, D] attention operands under shard_map:
    batch over the live subset of ``batch_axes``, sequence over ``seq_axis``,
    heads over ``head_axis``; axes missing from the mesh (or size 1, or
    already Manual — an enclosing shard_map owns those) are dropped.
    Returns (spec, seq_axis_live: str | None)."""
    auto = _auto_axes(mesh)
    live = lambda a: (a is not None and a in auto
                      and mesh.shape.get(a, 1) > 1)
    b_spec = tuple(a for a in batch_axes if live(a)) or None
    if isinstance(b_spec, tuple) and len(b_spec) == 1:
        b_spec = b_spec[0]
    s_spec = seq_axis if live(seq_axis) else None
    h_spec = head_axis if live(head_axis) else None
    return P(b_spec, s_spec, h_spec, None), s_spec


def _island_mesh(mesh):
    """The mesh a ``shard_map`` island would run over — ``mesh``, or the
    ambient one (``jax.set_mesh``) — or None where there is nothing to
    split: no mesh, one device, or every axis already Manual (a pipeline
    stage body)."""
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or mesh.size == 1 \
            or not _auto_axes(mesh):
        return None
    return mesh


def shard_attention(local_fn, q, k, v, mesh: Mesh | None, *,
                    batch_axes: Sequence[str] = _BATCH_AXES,
                    seq_axis: str | None = None,
                    head_axis: str | None = _HEAD_AXIS,
                    kv_split: int = 1):
    """Run a per-device attention ``local_fn(q, k, v)`` over global
    [B, S, H, D] operands as ONE ``shard_map`` island: batch over the
    mesh axes the rules give "batch", heads over the "heads" axis,
    sequence over ``seq_axis`` when the caller's body does its own
    cross-chunk collectives (ring / Ulysses). The flash kernels are
    Mosaic custom calls, which the SPMD partitioner cannot split ("Mosaic
    kernels cannot be automatically partitioned") — so every attention
    arm on a multi-device mesh goes through here, not only the
    context-parallel ones.

    ``mesh=None`` takes the ambient mesh (``jax.set_mesh``); with no mesh,
    one device, or every axis already Manual (a pipeline stage body) the
    call is ``local_fn(q, k, v)`` as is.

    GQA K/V (fewer heads than Q) stay UNEXPANDED when the kv heads
    survive the head sharding — ``tp · kv_split`` divides them, where
    ``kv_split`` is a further split the body makes itself (Ulysses'
    all-to-all over cp) — because the local arms pair local query head j
    with local kv head j // rep, which is the global pairing only when
    K/V heads shard like Q's. Otherwise they expand to full width first:
    correctness over the payload saving."""
    mesh = _island_mesh(mesh)
    if mesh is None:
        return local_fn(q, k, v)
    # an axis that does not divide its dim is dropped (the operand
    # replicates over it and the devices repeat the work) — what the
    # partitioner does with a constraint it cannot honour evenly, e.g. a
    # serving batch of 2 under a dp=4 mesh
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    while q.shape[0] % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = batch_axes[:-1]
    if head_axis in mesh.shape and q.shape[2] % mesh.shape[head_axis]:
        head_axis = None
    spec, _ = attention_spec(mesh, batch_axes, seq_axis, head_axis)
    h, hk = q.shape[2], k.shape[2]
    if hk != h:
        if hk <= 0 or h % hk:
            raise ValueError(f"kv heads ({hk}) must divide heads ({h})")
        tp = mesh.shape[spec[2]] if spec[2] is not None else 1
        if hk % (tp * kv_split):
            k, v = (jax.numpy.repeat(x, h // hk, axis=2) for x in (k, v))
    return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def cache_heads_split(kv_heads: int, mesh: Mesh | None = None, *,
                      head_axis: str = _HEAD_AXIS) -> bool:
    """Whether :func:`shard_cached_attention` can leave a K/V cache of
    ``kv_heads`` heads where the mesh's "heads" axis puts it: no such
    axis live, or one that divides the heads. Where it does not, a
    stored row splits INSIDE a head and only a program the partitioner
    can cut (the ``jnp`` reads) follows it without gathering the
    cache."""
    mesh = _island_mesh(mesh)
    return (mesh is None or head_axis not in _auto_axes(mesh)
            or kv_heads % mesh.shape[head_axis] == 0)


def shard_cached_attention(local_fn, q, k_all, v_all, q_pos,
                           mesh: Mesh | None = None, *,
                           batch_axes: Sequence[str] = _BATCH_AXES,
                           head_axis: str = _HEAD_AXIS):
    """Run a per-device cached read ``local_fn(q, k_all, v_all, q_pos)``
    — q [B, 1, H, hd] at positions ``q_pos`` [B] against the STACKED
    cache [L, B, rows, KV·hd] — as ONE ``shard_map`` island, the decode
    step's counterpart of :func:`shard_attention` and for its reason: the
    read is a Mosaic custom call, which the partitioner cannot split.
    Slots over the "batch" axes, K/V heads over the "heads" axis: the
    merged trailing axis KV·hd splits between heads (the caller has
    asked :func:`cache_heads_split`), each device holds whole stored
    rows of ITS heads and the query heads that read them (H is K/V-head
    major), and a slot's softmax never crosses devices — so the split is
    exact and the island holds no collective. The cache is never
    gathered: the island's specs are where sharding propagation leaves
    it (``wk`` / ``wv`` are cut by heads).

    ``v_all`` None: a latent cache, whose one stored row a token is key
    and value of every query head — ``local_fn(q, k_all, q_pos)``. One
    head does not split, so the caller has asked
    ``cache_heads_split(1)`` and no "heads" axis is live here: slots
    over the batch axes, nothing else.

    With nothing to split (:func:`_island_mesh`) the call is
    ``local_fn`` as is. A batch axis that does not divide the slots is
    dropped, as in :func:`shard_attention`."""
    mesh = _island_mesh(mesh)
    caches = (k_all,) if v_all is None else (k_all, v_all)
    if mesh is None:
        return local_fn(q, *caches, q_pos)
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    while q.shape[0] % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = batch_axes[:-1]
    spec, _ = attention_spec(mesh, batch_axes, None, head_axis)
    cache = P(None, spec[0], None, spec[2])
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(spec, *(cache for _ in caches), P(spec[0])),
        out_specs=spec, check_vma=False)(q, *caches, q_pos)
