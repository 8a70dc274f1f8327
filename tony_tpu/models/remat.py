"""What a decoder block keeps for its backward: a ladder of named saves,
walked richest-first by time saved per byte and stopped by the memory the
device has.

``jax.checkpoint`` around a block saves the block's input and replays the
block's forward inside the backward. The replay is work the step has done
once already; whether it must be done again is a question of bytes, and
the bytes are known when the step is traced. Each rung of :data:`LADDER`
adds tensors (``checkpoint_name`` in ``transformer._block`` and
``ops/attention.py``) to what the rung before it keeps:

====  ==========================================  ==========================
rung  kept                                        the replay no longer runs
====  ==========================================  ==========================
0     nothing (``remat_policy="full"``)           —
1     the flash kernel's output and its lse       the flash forward
2     + the flash kernel's operands (q, k, v as   q/k/v and output
      it reads them), the attention output        projections, RoPE
      projection
3     + the MLP's gate                            half of gate + up
4     + the MLP's up                              the other half
====  ==========================================  ==========================

:func:`choose` is pure arithmetic over bytes; :func:`decide` feeds it what
the trace and the device can observe. Nothing here is set by a user.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import logging
import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from tony_tpu.parallel.sharding import logical_sharding
from tony_tpu.runtime import metrics as metrics_mod

log = logging.getLogger(__name__)

#: names each rung ADDS to the rung before it
LADDER: tuple[tuple[str, ...], ...] = (
    (),
    ("flash_out", "flash_lse"),
    ("flash_q", "flash_k", "flash_v", "attn_proj"),
    ("mlp_gate",),
    ("mlp_up",),
)

#: a live reading of the device is rounded up to this share of its limit
_QUANTUM_SHARE = 64


def names(rung: int) -> tuple[str, ...]:
    """Every name rung ``rung`` keeps (a superset of the rung below)."""
    return tuple(n for added in LADDER[:rung + 1] for n in added)


def policy(rung: int):
    """``jax.checkpoint`` policy of a rung (None = save nothing)."""
    if rung == 0:
        return None
    return jax.checkpoint_policies.save_only_these_names(*names(rung))


# ---------------------------------------------------------------- bytes

def sharded_bytes(shape, logical, itemsize: int, mesh, rules) -> int:
    """Bytes one device holds of an array with these logical axes — the
    shard ``parallel.sharding.constrain`` gives it in the block."""
    if mesh is not None and not mesh.empty:
        shape = logical_sharding(logical, mesh, rules).shard_shape(
            tuple(shape))
    return math.prod(shape) * itemsize


def _named_tensors(cfg, b: int, s: int, mesh, rules) -> dict:
    """name → (global shape, logical axes, itemsize) of what a block
    computes under each of :data:`LADDER`'s names, per layer. A head's
    width counts in whole 128-lane tiles: the chip stores ``[.., H, 96]``
    padded to 128 (read off the compiler: Phi-3's rungs weigh 4/3). The
    kernel's K/V keep their own heads where the head sharding divides
    them and are expanded to Q's otherwise (``shard_attention``)."""
    h, kv, d, f = cfg.n_heads, cfg.kv_heads, cfg.d_model, cfg.d_ff
    hd = -(-cfg.head_dim // 128) * 128
    act = jnp.dtype(cfg.dtype).itemsize
    head_shards = h // sharded_bytes((h,), ("heads",), 1, mesh, rules)
    kv = kv if kv % head_shards == 0 else h
    heads = ("batch", "seq", "heads", "kv")
    return {
        "flash_out": ((b, s, h, hd), heads, act),
        "flash_lse": ((b, s, h), heads[:3], 4),
        "flash_q": ((b, s, h, hd), heads, act),
        "flash_k": ((b, s, kv, hd), heads, act),
        "flash_v": ((b, s, kv, hd), heads, act),
        "attn_proj": ((b, s, d), ("batch", "seq", "embed"), act),
        "mlp_gate": ((b, s, f), ("batch", "seq", "mlp"), act),
        "mlp_up": ((b, s, f), ("batch", "seq", "mlp"), act),
    }


def rung_bytes(cfg, b: int, s: int, mesh=None, rules=()) -> list[int]:
    """Bytes a device each rung keeps over all the layers it runs
    (cumulative, rung 0 first)."""
    tensors = _named_tensors(cfg, b, s, mesh, rules)
    out, total = [], 0
    for added in LADDER:
        total += cfg.n_layers * sum(
            sharded_bytes(*tensors[n], mesh, rules) for n in added)
        out.append(total)
    return out


def working_bytes(cfg, b: int, s: int, mesh=None, rules=()) -> int:
    """What the step holds besides its state, its gradients and the saved
    tensors, reckoned from shapes: every block's input (the scan stacks
    them), the logits, and one block's backward (three tensors of the
    MLP's width, four of the model's). The multiples were fitted ONCE to
    the described-chip compile's ``peak_memory_in_bytes`` at every rung
    over 17 shapes of four models (PERF.md section 6, PR 31): the
    compiler's peak lies between 0.8 GB under and 0.26 GB over this, its own
    scheduling moving it by as much between neighbouring shapes.
    ``tests/test_chip_compile.py`` holds the benchmark's train step to it."""
    act = jnp.dtype(cfg.dtype).itemsize
    x = sharded_bytes((b, s, cfg.d_model), ("batch", "seq", "embed"),
                      act, mesh, rules)
    wide = sharded_bytes((b, s, cfg.d_ff), ("batch", "seq", "mlp"), act,
                         mesh, rules)
    logits = sharded_bytes(
        (b, s, cfg.vocab_size), ("batch", "seq", "vocab"),
        jnp.dtype(cfg.logits_storage_dtype).itemsize, mesh, rules)
    return cfg.n_layers * x + logits + 3 * wide + 4 * x


def choose(rungs: list[int], left: int | None,
           mesh_shape: Mapping[str, int] | None = None,
           ceiling: int = len(LADDER) - 1) -> int:
    """The richest rung whose bytes fit in ``left`` (bytes a device still
    has once state, gradients and working set are taken off), not above
    ``ceiling``. Rung 0 where the device gave no numbers (``left`` None)
    and on meshes the estimate does not model: ``pp > 1`` (microbatches
    in flight multiply what is live) and ``cp > 1``."""
    shape = mesh_shape or {}
    if left is None or shape.get("pp", 1) > 1 or shape.get("cp", 1) > 1:
        return 0
    fits = [r for r, need in enumerate(rungs) if need <= left]
    return min(max(fits, default=0), ceiling)


# --------------------------------------------------------------- device

def device_memory() -> tuple[int, int] | None:
    """(``bytes_limit``, ``bytes_in_use``) of this process's devices, the
    least limit and the most in use; None where a device gives no numbers
    (the CPU, a described chip)."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    if not all(s and "bytes_limit" in s for s in stats):
        return None
    return (min(s["bytes_limit"] for s in stats),
            max(s.get("bytes_in_use", 0) for s in stats))


def bytes_a_device(tree: Any) -> int:
    """Bytes one device holds of a pytree of arrays (or of shapes with
    shardings): from shapes and shardings alone, so every process of a
    gang computes the same number."""
    total = 0
    for x in jax.tree.leaves(tree):
        shape = getattr(x, "shape", None)
        if shape is None:
            continue
        sharding = getattr(x, "sharding", None)
        if sharding is not None:
            shape = sharding.shard_shape(tuple(shape))
        total += math.prod(shape) * jnp.dtype(x.dtype).itemsize
    return total


@dataclasses.dataclass
class Scope:
    """What the step that wraps the forward tells it, and hears back:
    ``held`` bytes a device of the step's own arguments (and of their
    copy, where they are not donated), ``ceiling`` the rung the safety
    net allows, ``rung`` the one the last trace took."""
    held: int = 0
    ceiling: int = len(LADDER) - 1
    rung: int | None = None


_SCOPE: contextvars.ContextVar[Scope | None] = contextvars.ContextVar(
    "remat_scope", default=None)


@contextlib.contextmanager
def scope(sc: Scope):
    token = _SCOPE.set(sc)
    try:
        yield sc
    finally:
        _SCOPE.reset(token)


def decide(cfg, b: int, s: int, grads: int, mesh=None, rules=()) -> int:
    """The rung a training forward of ``[b, s]`` tokens takes, at trace
    time; ``grads`` the parameters' bytes a device.
    ``cfg.remat_policy == "full"`` pins rung 0.

    Bytes left a device = the device's ``bytes_limit``
    − what it holds: the step's arguments (``Scope.held``, set by
      ``train.make_train_step`` from shapes and shardings — the donated
      state, whatever optimizer the user chose) plus whatever ELSE
      ``bytes_in_use`` shows, rounded up to the next 1/64 of the limit
    − the gradients (the parameters' bytes a device)
    − :func:`working_bytes`.

    Every process of a gang reaches the same rung: shapes, shardings and
    ``bytes_limit`` are equal in all of them, and the one live reading
    (what a process holds beyond the step's arguments: prefetched
    batches, a copy the user keeps) is quantised so coarsely — 256 MB on
    a v5e — that two hosts a few MB apart read the same.
    """
    sc = _SCOPE.get() or Scope()
    if mesh is None:
        ambient = jax.sharding.get_abstract_mesh()
        mesh = None if ambient is None or ambient.empty else ambient
    rungs = rung_bytes(cfg, b, s, mesh, rules)
    left = None
    # "full" pins rung 0; the gshard block's working set is not reckoned
    memory = (None if cfg.remat_policy == "full" or cfg.num_experts
              else device_memory())
    if memory is not None:
        limit, in_use = memory
        # at least one quantum: the runtime keeps 258 MiB of a v5e's
        # 15.75 GiB for itself (the compiler's own out-of-memory report)
        quantum = limit // _QUANTUM_SHARE
        other = (max(in_use - sc.held, 0) // quantum + 1) * quantum
        left = (limit - sc.held - other - grads
                - working_bytes(cfg, b, s, mesh, rules))
    rung = choose(rungs, left,
                  dict(mesh.shape) if mesh is not None else None,
                  sc.ceiling)
    sc.rung = rung
    log.info("train step keeps rung %d of %d (%s): %d saved bytes a "
             "device, %s bytes left before them", rung, len(LADDER) - 1,
             ", ".join(names(rung)) or "block inputs only", rungs[rung],
             "no reading of" if left is None else left)
    reg = metrics_mod.get_default()
    reg.gauge("tony_train_saved_rung",
              help="rung of models/remat.LADDER the train step was "
                   "traced at").set(rung)
    reg.gauge("tony_train_saved_bytes",
              help="bytes a device the train step saves for its backward "
                   "beyond block inputs").set(rungs[rung])
    return rung


def step_down(sc: Scope, error: Exception, state: Any) -> bool:
    """The safety net of ``make_train_step``: after a step that failed to
    compile for memory, lower the ceiling one rung under the one it was
    traced at. False tells the caller to re-raise: another error,
    nothing to step down to (rung 0, or a program that was not traced
    under the ceiling already set), or the donated state already gone."""
    if ("RESOURCE_EXHAUSTED" not in str(error) or not sc.rung
            or sc.rung > sc.ceiling
            or any(getattr(x, "is_deleted", lambda: False)()
                   for x in jax.tree.leaves(state))):
        return False
    sc.ceiling = sc.rung - 1
    metrics_mod.get_default().counter(
        "tony_train_saved_step_downs_total",
        help="times a train step that ran out of memory at its rung was "
             "rebuilt one rung down").inc()
    log.warning("train step ran out of memory at rung %d; rebuilding it "
                "at rung %d: %s", sc.rung, sc.ceiling,
                str(error).splitlines()[0][:200])
    return True
