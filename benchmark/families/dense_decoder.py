"""The dense decoder family: one kind of layer — RMSNorm, grouped-query
attention with RoPE (base 10000) and an optional sliding window, SwiGLU —
between an embedding and an untied head. The program block it stands for
is ``tony_tpu.models.transformer`` with ``num_experts == 0``.

What every family provides, and the rest of the harness reaches a model
through nothing else (``lib/modelcfg.family(c)`` finds the module a
configuration's ``"family"`` names):

JAX-free — the parent process of a run reads these, so nothing at a
family's top level may touch ``jax`` or ``jnp`` (``lib/lazyjax.py``):

- ``check(c, name)``: raise ``ValueError`` with the reason where the
  program block cannot express the configuration;
- ``program_config(c, **job)``: the program's configuration object (the
  job script adds ``dtype`` / ``remat``);
- ``layer_kinds(c)``: the kind of each layer, in order, as a list of
  names; the reference compiles one program per kind;
- ``param_count(c)``, ``forward_flops_per_token(c, seq)``,
  ``decode_step_bytes(c, live_rows, ctx)``: the counts. ``ctx`` is the
  run's context, so a sparse family can count the experts a step really
  touched from a counter.

With JAX — the children run these:

- ``make_params(seed, c, dtype, shardings)``: the seeded weights in the
  program's parameter layout, one jitted call, born sharded;
- ``layer_weights(seed, li, c, dtype, kind)``, ``outer_weights(seed, c,
  dtype)``: the same values one layer at a time (``li`` counts through all
  layers and may be traced), and the embedding, final norm and head;
- ``leaf_name(li, leaf)`` and ``leaf_norms(tree, minus)``: the names under
  which the norms of the program's tree meet the reference's;
- ``layer_forward(x, p, c, kind)`` and ``head(o, x, c)``: the float32
  reference of ONE layer of each kind and of the head, plain
  ``jax.numpy``, nothing imported from the program; ``CONTRACT``: each
  matmul leaf's contraction axes, which the control modes (``int8``,
  ``fp8``) round over; ``HEAD_LEAVES``: the outer leaves the head reads
  (the embedding lookup is the driver's, ``lib/reference.py``).

Beyond the list, this family has the shape functions of its flash
kernels (``flash_layer_flops_bytes``, ``flash_train_flops_bytes``), which
the three ``flash_*_roofline`` readers find by name; a family without
them reads as None there.

Departures from the published models, shared with the program and noted
in each configuration file: RMSNorm epsilon 1e-6 (the program's constant),
separate q/k/v and gate/up matrices (Phi-3 publishes them fused: same
mathematics), sliding window = "query i sees keys j with 0 <= i-j < W".
"""

from __future__ import annotations

import functools
import math

from benchmark.lib import weights
from benchmark.lib.flops import attended
from benchmark.lib.lazyjax import jax, jnp
from benchmark.lib.reference import rms_norm

_LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "w_gate": (0,), "w_up": (0,), "w_down": (0,), "lm_head": (0,)}
HEAD_LEAVES = ("final_norm", "lm_head")
_NEG = -1e30


# ------------------------------------------------------ check and counts
def check(c: dict, name: str) -> None:
    heads, d = c["num_attention_heads"], c["hidden_size"]
    if c.get("head_dim", d // heads) * heads != d:
        raise ValueError(f"{name}: head_dim x heads != hidden_size — the "
                         f"program derives head_dim = d_model / n_heads")
    if c["rope_theta"] != 10000.0 or c["hidden_act"] != "silu" \
            or c["tie_word_embeddings"]:
        raise ValueError(f"{name}: the program's block is RoPE base 10000, "
                         f"SwiGLU, untied head")


def program_config(c: dict, **job):
    """``tony_tpu.models.transformer.TransformerConfig`` of this
    configuration (dtype and remat are the job script's)."""
    from tony_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq=c["max_position_embeddings"],
        attn_window=c.get("sliding_window") or 0, **job)


def layer_kinds(c: dict) -> list[str]:
    return ["block"] * c["num_hidden_layers"]


def _dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return d, h, c["num_key_value_heads"], d // h, c["intermediate_size"]


def _window(c: dict) -> int:
    return c.get("sliding_window") or 0


def layer_params(c: dict) -> int:
    d, _, kv, k, f = _dims(c)
    return 2 * d * d + 2 * d * kv * k + 3 * d * f + 2 * d


def param_count(c: dict) -> int:
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * c["vocab_size"] * d + d)


def forward_flops_per_token(c: dict, seq: int) -> float:
    d, _, kv, k, f = _dims(c)
    proj = 2 * (2 * d * d + 2 * d * kv * k)          # wq, wo, wk, wv
    attn = 4 * attended(seq, _window(c)) * d         # QK^T, AV
    mlp = 2 * 3 * d * f                              # gate, up, down
    return (c["num_hidden_layers"] * (proj + attn + mlp)
            + 2 * d * c["vocab_size"])


def decode_step_bytes(c: dict, live_rows: float, ctx: dict | None = None,
                      dtype_bytes: int = 2) -> float:
    """Bytes ONE decode step over the whole batch must read: every matmul
    weight once (the embedding is a gather of a few rows) plus the live
    cache rows (K and V of every layer for each token already held).
    Dense: every step reads every weight, so ``ctx`` has nothing to add."""
    d, _, kv, k, _ = _dims(c)
    weight = (c["num_hidden_layers"] * layer_params(c)
              + c["vocab_size"] * d + d)
    cache = live_rows * c["num_hidden_layers"] * 2 * kv * k
    return (weight + cache) * dtype_bytes


def flash_layer_flops_bytes(c: dict, batch: int, seq: int,
                            dtype_bytes: int = 2) -> dict:
    """``{"fwd": (flops, bytes), "bwd": (flops, bytes)}`` of ONE layer's
    attention kernels: forward QK^T and AV (4 flops per attended pair per
    head dim), reading q, k, v and writing o; backward dV, dP, dQ, dK and
    the score recompute (10), reading q, k, v, o, do and writing dq, dk,
    dv."""
    d, _, kv, k, _ = _dims(c)
    pairs = batch * seq * attended(seq, _window(c))
    tok = batch * seq
    return {"fwd": (4 * pairs * d, tok * (2 * d + 2 * kv * k) * dtype_bytes),
            "bwd": (10 * pairs * d,
                    tok * (4 * d + 4 * kv * k) * dtype_bytes)}


def flash_train_flops_bytes(c: dict, batch: int, seq: int,
                            dtype_bytes: int = 2) -> tuple[float, float]:
    """What the attention kernels of ONE train step must do over all
    layers, forward and backward together, and the bytes they must move.
    Remat's replay of the forward is not counted: it is the program's
    choice, not the algorithm's."""
    (f_fl, f_by), (b_fl, b_by) = flash_layer_flops_bytes(
        c, batch, seq, dtype_bytes).values()
    layers = c["num_hidden_layers"]
    return layers * (f_fl + b_fl), layers * (f_by + b_by)


# ---------------------------------------------------------------- weights
def layer_weights(seed, li, c: dict, dtype, kind: str = "block") -> dict:
    """Block ``li``'s leaves (unstacked). Traced or concrete ``li``."""
    d, h, kv, k, f = _dims(c)
    ks = jax.random.split(weights.layer_key(seed, li), len(_LAYER_LEAVES))
    shapes = {"wq": ((d, h, k), d), "wk": ((d, kv, k), d),
              "wv": ((d, kv, k), d), "wo": ((h, k, d), d),
              "w_gate": ((d, f), d), "w_up": ((d, f), d),
              "w_down": ((f, d), f)}
    out = {n: weights.normal(ks[i], *shapes[n], dtype)
           for i, n in enumerate(_LAYER_LEAVES)}
    out["attn_norm"] = jnp.ones((d,), dtype)
    out["mlp_norm"] = jnp.ones((d,), dtype)
    return out


def outer_weights(seed, c: dict, dtype) -> dict:
    """Embedding, final norm and the untied head."""
    d, v = c["hidden_size"], c["vocab_size"]
    k_emb, k_out = jax.random.split(weights.outer_key(seed))
    return {"embed": weights.normal(k_emb, (v, d), d, dtype),
            "final_norm": jnp.ones((d,), dtype),
            "lm_head": weights.normal(k_out, (d, v), d, dtype)}


def make_params(seed: int, c: dict, dtype, shardings=None):
    """The whole pytree (stacked ``[L, ...]`` block leaves) in ONE jitted
    call. ``shardings``: an optional pytree of shardings (the program's,
    from its logical axes) so a sharded state is born sharded instead of
    gathered on one chip."""
    @functools.partial(jax.jit, out_shardings=shardings)
    def build(seed):
        blocks = jax.vmap(lambda li: layer_weights(seed, li, c, dtype))(
            jnp.arange(c["num_hidden_layers"]))
        return dict(outer_weights(seed, c, dtype), blocks=blocks)

    return build(weights.as_seed(seed))


def leaf_name(li: int, leaf: str) -> str:
    return f"blocks/{leaf}/{li}"


def leaf_norms(tree: dict, minus: dict | None = None) -> dict:
    """{"blocks/wq/3": norm, "embed": norm, ...} of a params-shaped tree
    (less ``minus``), one jitted program that materialises no difference."""
    @jax.jit
    def norms(t, m):
        if m is not None:
            t = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32), t, m)
        sq = lambda x, ax: jnp.sqrt(jnp.sum(                # noqa: E731
            jnp.square(x.astype(jnp.float32)), axis=ax))
        return {"blocks": {n: sq(x, tuple(range(1, x.ndim)))
                           for n, x in t["blocks"].items()},
                **{n: sq(x, None) for n, x in t.items() if n != "blocks"}}
    out = jax.device_get(norms(tree, minus))
    flat = {n: float(v) for n, v in out.items() if n != "blocks"}
    for n, per_layer in out["blocks"].items():
        flat.update({leaf_name(li, n): float(v)
                     for li, v in enumerate(per_layer)})
    return flat


# -------------------------------------------------------------- reference
def rope(x, positions):
    """[B, S, H, D] rotated by position, halves convention, base 10000."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(10000.0) / half))
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def attention(q, k, v, window: int):
    """Causal (and windowed) softmax attention, grouped-query aware, over
    blocks of query rows so the score matrix never exceeds ~1 GiB. Each
    block is rematerialised in the backward pass."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    bq = s
    while b * h * bq * s * 4 > (1 << 30) and bq % 2 == 0 and bq > 128:
        bq //= 2
    q = q.reshape(b, s // bq, bq, kv, g, d)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qb, i0 = args                                 # [b, bq, kv, g, d]
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) * (d ** -0.5)
        qpos = i0 + jnp.arange(bq)
        mask = qpos[:, None] >= kpos[None, :]
        if window:
            mask = mask & (qpos[:, None] - kpos[None, :] < window)
        p = jax.nn.softmax(jnp.where(mask, sc, _NEG), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v)

    out = jax.lax.map(block, (jnp.moveaxis(q, 1, 0),
                              jnp.arange(s // bq) * bq))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def layer_forward(x, p, c: dict, kind: str = "block"):
    """One decoder block on [B, S, d] float32."""
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    hdn = rms_norm(x, p["attn_norm"])
    q = rope(jnp.einsum("bsd,dhk->bshk", hdn, p["wq"]), pos)
    k = rope(jnp.einsum("bsd,dhk->bshk", hdn, p["wk"]), pos)
    v = jnp.einsum("bsd,dhk->bshk", hdn, p["wv"])
    o = attention(q, k, v, _window(c))
    x = x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    hdn = rms_norm(x, p["mlp_norm"])
    inner = (jax.nn.silu(jnp.einsum("bsd,df->bsf", hdn, p["w_gate"]))
             * jnp.einsum("bsd,df->bsf", hdn, p["w_up"]))
    return x + jnp.einsum("bsf,fd->bsd", inner, p["w_down"])


def head(o, x, c: dict):
    return jnp.einsum("bsd,dv->bsv", rms_norm(x, o["final_norm"]),
                      o["lm_head"])
