"""The latent-attention, sparse-expert decoder family (DeepSeek-V3's layers,
as Kimi-K2.5's language model configures them): a leading dense layer,
then expert layers, between an embedding and an untied head. Provides what
``dense_decoder.py``'s docstring lists. The program block it stands for is
``tony_tpu.models.transformer`` with ``layer_kinds`` set (served through
``models/decode.py``; the program refuses to train it).

One layer, on x [B, S, d] (``rms`` with the published epsilon):

- attention: ``h = rms(x)``; ``c_q = rms(h W_qa)``; ``q = c_q W_qb`` →
  heads of ``[q_n (nope); q_r (rope)]``; ``[c_kv; k_r] = h W_kva``, ``c_kv
  ← rms(c_kv)``; ``q_r, k_r ← rope(.)`` (k_r ONE head, shared by all);
  ``[k_n; v]`` a head ``= c_kv W_kvb``. Scores ``(q_n.k_n + q_r.k_r) s``,
  causal softmax, ``sum p v`` → ``W_o``. ``s = (nope + rope)^-0.5 m^2``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1``. RoPE on the rope dims only,
  YaRN frequencies (:func:`yarn_frequencies`). This reference is the
  EXPANDED form at every position; the program decodes in the absorbed
  form over one stored row ``[c_kv; k_r]`` a token — the same mathematics.
- layer 0 (``dense``): SwiGLU of ``intermediate_size``.
- layers 1.. (``moe``): ``z = sigmoid(h W_r)`` over ALL ``router_experts``
  (float32); the ``num_experts_per_tok`` largest of ``z + b`` (``b``: the
  selection bias, for the pick only; one group, so no group step);
  ``w = z[picked] / (sum z[picked] + 1e-20) x routed_scaling_factor``;
  ``y = sum_picked w_e SwiGLU_e(h) + SwiGLU_shared(h)``. THE SHARE: this
  chip holds experts ``[first_expert, first_expert + n_routed_experts)``;
  routing and normalisation are over all, the sum runs over the picked
  experts held here, the shared expert is whole, and what the absent
  experts would add is left out — here as in the program. The reference
  computes EVERY held expert for EVERY token and weights by the pick: no
  sort, no dispatch.
- head: ``rms`` then ``W_head`` over the held vocabulary rows.

Memory: ``lib/reference.served_token_gaps`` hands a layer 8 rows of up to
2,048 positions; 64 heads of float32 scores would be 8.6 GB whole, so
:func:`attention` walks the queries in blocks (a ``lax.map``; still plain
``jax.numpy``), and the experts are walked one at a time (a ``lax.scan``:
[tokens, 2048] per expert, not [tokens, 12, 2048]).

Touched experts. ``decode_step_bytes`` and the routed product's roofline
need the (layer, held expert) pairs a decode step touched. The program
counts them (``engine.stats()["moe_expert_touches"]``, in the replica's
snapshot), but ``drivers/serve.py`` forwards fixed keys of the snapshot,
so until a ``benchmark`` PR passes ``stats`` through, :func:`expert_load`
RECKONS them: with ``rows`` tokens a step, each picking ``k`` of ``E``
uniformly, a held expert is touched with probability ``1 - (1 -
k/E)^rows``. ``rows`` is the mix's ``slots``: the program routes every
slot in every step, idle or live, and in a saturated cell's traced window
every slot is live. Read on the chip against the counter (PR 28, two
runs): 5.88 a layer a step reckoned, 5.81 and 5.83 counted (1% over).
ISSUE 28's estimator, ``tokens_kept / steps_executed``, read 4.98 (14%
under): the driver's step count runs on through the drain after the
window, when the slots empty.

Departures from the published model, shared with the program and noted
in the configuration file: rotate-half layout of the rotary dims
(published interleaved: a permutation of seeded weights); ``W_qa`` and
``W_kva`` separate (one fused matrix upstream: the same mathematics).
"""

from __future__ import annotations

import functools
import math
import os

from benchmark.lib import modelcfg, weights
from benchmark.lib.flops import attended
from benchmark.lib.lazyjax import jax, jnp

_ATTN_LEAVES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
_LEAVES = {
    "dense": _ATTN_LEAVES + ("w_gate", "w_up", "w_down"),
    "moe": _ATTN_LEAVES + ("router", "router_bias", "w_gate", "w_up",
                           "w_down", "shared_gate", "shared_up",
                           "shared_down"),
}
_NORMS = ("attn_norm", "q_norm", "kv_norm", "mlp_norm")
#: each matmul leaf's contraction axes (what the controls round over);
#: the experts carry a leading expert axis
CONTRACT = {"wq_a": (0,), "wq_b": (0,), "wkv_a": (0,), "wkv_b": (0,),
            "wo": (0, 1), "router": (0,),
            "w_gate": (-2,), "w_up": (-2,), "w_down": (-2,),
            "shared_gate": (0,), "shared_up": (0,), "shared_down": (0,),
            "lm_head": (0,)}
HEAD_LEAVES = ("final_norm", "lm_head")
#: scale of the selection bias, and the key it is drawn from (``assumed``
#: in the configuration): the SAME draw for every --seed, folded by layer
BIAS_SCALE = 0.005
BIAS_KEY = 20260928
_NEG = -1e30
KERNEL = "tony_moe_gmm"


# ------------------------------------------------------ check and counts
def _dims(c: dict) -> dict:
    return {
        "d": c["hidden_size"], "h": c["num_attention_heads"],
        "qr": c["q_lora_rank"], "cr": c["kv_lora_rank"],
        "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
        "v": c["v_head_dim"], "f": c["intermediate_size"],
        "fe": c["moe_intermediate_size"], "held": c["n_routed_experts"],
        "first": c.get("first_expert", 0), "total": c["router_experts"],
        "k": c["num_experts_per_tok"], "vocab": c["vocab_size"],
        "dense": c["first_k_dense_replace"], "layers": c["num_hidden_layers"]}


def check(c: dict, name: str) -> None:
    m = _dims(c)
    # A program from before layer kinds cannot run this family: say so at
    # once, from the JAX-free parent (reading the source, not importing
    # it — the module imports jax), not after a replica has made 8 GB of
    # weights.
    source = os.path.join(os.path.dirname(modelcfg.BENCH_DIR), "tony_tpu",
                          "models", "transformer.py")
    with open(source) as f:
        if "layer_kinds" not in f.read():
            raise ValueError(
                f"{name}: the program beside this benchmark has no model "
                f"with layer_kinds (latent attention, sparse experts): "
                f"{source}")
    if c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc" \
            or not c["norm_topk_prob"]:
        raise ValueError(f"{name}: the program's router is sigmoid scores, "
                         f"pick by score + bias, weights normalised over "
                         f"the pick")
    if c["n_group"] != 1 or c["topk_group"] != 1:
        raise ValueError(f"{name}: the program's router has no group step "
                         f"(n_group = topk_group = 1)")
    if c["tie_word_embeddings"] or c["hidden_act"] != "silu" \
            or c["n_shared_experts"] != 1 or c["moe_layer_freq"] != 1 \
            or c.get("attention_bias"):
        raise ValueError(f"{name}: the program's block is an untied head, "
                         f"SwiGLU, one shared expert, experts in every "
                         f"layer after the dense ones, no attention bias")
    if not (0 <= m["first"] and 0 < m["held"]
            and m["first"] + m["held"] <= m["total"]
            and 0 < m["k"] <= m["total"]):
        raise ValueError(f"{name}: experts [first_expert, first_expert + "
                         f"n_routed_experts) must lie inside router_experts")
    if m["first"] % 2 or m["held"] % 2 or m["total"] % 2:
        raise ValueError(f"{name}: the router's columns are seeded in "
                         f"antithetic pairs: first_expert, n_routed_experts "
                         f"and router_experts must be even")
    if not 0 < m["dense"] < m["layers"]:
        raise ValueError(f"{name}: dense layers first, then expert layers")
    y = c["rope_scaling"]
    if y is None or y["type"] != "yarn" or m["rope"] % 2:
        raise ValueError(f"{name}: the rotary dims are even and YaRN-scaled")


def program_config(c: dict, **job):
    """``tony_tpu.models.transformer.TransformerConfig`` with
    ``layer_kinds`` (dtype and remat are the job script's)."""
    from tony_tpu.models import transformer as T
    m, y = _dims(c), c["rope_scaling"]
    return T.TransformerConfig(
        vocab_size=m["vocab"], d_model=m["d"], n_layers=m["layers"],
        n_heads=m["h"], d_ff=m["f"], max_seq=c["max_position_embeddings"],
        rms_eps=c["rms_norm_eps"], rope_base=float(c["rope_theta"]),
        rope_scaling=T.RopeYarn(
            factor=float(y["factor"]), beta_fast=float(y["beta_fast"]),
            beta_slow=float(y["beta_slow"]),
            original_max=y["original_max_position_embeddings"],
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        layer_kinds=tuple(layer_kinds(c)),
        latent=T.LatentAttention(q_rank=m["qr"], kv_rank=m["cr"],
                                 nope_dim=m["nope"], rope_dim=m["rope"],
                                 v_dim=m["v"]),
        experts=T.SparseExperts(total=m["total"], top_k=m["k"],
                                d_expert=m["fe"],
                                scale=float(c["routed_scaling_factor"]),
                                first=m["first"], held=m["held"]),
        **job)


def layer_kinds(c: dict) -> list[str]:
    n = c["first_k_dense_replace"]
    return ["dense"] * n + ["moe"] * (c["num_hidden_layers"] - n)


def _attn_params(m: dict) -> int:
    return (m["d"] * m["qr"] + m["qr"] * m["h"] * (m["nope"] + m["rope"])
            + m["d"] * (m["cr"] + m["rope"])
            + m["cr"] * m["h"] * (m["nope"] + m["v"])
            + m["h"] * m["v"] * m["d"]
            + 2 * m["d"] + m["qr"] + m["cr"])          # the four norms


def _expert_params(m: dict) -> int:
    return 3 * m["d"] * m["fe"]


def _router_params(m: dict) -> int:
    return m["d"] * m["total"] + m["total"]


def param_count(c: dict) -> int:
    m = _dims(c)
    dense = _attn_params(m) + 3 * m["d"] * m["f"]
    moe = (_attn_params(m) + _router_params(m)
           + (m["held"] + 1) * _expert_params(m))
    return (m["dense"] * dense + (m["layers"] - m["dense"]) * moe
            + 2 * m["vocab"] * m["d"] + m["d"])


def forward_flops_per_token(c: dict, seq: int) -> float:
    """A token meets, of the routed experts, the ``k x held / total`` of
    its picks that live here; the shared expert and the router whole."""
    m = _dims(c)
    attn = (2 * (_attn_params(m) - 2 * m["d"] - m["qr"] - m["cr"])
            + 2 * attended(seq, 0) * m["h"]
            * (m["nope"] + m["rope"] + m["v"]))
    moe = (2 * m["d"] * m["total"] + 2 * _expert_params(m)
           * (1 + m["k"] * m["held"] / m["total"]))
    return (m["dense"] * (attn + 2 * 3 * m["d"] * m["f"])
            + (m["layers"] - m["dense"]) * (attn + moe)
            + 2 * m["d"] * m["vocab"])


def expert_load(c: dict, ctx: dict | None) -> tuple[float, float]:
    """(assignments, touched held experts) of ONE expert layer in ONE
    decode step, reckoned from the run's slots (module docstring).
    Without a run: every held expert, no assignment."""
    m = _dims(c)
    if ctx is None:
        return 0.0, float(m["held"])
    rows = ctx["mix"]["slots"]
    share = m["k"] / m["total"]
    return (rows * share * m["held"],
            m["held"] * (1.0 - (1.0 - share) ** rows))


def decode_step_bytes(c: dict, live_rows: float, ctx: dict | None = None,
                      dtype_bytes: int = 2) -> float:
    """Bytes ONE decode step over the whole batch must read: attention,
    router (float32), shared and dense weights and the head once, one
    ``kv_lora_rank + qk_rope_head_dim`` row a live token a layer, and a
    routed expert's three matrices for each (layer, held expert)
    TOUCHED (:func:`expert_load`)."""
    m = _dims(c)
    n_moe = m["layers"] - m["dense"]
    once = (m["layers"] * _attn_params(m) + m["dense"] * 3 * m["d"] * m["f"]
            + n_moe * _expert_params(m) + m["vocab"] * m["d"] + m["d"])
    cache = live_rows * m["layers"] * (m["cr"] + m["rope"])
    _, touched = expert_load(c, ctx)
    return ((once + cache + n_moe * touched * _expert_params(m))
            * dtype_bytes + n_moe * _router_params(m) * 4)


def moe_experts_flops_bytes(c: dict, assignments: float, touched: float,
                            dtype_bytes: int = 2) -> tuple[float, float]:
    """What the routed-expert products (the ``tony_moe_gmm`` calls: gate,
    up, down) of ONE layer must do: 2 FLOPs a weight an assignment, and
    each touched expert's three matrices read once. The gathered rows
    and the output (``assignments x d`` values) are left out: under a
    hundredth of the weights at decode."""
    per = _expert_params(_dims(c))
    return 2.0 * assignments * per, touched * per * dtype_bytes


# ---------------------------------------------------------------- weights
def _normal(key, shape, fan_in, dtype):
    """``weights.normal``, rounded to bfloat16 by an explicit
    ``reduce_precision`` first. The values are the same; but the
    reference upcasts them straight back to float32, and on the TPU the
    compiler elides a float32 -> bfloat16 -> float32 round trip (excess
    precision is allowed), so the reference would run on UNROUNDED
    weights, 0.17% off the program's in every matrix (read on the chip,
    PR 28: the embedded rows 1.66e-3 apart). The explicit op stays."""
    w = (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5))
    if jnp.dtype(dtype) == jnp.bfloat16:
        w = jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
    return w.astype(dtype)


def _shapes(c: dict, kind: str) -> dict:
    m = _dims(c)
    d, h = m["d"], m["h"]
    out = {"wq_a": ((d, m["qr"]), d),
           "wq_b": ((m["qr"], h, m["nope"] + m["rope"]), m["qr"]),
           "wkv_a": ((d, m["cr"] + m["rope"]), d),
           "wkv_b": ((m["cr"], h, m["nope"] + m["v"]), m["cr"]),
           "wo": ((h, m["v"], d), h * m["v"])}
    if kind == "dense":
        f = m["f"]
        out.update({"w_gate": ((d, f), d), "w_up": ((d, f), d),
                    "w_down": ((f, d), f)})
        return out
    e, f = m["held"], m["fe"]
    out.update({"router": ((d, m["total"]), d),
                "router_bias": ((m["total"],), None),
                "w_gate": ((e, d, f), d), "w_up": ((e, d, f), d),
                "w_down": ((e, f, d), f),
                "shared_gate": ((d, f), d), "shared_up": ((d, f), d),
                "shared_down": ((f, d), f)})
    return out


def layer_weights(seed, li, c: dict, dtype, kind: str) -> dict:
    """Layer ``li``'s leaves (unstacked) of ``kind``. Traced or concrete
    ``li``. Router and selection bias float32, as the program holds them;
    the router's columns drawn in ANTITHETIC PAIRS ``(w, -w)``. Under
    seeded weights the normed activations share a large common component
    mu, so expert e's logit carries a constant ``mu . w_e`` that makes it
    hot or cold for that seed: a held block's load then swung 2.7% (one
    sigma) from seed to seed, the touched experts a step 5.55-5.82, and
    ``itl_p95_ms`` 1.2-2% — beyond its 1% bound (my chip runs, PR 28, 12
    seeds). A pair's constants cancel to first order, so a held block of
    whole pairs (``first_expert`` and ``n_routed_experts`` even) carries
    the load the deployment expects on every seed. Each column is also
    scaled to UNIT NORM (what a fan-in scaled draw has on average): a
    column 1% longer has a logit 1% wider and, at the 8-of-384 cut, 5%
    more load, which no pairing cancels (both of a pair share it) —
    with the embedding at unit variance this was the larger half of
    what was left: an expert's load over 24,576 tokens swung 5.0-5.3%
    in its pair's common part, 2.9% with unit columns (float32
    reference on the CPU, PR 28; on the chip ONE run was made after
    this, PERF.md section 6). The mechanics —
    sigmoid scores over all experts, top-k by score + bias, normalised
    weights — are untouched. The bias is a normal of scale
    ``BIAS_SCALE`` drawn from ``BIAS_KEY`` and
    the layer, NOT from ``seed``: it is what skews the experts' load (a
    tenth an expert at this scale), and drawn afresh for each seed it
    moved the touched experts a step by 3.5% and with them ``itl_p95_ms``
    by 1.2% from seed to seed (my chip runs, PR 28) — more than the
    metric's bound. Every seed now serves the same skew."""
    m = _dims(c)
    names = _LEAVES[kind]
    ks = jax.random.split(weights.layer_key(seed, li), len(names))
    shapes = _shapes(c, kind)
    out = {}
    for key, name in zip(ks, names):
        shape, fan_in = shapes[name]
        if name == "router_bias":
            out[name] = BIAS_SCALE * jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(BIAS_KEY), li),
                shape, jnp.float32)
        elif name == "router":
            # antithetic pairs (w, -w, w', -w', ...): see the docstring
            half = _normal(key, (shape[0], shape[1] // 2), fan_in,
                           jnp.float32)
            half = half / jnp.linalg.norm(half, axis=0, keepdims=True)
            out[name] = jnp.stack([half, -half], axis=-1).reshape(shape)
        else:
            out[name] = _normal(key, shape, fan_in, dtype)
    for name, n in (("attn_norm", m["d"]), ("q_norm", m["qr"]),
                    ("kv_norm", m["cr"]), ("mlp_norm", m["d"])):
        out[name] = jnp.ones((n,), dtype)
    return out


def outer_weights(seed, c: dict, dtype) -> dict:
    """Embedding, final norm and the untied head (the held rows). The
    embedding rows are drawn at UNIT variance (``torch.nn.Embedding``'s
    default), not at ``d^-0.5``: at ``d^-0.5`` a token's row is a
    hundredth of the first attention's output, the stream forgets the
    token after layer 0, and what is left — attention's average over the
    context — is shared by a request's positions: 12-22% of the normed
    activations' energy at the routers of layers 1-3, 4-7% at unit
    variance (float32 reference at the published widths on the CPU, 32
    prompts of 128, PR 28). Top-8-of-384 amplifies a common component,
    so a request then kept hitting the same experts, a held block's load
    over 32 requests swung 4-6% (0.8-2.9% at unit variance), and with it
    the touched experts a step and ``itl_p95_ms`` from seed to seed."""
    d, v = c["hidden_size"], c["vocab_size"]
    k_emb, k_out = jax.random.split(weights.outer_key(seed))
    return {"embed": _normal(k_emb, (v, d), 1, dtype),
            "final_norm": jnp.ones((d,), dtype),
            "lm_head": _normal(k_out, (d, v), d, dtype)}


def make_params(seed: int, c: dict, dtype, shardings=None):
    """The whole pytree in the program's layout — one stacked group a
    KIND, ``blocks[kind][leaf]: [layers of that kind, ...]`` — in ONE
    jitted call."""
    kinds = layer_kinds(c)

    @functools.partial(jax.jit, out_shardings=shardings)
    def build(seed):
        blocks = {
            kind: jax.vmap(lambda li, kind=kind: layer_weights(
                seed, li, c, dtype, kind))(jnp.asarray(
                    [li for li, k in enumerate(kinds) if k == kind],
                    jnp.int32))
            for kind in dict.fromkeys(kinds)}
        return dict(outer_weights(seed, c, dtype), blocks=blocks)

    return build(weights.as_seed(seed))


def leaf_name(li: int, leaf: str) -> str:
    return f"blocks/{leaf}/{li}"


def leaf_norms(tree: dict, minus: dict | None = None) -> dict:
    """{"blocks/wq_a/3": norm, "embed": norm, ...} of a params-shaped tree
    (less ``minus``); a layer goes by its index through all kinds. For a
    train cell, which this family has none of yet: the program refuses
    to train it."""
    raise NotImplementedError(
        "the latent-attention family is served only: the program has no "
        "train step for it, so no cell compares leaf norms")


# -------------------------------------------------------------- reference
def rms(x, w, c: dict):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + c["rms_norm_eps"]) * w


def yarn_frequencies(c: dict):
    """[rope/2] float32: ``theta_i = base^(-2i/rope)`` blended with
    ``theta_i / factor`` by the linear ramp between the dimensions at
    which ``beta_fast`` and ``beta_slow`` rotations fit in the original
    context (dimension of r rotations: ``rope ln(L / (2 pi r)) / (2 ln
    base)``; floor and ceil, clipped to [0, rope - 1])."""
    y, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]

    def at(rotations):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(at(y["beta_fast"])), 0)
    high = min(math.ceil(at(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    theta = jnp.exp(-i * (2.0 * math.log(base) / dim))
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return theta / y["factor"] * ramp + theta * (1.0 - ramp)


def _mscale(c: dict, key: str) -> float:
    y = c["rope_scaling"]
    if y["factor"] <= 1 or not y.get(key):
        return 1.0
    return 0.1 * y[key] * math.log(y["factor"]) + 1.0


def rope(x, positions, c: dict):
    """[B, S, H, rope] rotated by position, halves convention."""
    half = x.shape[-1] // 2
    ang = (positions[:, :, None, None].astype(jnp.float32)
           * yarn_frequencies(c))
    t = _mscale(c, "mscale") / _mscale(c, "mscale_all_dim")
    cos, sin = jnp.cos(ang) * t, jnp.sin(ang) * t
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, scale: float):
    """Causal softmax attention, q and k [B, S, H, dk], v [B, S, H, dv],
    over blocks of query rows so that the float32 scores never exceed
    ~1 GiB (8 rows x 64 heads x 2,048 keys: blocks of 256 queries)."""
    b, s, h, _ = q.shape
    bq = s
    while b * h * bq * s * 4 > (1 << 30) and bq % 2 == 0 and bq > 16:
        bq //= 2
    kpos = jnp.arange(s)

    def block(args):
        qb, i0 = args                                   # [b, bq, h, dk]
        sc = jnp.einsum("bqhd,bshd->bhqs", qb, k) * scale
        mask = (i0 + jnp.arange(bq))[:, None] >= kpos[None, :]
        p = jax.nn.softmax(jnp.where(mask, sc, _NEG), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", p, v)

    out = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(b, s // bq, bq, h, q.shape[-1]), 1, 0),
        jnp.arange(s // bq) * bq))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])


def _swiglu(h, gate, up, down):
    return jnp.einsum("tf,fd->td", jax.nn.silu(
        jnp.einsum("td,df->tf", h, gate)) * jnp.einsum("td,df->tf", h, up),
        down)


def route(h, p, c: dict):
    """h [T, d] → (picks [T, k], weights [T, k]) over ALL experts."""
    z = jax.nn.sigmoid(jnp.einsum("td,de->te", h, p["router"]))
    _, picks = jax.lax.top_k(z + p["router_bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(z, picks, axis=-1)
    return picks, (w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
                   * c["routed_scaling_factor"])


def experts(h, p, c: dict):
    """The held experts' part of the routed sum plus the shared expert,
    on h [T, d]: every held expert for every token, weighted by the pick
    (0 where it was not picked), one expert at a time."""
    picks, w = route(h, p, c)
    first = c.get("first_expert", 0)

    def one(acc, xs):
        e, gate, up, down = xs
        mine = jnp.sum(jnp.where(picks == first + e, w, 0.0), axis=-1)
        return acc + mine[:, None] * _swiglu(h, gate, up, down), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
         p["w_down"]))
    return routed + _swiglu(h, p["shared_gate"], p["shared_up"],
                            p["shared_down"])


def layer_forward(x, p, c: dict, kind: str):
    """One layer of ``kind`` on [B, S, d] float32, attention expanded."""
    m = _dims(c)
    b, s, d = x.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = rms(x, p["attn_norm"], c)
    q = jnp.einsum("bsr,rhk->bshk",
                   rms(jnp.einsum("bsd,dr->bsr", h, p["wq_a"]),
                       p["q_norm"], c), p["wq_b"])
    kv = jnp.einsum("bsd,dr->bsr", h, p["wkv_a"])
    c_kv = rms(kv[..., :m["cr"]], p["kv_norm"], c)
    k_r = rope(kv[:, :, None, m["cr"]:], pos, c)          # one head
    q = jnp.concatenate([q[..., :m["nope"]],
                         rope(q[..., m["nope"]:], pos, c)], axis=-1)
    kvb = jnp.einsum("bsc,chk->bshk", c_kv, p["wkv_b"])
    k = jnp.concatenate(
        [kvb[..., :m["nope"]],
         jnp.broadcast_to(k_r, (b, s, m["h"], m["rope"]))], axis=-1)
    scale = ((m["nope"] + m["rope"]) ** -0.5
             * _mscale(c, "mscale_all_dim") ** 2)
    o = attention(q, k, kvb[..., m["nope"]:], scale)
    x = x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    h = rms(x, p["mlp_norm"], c).reshape(b * s, d)
    if kind == "dense":
        out = _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    else:
        out = experts(h, p, c)
    return x + out.reshape(b, s, d)


def head(o, x, c: dict):
    return jnp.einsum("bsd,dv->bsv", rms(x, o["final_norm"], c),
                      o["lm_head"])
