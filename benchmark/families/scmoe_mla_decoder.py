"""The shortcut-connected, latent-attention, zero-expert decoder family
(LongCat-Flash's layers, as LongCat-Flash-Chat configures them): every
layer is a DOUBLE layer — two (latent attention, dense SwiGLU) halves in
sequence and ONE routed block that reads the first half's normed stream
and joins the stream after the second half — between an embedding and an
untied head. Provides what ``dense_decoder.py``'s docstring lists. The
program block it stands for is ``tony_tpu.models.transformer`` with
``layer_kinds`` of ``latent2_scmoe`` (served through ``models/decode.py``;
the program refuses to train it).

One LAYER, on x [B, S, d] (``rms`` with weight, the published epsilon),
halves i = 0, 1 with their own ``attn_norm_i``, ``mlp_norm_i``, latent
attention ``A_i`` and dense SwiGLU ``F_i`` (``ffn_hidden_size`` wide), and
one routed block ``M``:

    x1 = x  + A_0(rms(x;  attn_norm_0))
    h1 = rms(x1; mlp_norm_0)
    m  = M(h1)                      # the shortcut: from the FIRST half
    x2 = x1 + F_0(h1)
    x3 = x2 + A_1(rms(x2; attn_norm_1))
    y  = x3 + F_1(rms(x3; mlp_norm_1)) + m     # added after the SECOND

- ``A_i``: ``c_q = rms(h W_qa) s_q``, ``s_q = (hidden / q_lora_rank)^0.5``
  (``mla_scale_q_lora``); ``q = c_q W_qb`` -> heads of ``[q_n (nope); q_r
  (rope)]``; ``[c_kv; k_r] = h W_kva``, ``c_kv <- rms(c_kv) s_kv``, ``s_kv
  = (hidden / kv_lora_rank)^0.5`` (``mla_scale_kv_lora``); ``k_r`` (ONE
  head, shared by all) is not scaled; ``q_r, k_r <- rope(.)``, base
  ``rope_theta``, no scaling of the frequencies; ``[k_n; v]`` a head ``=
  c_kv W_kvb``. Scores ``(q_n.k_n + q_r.k_r) (nope + rope)^-0.5``, causal
  softmax, ``sum p v`` -> ``W_o``. (Upstream scales ``q`` after ``W_qb``:
  ``W_qb`` is linear, the same.) This reference is the EXPANDED form at
  every position; the program decodes in the absorbed form over one
  stored row ``[c_kv (scaled); k_r]`` a token AN ATTENTION — two row-sets
  a layer.
- ``M``: ``z = softmax(h1 W_r)`` over ALL ``router_experts +
  zero_expert_num`` outputs (the routed experts, then the zero ones;
  float32; no bias in the product); the ``moe_topk`` largest of ``z + b``
  (``b``: the selection bias, for the pick only); ``w = z[picked] x
  routed_scaling_factor``, NOT renormalised over the pick; ``m = sum over
  picked e < router_experts of w_e SwiGLU_e(h1) + (sum over picked e >=
  router_experts of w_e) h1``: a zero expert is the identity
  (``zero_expert_type``), has no weights and costs no product. No shared
  expert.
- THE SHARE: this chip holds routed experts ``[first_expert, first_expert
  + n_routed_experts)``; routing is over all outputs, the routed sum runs
  over the picked experts held here, the zero-expert term is whole for
  this chip's own tokens (it needs no weights, so in the deployment it
  never leaves the token's rank: every rank computes it alike and the
  share-sum counts it once), and what the absent experts would add is
  left out — here as in the program. The reference computes EVERY held
  expert for EVERY token and weights by the pick: no sort, no dispatch.
- head: ``rms`` then ``W_head`` over the held vocabulary rows.

Every layer's kind is ``"moe"`` (``metrics/moe_experts_roofline.serve.py``
multiplies by ``layer_kinds(c).count("moe")``); a layer's leaves are the
program's: the halves' stacked on a leading axis of 2.

Memory: :func:`attention` walks the queries in blocks and the experts are
walked one at a time, as ``mla_moe_decoder.py`` does. At
``lib/reference.served_token_gaps``' 8 rows of 4,096 positions ONE double
layer's program peaks at 9.97 GB and the head's at 2.95 GB (described-chip
compile, PR 37; a layer's weights are regenerated inside it and never
stand whole in float32), beside nothing: the program's state is freed
first.

Touched experts, as ``mla_moe_decoder.expert_load`` reckons them: each of
``rows`` tokens a step picks ``moe_topk`` of the ``router_experts +
zero_expert_num`` outputs uniformly, ``rows`` the mix's slots (within 0.7%
of the program's counter on the chip: :func:`expert_load`). A zero pick
reads no byte and does no FLOP.

Departures from the published model, shared with the program and noted
in the configuration file: rotate-half layout of the rotary dims
(published interleaved: a permutation of seeded weights); ``W_qa`` and
``W_kva`` separate (one fused matrix upstream: the same mathematics).
"""

from __future__ import annotations

import functools
import os

from benchmark.lib import modelcfg, weights
from benchmark.lib.flops import attended
from benchmark.lib.lazyjax import jax, jnp

#: a half's matrices, stacked [2, ...] in a layer's leaves, in the order
#: their keys are split
_HALF_LEAVES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "mlp_gate",
                "mlp_up", "mlp_down")
_ROUTED_LEAVES = ("router", "router_bias", "w_gate", "w_up", "w_down")
_HALF_NORMS = ("attn_norm", "q_norm", "kv_norm", "mlp_norm")
#: each matmul leaf's contraction axes (what the controls round over); a
#: half's leaves carry the leading axis of 2, the experts an expert axis
CONTRACT = {"wq_a": (1,), "wq_b": (1,), "wkv_a": (1,), "wkv_b": (1,),
            "wo": (1, 2), "mlp_gate": (1,), "mlp_up": (1,),
            "mlp_down": (1,), "router": (0,),
            "w_gate": (-2,), "w_up": (-2,), "w_down": (-2,),
            "lm_head": (0,)}
HEAD_LEAVES = ("final_norm", "lm_head")
#: scale of the selection bias, and the key it is drawn from (``assumed``
#: in the configuration): the SAME draw for every --seed, folded by layer
BIAS_SCALE = 2.5e-4
BIAS_KEY = 20261001
_NEG = -1e30
KERNEL = "tony_moe_gmm"


# ------------------------------------------------------ check and counts
def _dims(c: dict) -> dict:
    return {
        "d": c["hidden_size"], "h": c["num_attention_heads"],
        "qr": c["q_lora_rank"], "cr": c["kv_lora_rank"],
        "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
        "v": c["v_head_dim"], "f": c["ffn_hidden_size"],
        "fe": c["expert_ffn_hidden_size"], "held": c["n_routed_experts"],
        "first": c.get("first_expert", 0), "total": c["router_experts"],
        "zero": c["zero_expert_num"], "k": c["moe_topk"],
        "vocab": c["vocab_size"], "layers": c["num_layers"]}


def check(c: dict, name: str) -> None:
    m = _dims(c)
    # A program from before zero experts cannot run this family: say so at
    # once, from the JAX-free parent (reading the source, not importing
    # it — the module imports jax), not after a replica has made 10 GB of
    # weights.
    source = os.path.join(os.path.dirname(modelcfg.BENCH_DIR), "tony_tpu",
                          "models", "transformer.py")
    with open(source) as f:
        text = f.read()
    if "n_zero" not in text or '"latent2_scmoe"' not in text:
        raise ValueError(
            f"{name}: the program beside this benchmark has no double "
            f"layer with a shortcut-connected expert block and no zero "
            f"experts (layer kind 'latent2_scmoe', SparseExperts.n_zero): "
            f"{source}")
    if c["attention_method"] != "MLA" or c.get("attention_bias"):
        raise ValueError(f"{name}: this family attends through latent "
                         f"attention (attention_method MLA), no bias")
    if c["zero_expert_type"] != "identity":
        raise ValueError(f"{name}: the program's zero experts are the "
                         f"identity")
    if c["norm_topk_prob"] or c["router_bias"] or c["tie_word_embeddings"] \
            or c["hidden_act"] != "silu":
        raise ValueError(f"{name}: this family's router is a softmax over "
                         f"all outputs, not renormalised over the pick, no "
                         f"bias in its product; SwiGLU; an untied head")
    if c.get("num_hidden_layers", m["layers"]) != m["layers"]:
        raise ValueError(f"{name}: num_hidden_layers restates num_layers "
                         f"for the harness's tests; they differ")
    if not (0 <= m["first"] and 0 < m["held"]
            and m["first"] + m["held"] <= m["total"] and 0 <= m["zero"]
            and 0 < m["k"] <= m["total"] + m["zero"]):
        raise ValueError(f"{name}: experts [first_expert, first_expert + "
                         f"n_routed_experts) must lie inside router_experts")
    if m["first"] % 2 or m["held"] % 2 or m["total"] % 2 or m["zero"] % 2:
        raise ValueError(f"{name}: the router's columns are seeded in "
                         f"antithetic pairs: first_expert, n_routed_experts,"
                         f" router_experts and zero_expert_num must be even")
    if m["rope"] % 2:
        raise ValueError(f"{name}: the rotary dims are even")


def _scale(c: dict, flag: str, rank: str) -> float:
    return (c["hidden_size"] / c[rank]) ** 0.5 if c[flag] else 1.0


def program_config(c: dict, **job):
    """``tony_tpu.models.transformer.TransformerConfig`` with
    ``layer_kinds`` (dtype and remat are the job script's)."""
    from tony_tpu.models import transformer as T
    m = _dims(c)
    return T.TransformerConfig(
        vocab_size=m["vocab"], d_model=m["d"], n_layers=m["layers"],
        n_heads=m["h"], d_ff=m["f"], max_seq=c["max_position_embeddings"],
        rms_eps=c["rms_norm_eps"], rope_base=float(c["rope_theta"]),
        layer_kinds=("latent2_scmoe",) * m["layers"],
        latent=T.LatentAttention(
            q_rank=m["qr"], kv_rank=m["cr"], nope_dim=m["nope"],
            rope_dim=m["rope"], v_dim=m["v"],
            q_scale=_scale(c, "mla_scale_q_lora", "q_lora_rank"),
            kv_scale=_scale(c, "mla_scale_kv_lora", "kv_lora_rank")),
        experts=T.SparseExperts(
            total=m["total"], top_k=m["k"], d_expert=m["fe"],
            scale=float(c["routed_scaling_factor"]), first=m["first"],
            held=m["held"], n_shared=0, route="softmax", n_zero=m["zero"]),
        **job)


def layer_kinds(c: dict) -> list[str]:
    """Every (double) layer is of kind ``"moe"`` (module docstring)."""
    return ["moe"] * c["num_layers"]


def _attn_params(m: dict) -> int:
    """The five matrices of ONE latent attention."""
    return (m["d"] * m["qr"] + m["qr"] * m["h"] * (m["nope"] + m["rope"])
            + m["d"] * (m["cr"] + m["rope"])
            + m["cr"] * m["h"] * (m["nope"] + m["v"])
            + m["h"] * m["v"] * m["d"])


def _half_params(m: dict) -> int:
    """One half: an attention, a dense SwiGLU and the four norms."""
    return (_attn_params(m) + 3 * m["d"] * m["f"]
            + 2 * m["d"] + m["qr"] + m["cr"])


def _expert_params(m: dict) -> int:
    return 3 * m["d"] * m["fe"]


def _router_params(m: dict) -> int:
    outputs = m["total"] + m["zero"]
    return m["d"] * outputs + outputs


def param_count(c: dict) -> int:
    m = _dims(c)
    layer = (2 * _half_params(m) + _router_params(m)
             + m["held"] * _expert_params(m))
    return m["layers"] * layer + 2 * m["vocab"] * m["d"] + m["d"]


def forward_flops_per_token(c: dict, seq: int) -> float:
    """A token meets both halves and the router whole and, of the routed
    experts, the ``k x held / (total + zero)`` of its picks that live
    here; a zero pick costs nothing."""
    m = _dims(c)
    half = (2 * (_attn_params(m) + 3 * m["d"] * m["f"])
            + 2 * attended(seq, 0) * m["h"]
            * (m["nope"] + m["rope"] + m["v"]))
    outputs = m["total"] + m["zero"]
    moe = (2 * m["d"] * outputs
           + 2 * _expert_params(m) * m["k"] * m["held"] / outputs)
    return m["layers"] * (2 * half + moe) + 2 * m["d"] * m["vocab"]


def expert_load(c: dict, ctx: dict | None) -> tuple[float, float]:
    """(assignments, touched held experts) of ONE layer's routed block in
    ONE decode step, reckoned as ``mla_moe_decoder.expert_load`` reckons
    them: ``rows`` tokens a step, each picking ``k`` of the ``total +
    zero`` outputs uniformly, a held expert touched with probability ``1
    - (1 - k / (total + zero))^rows``; ``rows`` the mix's ``slots`` — the
    program routes every slot in every step, and in this cell's window
    every slot is live from the end of the opening sweep of admissions
    (second ~5) on. Held against the counter on the chip (PR 37, seed
    2147533701, ``engine.stats()["moe_expert_touches"]["decode"]`` over
    the 4,312 decode steps between the run's two snapshots, the drain
    included): 10.09 a layer a step counted, 10.16 reckoned (0.7% over).
    ISSUE 37 asked for ``window_full_moe_decoder``'s rule — the run's mean
    live slots, tokens kept over steps executed, plus one — which reads
    46.5 rows and 8.30 touched here, 18% UNDER the counter: the steps
    count the drain's emptying tail and every chunk's overrun as lost
    slots, but the chunks that touch experts run with the slots full.
    Without a run: every held expert, no assignment."""
    m = _dims(c)
    if ctx is None:
        return 0.0, float(m["held"])
    rows = ctx["mix"]["slots"]
    share = m["k"] / (m["total"] + m["zero"])
    return (rows * share * m["held"],
            m["held"] * (1.0 - (1.0 - share) ** rows))


def stored_row(c: dict) -> int:
    """Values of a cache row as the program stores it: ``kv_lora_rank +
    qk_rope_head_dim`` rounded up to whole 128-lane tiles (576 -> 640)."""
    return -(-(c["kv_lora_rank"] + c["qk_rope_head_dim"]) // 128) * 128


def decode_step_bytes(c: dict, live_rows: float, ctx: dict | None = None,
                      dtype_bytes: int = 2) -> float:
    """Bytes ONE decode step over the whole batch must read: each
    matrix of both halves and the head once, the routers in float32, a
    stored row (:func:`stored_row`) a live token an ATTENTION — two a
    layer —, and a routed expert's three matrices for each (layer, held
    expert) TOUCHED (:func:`expert_load`). A zero pick reads nothing."""
    m = _dims(c)
    once = (m["layers"] * 2 * _half_params(m) + m["vocab"] * m["d"]
            + m["d"])
    cache = live_rows * 2 * m["layers"] * stored_row(c)
    _, touched = expert_load(c, ctx)
    return ((once + cache + m["layers"] * touched * _expert_params(m))
            * dtype_bytes + m["layers"] * _router_params(m) * 4)


def moe_experts_flops_bytes(c: dict, assignments: float, touched: float,
                            dtype_bytes: int = 2) -> tuple[float, float]:
    """What the routed-expert products (the ``tony_moe_gmm`` calls: gate,
    up, down) of ONE layer must do: 2 FLOPs a weight an assignment that
    landed on a held expert, and each touched expert's three matrices
    read once. Zero picks are in neither."""
    per = _expert_params(_dims(c))
    return 2.0 * assignments * per, touched * per * dtype_bytes


# ---------------------------------------------------------------- weights
def _normal(key, shape, fan_in, dtype):
    """``weights.normal``, rounded to bfloat16 by an explicit
    ``reduce_precision`` first (``mla_moe_decoder._normal`` says why: the
    TPU compiler elides a float32 -> bfloat16 -> float32 round trip, and
    the reference would run on unrounded weights)."""
    w = (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5))
    if jnp.dtype(dtype) == jnp.bfloat16:
        w = jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
    return w.astype(dtype)


def _shapes(c: dict) -> dict:
    """leaf -> (shape of ONE layer, fan-in): a half's leaves behind a
    leading axis of 2."""
    m = _dims(c)
    d, h, f = m["d"], m["h"], m["f"]
    e, fe = m["held"], m["fe"]
    outputs = m["total"] + m["zero"]
    # the two matrices that read a SCALED bottleneck are drawn for an
    # input of that variance: layer_weights says why
    s_q = _scale(c, "mla_scale_q_lora", "q_lora_rank")
    s_kv = _scale(c, "mla_scale_kv_lora", "kv_lora_rank")
    return {"wq_a": ((2, d, m["qr"]), d),
            "wq_b": ((2, m["qr"], h, m["nope"] + m["rope"]),
                     m["qr"] * s_q ** 2),
            "wkv_a": ((2, d, m["cr"] + m["rope"]), d),
            "wkv_b": ((2, m["cr"], h, m["nope"] + m["v"]),
                      m["cr"] * s_kv ** 2),
            "wo": ((2, h, m["v"], d), h * m["v"]),
            "mlp_gate": ((2, d, f), d), "mlp_up": ((2, d, f), d),
            "mlp_down": ((2, f, d), f),
            "router": ((d, outputs), d),
            "router_bias": ((outputs,), None),
            "w_gate": ((e, d, fe), d), "w_up": ((e, d, fe), d),
            "w_down": ((e, fe, d), fe)}


def layer_weights(seed, li, c: dict, dtype, kind: str) -> dict:
    """Layer ``li``'s leaves (unstacked: the program's leaves of ONE
    double layer). Traced or concrete ``li``. ``mla_moe_decoder.
    layer_weights``'s initializer: fan-in scaled normals; router and
    selection bias float32; the router's columns — all ``router_experts +
    zero_expert_num`` of them — of UNIT NORM, drawn in ANTITHETIC PAIRS
    ``(w, -w)``, so that a held block of whole pairs carries the load the
    deployment expects on every seed (a pair's constant offsets under
    seeded weights cancel to first order; routed columns pair with
    routed, zero with zero). The bias is a normal of scale
    ``BIAS_SCALE`` drawn from ``BIAS_KEY`` and the layer, NOT from
    ``seed``: every seed serves the same skew. Its scale is a softmax
    score's, not a sigmoid's: under unit-norm columns a logit is standard
    normal, the 12th of 768 scores lies 2.16 sigma up at ``z = e^2.16 /
    (768 e^0.5) = 0.0068``, and a shift of 2.5e-4 in score is 0.037 in
    logit there: about a tenth of an expert's load, as Kimi's 0.005 is on
    a sigmoid score.

    ``W_qb`` and ``W_kvb`` read bottlenecks that carry the constant
    scales ``s_q`` = 2 and ``s_kv`` = 3.46, and are drawn at ``(fan_in x
    s^2)^-0.5``: what fan-in scaling is FOR — outputs of unit variance —
    given an input of variance ``s^2``. Drawn at ``fan_in^-0.5`` the
    queries have standard deviation 2 and keys and values 3.46, the
    scores 5.7: attention is nearly a hard pick, its output (rms 2.7)
    is seven eighths of the stream's energy, and a relative perturbation
    of the stream comes out of EACH of the eight attentions about eight
    times larger (a score moves by 5.7 x sqrt 2 times the perturbation):
    bfloat16's own rounding grew to a served-token mismatch share of
    0.435 and a mean gap of 0.150 against the float32 reference — where
    float32 activations on the same bfloat16 weights read 0.0013 and
    7e-9, the same program without the scales 0.033 and 0.00045, and
    Kimi's configuration in the same probe 0.070 and 0.0025 (the
    program's prefill at the published widths on the CPU, 2 x 384
    tokens, PR 37) — so ``correct`` could have told nothing: the held
    experts zeroed read 0.13. A trained model's projections have learned
    the scales they sit behind; seeded ones are drawn for them. The
    scales themselves run as published, in the program and here."""
    m = _dims(c)
    names = _HALF_LEAVES + _ROUTED_LEAVES
    ks = jax.random.split(weights.layer_key(seed, li), len(names))
    shapes = _shapes(c)
    out = {}
    for key, name in zip(ks, names):
        shape, fan_in = shapes[name]
        if name == "router_bias":
            out[name] = BIAS_SCALE * jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(BIAS_KEY), li),
                shape, jnp.float32)
        elif name == "router":
            half = _normal(key, (shape[0], shape[1] // 2), fan_in,
                           jnp.float32)
            half = half / jnp.linalg.norm(half, axis=0, keepdims=True)
            out[name] = jnp.stack([half, -half], axis=-1).reshape(shape)
        else:
            out[name] = _normal(key, shape, fan_in, dtype)
    for name, n in (("attn_norm", m["d"]), ("q_norm", m["qr"]),
                    ("kv_norm", m["cr"]), ("mlp_norm", m["d"])):
        out[name] = jnp.ones((2, n), dtype)
    return out


def outer_weights(seed, c: dict, dtype) -> dict:
    """Embedding, final norm and the untied head (the held rows); the
    embedding rows at UNIT variance (``mla_moe_decoder.outer_weights``
    says why: the stream carries the token past the first attention)."""
    d, v = c["hidden_size"], c["vocab_size"]
    k_emb, k_out = jax.random.split(weights.outer_key(seed))
    return {"embed": _normal(k_emb, (v, d), 1, dtype),
            "final_norm": jnp.ones((d,), dtype),
            "lm_head": _normal(k_out, (d, v), d, dtype)}


def make_params(seed: int, c: dict, dtype, shardings=None):
    """The whole pytree in the program's layout — ONE stacked group,
    ``blocks["latent2_scmoe"][leaf]: [layers, ...]`` (a half's leaves
    ``[layers, 2, ...]``) — in ONE jitted call."""
    @functools.partial(jax.jit, out_shardings=shardings)
    def build(seed):
        blocks = jax.vmap(lambda li: layer_weights(
            seed, li, c, dtype, "moe"))(jnp.arange(c["num_layers"],
                                                   dtype=jnp.int32))
        return dict(outer_weights(seed, c, dtype),
                    blocks={"latent2_scmoe": blocks})

    return build(weights.as_seed(seed))


def leaf_name(li: int, leaf: str) -> str:
    return f"blocks/{leaf}/{li}"


def leaf_norms(tree: dict, minus: dict | None = None) -> dict:
    """For a train cell, which this family has none of: the program
    refuses to train a model with layer_kinds."""
    raise NotImplementedError(
        "the shortcut-connected family is served only: the program has no "
        "train step for it, so no cell compares leaf norms")


# -------------------------------------------------------------- reference
def rms(x, w, c: dict):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + c["rms_norm_eps"]) * w


def rope(x, positions, c: dict):
    """[B, S, H, rope] rotated by position, halves convention, ``theta_i =
    rope_theta^(-2i / rope)``, nothing scaled."""
    half = x.shape[-1] // 2
    theta = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(float(c["rope_theta"])) / half))
    ang = positions[:, :, None, None].astype(jnp.float32) * theta
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, scale: float):
    """Causal softmax attention, q and k [B, S, H, dk], v [B, S, H, dv],
    over blocks of query rows so that the float32 scores never exceed
    ~1 GiB."""
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


def latent_attention(h, p, c: dict):
    """``A_i`` on normed h [B, S, d], expanded; ``p``: ONE half's
    leaves."""
    m = _dims(c)
    b, s, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    c_q = rms(jnp.einsum("bsd,dr->bsr", h, p["wq_a"]), p["q_norm"], c) \
        * _scale(c, "mla_scale_q_lora", "q_lora_rank")
    q = jnp.einsum("bsr,rhk->bshk", c_q, p["wq_b"])
    kv = jnp.einsum("bsd,dr->bsr", h, p["wkv_a"])
    c_kv = rms(kv[..., :m["cr"]], p["kv_norm"], c) \
        * _scale(c, "mla_scale_kv_lora", "kv_lora_rank")
    k_r = rope(kv[:, :, None, m["cr"]:], pos, c)           # one head
    q = jnp.concatenate([q[..., :m["nope"]],
                         rope(q[..., m["nope"]:], pos, c)], axis=-1)
    kvb = jnp.einsum("bsc,chk->bshk", c_kv, p["wkv_b"])
    k = jnp.concatenate(
        [kvb[..., :m["nope"]],
         jnp.broadcast_to(k_r, (b, s, m["h"], m["rope"]))], axis=-1)
    o = attention(q, k, kvb[..., m["nope"]:],
                  (m["nope"] + m["rope"]) ** -0.5)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _swiglu(h, gate, up, down):
    return jnp.einsum("tf,fd->td", jax.nn.silu(
        jnp.einsum("td,df->tf", h, gate)) * jnp.einsum("td,df->tf", h, up),
        down)


def route(h, p, c: dict):
    """h [T, d] -> (picks [T, k], weights [T, k]) over ALL outputs: the
    routed experts, then the zero ones. Not renormalised."""
    z = jax.nn.softmax(jnp.einsum("td,de->te", h, p["router"]), axis=-1)
    _, picks = jax.lax.top_k(z + p["router_bias"], c["moe_topk"])
    return picks, (jnp.take_along_axis(z, picks, axis=-1)
                   * c["routed_scaling_factor"])


def experts(h, p, c: dict, zero_term: bool = True):
    """``M`` on h [T, d]: the held experts' part of the routed sum —
    every held expert for every token, weighted by the pick (0 where it
    was not picked), one expert at a time — plus the zero experts' term,
    ``(sum of the weights of the picks >= router_experts) x h``
    (``zero_term`` False leaves it out: ``tools/control_zero.py``'s
    fault)."""
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
    if not zero_term:
        return routed
    zero = jnp.sum(jnp.where(picks >= c["router_experts"], w, 0.0), axis=-1)
    return routed + zero[:, None] * h


def half_of(p: dict, i: int) -> dict:
    """Half ``i`` of a layer's leaves."""
    return {n: p[n][i] for n in _HALF_LEAVES + _HALF_NORMS}


def layer_forward(x, p, c: dict, kind: str, routed=experts):
    """ONE whole double layer on [B, S, d] float32, attention expanded:
    the six lines of the module docstring (``routed``: the block ``M``;
    a control hands in a faulty one)."""
    b, s, d = x.shape
    p0, p1 = half_of(p, 0), half_of(p, 1)

    def mlp(h, q):
        return _swiglu(h.reshape(b * s, d), q["mlp_gate"], q["mlp_up"],
                       q["mlp_down"]).reshape(b, s, d)

    x1 = x + latent_attention(rms(x, p0["attn_norm"], c), p0, c)
    h1 = rms(x1, p0["mlp_norm"], c)
    m = routed(h1.reshape(b * s, d), p, c).reshape(b, s, d)
    x2 = x1 + mlp(h1, p0)
    x3 = x2 + latent_attention(rms(x2, p1["attn_norm"], c), p1, c)
    return x3 + mlp(rms(x3, p1["mlp_norm"], c), p1) + m


def head(o, x, c: dict):
    return jnp.einsum("bsd,dv->bsv", rms(x, o["final_norm"], c),
                      o["lm_head"])
