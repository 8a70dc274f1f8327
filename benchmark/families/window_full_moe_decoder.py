"""The interleaved window / full attention, sparse-expert decoder family
(Cohere2-MoE's layers, as Command A+ configures them): ``layer_types``
names each layer ``sliding_attention`` or ``full_attention``; every layer
is a PARALLEL block with sigmoid-routed experts beside averaged shared
ones, between an embedding and a head tied to it. Provides what
``dense_decoder.py``'s docstring lists. The program block it stands for
is ``tony_tpu.models.transformer`` with ``layer_kinds`` of ``window_moe``
and ``full_moe`` (served through ``models/decode.py``; the program
refuses to train it).

One layer, on x [B, S, d] (every matrix bias-free):

- ``h = LN(x) = (x - mean x) / sqrt(var x + layer_norm_eps) * g``
  (weight-only LayerNorm; statistics in float32).
- attention: ``q = h W_q`` -> ``num_attention_heads`` heads of
  ``head_dim``; ``k = h W_k``, ``v = h W_v`` -> ``num_key_value_heads``
  heads (query head i reads K/V head ``i // (heads / kv_heads)``); no q/k
  norm. In a ``sliding_attention`` layer ``q, k <- rope(q, k)`` over the
  whole head (``rotary_pct`` 1, base ``rope_theta``) and query i sees the
  keys j with ``0 <= i - j < sliding_window``; in a ``full_attention``
  layer NO positional rotation and the causal mask. Scores x
  ``head_dim^-0.5``, softmax, heads concatenated -> ``W_o`` = ``a``.
- experts, from the SAME ``h``: ``z = sigmoid(h W_r)`` over ALL
  ``router_experts`` (float32); the ``num_experts_per_tok`` largest z;
  ``w = z[picked] / (sum z[picked] + 1e-20)`` (``norm_topk_prob``; no
  selection bias, no scale); ``routed = sum_picked w_e SwiGLU_e(h)``;
  ``shared = (1 / num_shared_experts) sum_s SwiGLU_s(h)``
  (``shared_expert_combination_strategy: average``); ``m = routed +
  shared``. Every expert is ``intermediate_size`` wide.
- ``x <- x + a + m`` (``use_parallel_block``).
- head: ``LN_final(x) E^T x logit_scale``, ``E`` the embedding
  (``tie_word_embeddings``).
- THE SHARE: this chip holds experts ``[first_expert, first_expert +
  num_experts)``; routing and normalisation are over all
  ``router_experts``, the sum runs over the picked experts held here, the
  shared experts are whole, and what the absent experts would add is left
  out — here as in the program. The reference computes EVERY held expert
  for EVERY token and weights by the pick: no sort, no dispatch.

Which layer is which. ``lib/reference.py`` compiles one program a KIND
and hands ``layer_forward`` a layer's leaves, not its index; and
``metrics/moe_experts_roofline.serve.py`` multiplies by
``layer_kinds(c).count("moe")``. So every layer's kind here is ``"moe"``
and a layer's TYPE rides in its leaves: ``router_bias`` — the selection
bias the program's router adds to its scores FOR THE PICK ONLY, which
this architecture does not have — is a constant over a layer's experts,
0 in a sliding layer and 1 in a full one. A constant cannot change a
pick (the k largest of ``z + b`` are the k largest of ``z``), so the
program routes as without it, and the reference reads ``router_bias[0]``
as the layer's type: rotation and window are selects on it, one
compiled program for both types. A ``benchmark`` PR that hands
``layer_forward`` the layer's index makes the leaf zeros (PERF.md
section 7).

Memory: the reference is handed ``check_rows`` rows
(``jobs/serve_replica_rows.py``) of up to 16,384 positions. 128 heads of
float32 scores would be 137 GB whole, so :func:`attention` walks the
queries in blocks (a ``lax.map``; still plain ``jax.numpy``) with the 16
queries of a K/V head grouped (K and V are never repeated), and the
experts, routed and shared, are walked one at a time (``lax.scan``:
[tokens, 4096] per expert).

Touched experts, as ``mla_moe_decoder.py`` reckons them
(:func:`expert_load`). Live rows: ``drivers/serve.py`` hands
``decode_step_bytes`` the mean LIVE rows a decode step (prompt + tokens
so far, averaged over requests and steps); a full layer must read those,
a sliding layer ``min(rows, sliding_window)`` of them, whose mean is
reckoned from the mix's own fixed request shapes
(``lib/traffic.request_shapes``, JAX-free): :func:`window_share`.

Departures from the published model, shared with the program and noted
in the configuration file: rotate-half layout of the rotary dims
(published ``rope_gptj``, interleaved pairs: a permutation of seeded
columns); separate q, k, v matrices.
"""

from __future__ import annotations

import functools
import os

from benchmark.lib import modelcfg, traffic, weights
from benchmark.lib.flops import attended
from benchmark.lib.lazyjax import jax, jnp

_LEAVES = ("wq", "wk", "wv", "wo", "router", "router_bias", "w_gate",
           "w_up", "w_down", "shared_gate", "shared_up", "shared_down")
#: each matmul leaf's contraction axes (what the controls round over);
#: the experts, routed and shared, carry a leading expert axis. The tied
#: embedding is left as it is: it is the lookup table too.
CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "router": (0,),
            "w_gate": (-2,), "w_up": (-2,), "w_down": (-2,),
            "shared_gate": (-2,), "shared_up": (-2,), "shared_down": (-2,)}
HEAD_LEAVES = ("final_norm", "embed")
_NEG = -1e30
KERNEL = "tony_moe_gmm"
SLIDING, FULL = "sliding_attention", "full_attention"


# ------------------------------------------------------ check and counts
def _dims(c: dict) -> dict:
    return {
        "d": c["hidden_size"], "h": c["num_attention_heads"],
        "kv": c["num_key_value_heads"], "hd": c["head_dim"],
        "f": c["intermediate_size"], "held": c["num_experts"],
        "first": c.get("first_expert", 0), "total": c["router_experts"],
        "k": c["num_experts_per_tok"], "ns": c["num_shared_experts"],
        "vocab": c["vocab_size"], "layers": c["num_hidden_layers"],
        "window": c["sliding_window"],
        "full": sum(t == FULL for t in c["layer_types"])}


def check(c: dict, name: str) -> None:
    m = _dims(c)
    # A program from before kinds that own their cache cannot run this
    # family: say so at once, from the JAX-free parent (reading the
    # source, not importing it — the module imports jax), not after a
    # replica has made 9 GB of weights.
    source = os.path.join(os.path.dirname(modelcfg.BENCH_DIR), "tony_tpu",
                          "models", "transformer.py")
    with open(source) as f:
        if '"window_moe"' not in f.read():
            raise ValueError(
                f"{name}: the program beside this benchmark has no layer "
                f"kinds with window / full attention (layer_kinds "
                f"'window_moe', 'full_moe'): {source}")
    if len(c["layer_types"]) != m["layers"] or any(
            t not in (SLIDING, FULL) for t in c["layer_types"]):
        raise ValueError(f"{name}: layer_types names sliding_attention or "
                         f"full_attention for each of the "
                         f"{m['layers']} layers")
    if c["expert_selection_fn"] != "sigmoid" or not c["norm_topk_prob"]:
        raise ValueError(f"{name}: the program's router is sigmoid scores, "
                         f"weights normalised over the pick")
    if c["shared_expert_combination_strategy"] != "average":
        raise ValueError(f"{name}: this family averages its shared experts")
    if not c["tie_word_embeddings"] or not c["use_parallel_block"] \
            or not c["use_gated_activation"] or c["hidden_act"] != "silu" \
            or c["use_qk_norm"] or c.get("attention_bias") \
            or c["first_k_dense_replace"] or c["rotary_pct"] != 1 \
            or c["rms_norm_eps"] is not None:
        raise ValueError(f"{name}: this family's block is parallel, "
                         f"LayerNorm (layer_norm_eps), SwiGLU experts in "
                         f"every layer, RoPE over the whole head, no q/k "
                         f"norm, no attention bias, a tied head")
    if m["h"] % m["kv"] or m["hd"] % 2:
        raise ValueError(f"{name}: K/V heads divide the query heads, and "
                         f"head_dim is even")
    if not (0 <= m["first"] and 0 < m["held"]
            and m["first"] + m["held"] <= m["total"]
            and 0 < m["k"] <= m["total"]):
        raise ValueError(f"{name}: experts [first_expert, first_expert + "
                         f"num_experts) must lie inside router_experts")
    if m["first"] % 2 or m["held"] % 2 or m["total"] % 2:
        raise ValueError(f"{name}: the router's columns are seeded in "
                         f"antithetic pairs: first_expert, num_experts "
                         f"and router_experts must be even")


def program_config(c: dict, **job):
    """``tony_tpu.models.transformer.TransformerConfig`` with
    ``layer_kinds`` (dtype and remat are the job script's)."""
    from tony_tpu.models import transformer as T
    m = _dims(c)
    return T.TransformerConfig(
        vocab_size=m["vocab"], d_model=m["d"], n_layers=m["layers"],
        n_heads=m["h"], n_kv_heads=m["kv"], head_dim=m["hd"],
        max_seq=c["max_position_embeddings"],
        rms_eps=c["layer_norm_eps"], rope_base=float(c["rope_theta"]),
        attn_window=m["window"],
        layer_kinds=tuple("window_moe" if t == SLIDING else "full_moe"
                          for t in c["layer_types"]),
        experts=T.SparseExperts(total=m["total"], top_k=m["k"],
                                d_expert=m["f"], first=m["first"],
                                held=m["held"], n_shared=m["ns"],
                                shared_mean=True),
        norm="layer", parallel_block=True, tie_embeddings=True,
        logit_scale=float(c["logit_scale"]), **job)


def layer_kinds(c: dict) -> list[str]:
    """Every layer is of kind ``"moe"``; its TYPE rides in its leaves
    (module docstring)."""
    return ["moe"] * c["num_hidden_layers"]


def _attn_params(m: dict) -> int:
    return (2 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]
            + m["d"])                                   # q, o; k, v; norm


def _expert_params(m: dict) -> int:
    return 3 * m["d"] * m["f"]


def _router_params(m: dict) -> int:
    return m["d"] * m["total"] + m["total"]


def param_count(c: dict) -> int:
    m = _dims(c)
    layer = (_attn_params(m) + _router_params(m)
             + (m["held"] + m["ns"]) * _expert_params(m))
    return m["layers"] * layer + m["vocab"] * m["d"] + m["d"]


def forward_flops_per_token(c: dict, seq: int) -> float:
    """A token meets, of the routed experts, the ``k x held / total`` of
    its picks that live here; the shared experts and the router whole;
    a sliding layer attends inside its window."""
    m = _dims(c)
    proj = 2 * (_attn_params(m) - m["d"])
    scores = 2 * 2 * m["h"] * m["hd"]                   # q.k and p.v a key
    moe = (2 * m["d"] * m["total"] + 2 * _expert_params(m)
           * (m["ns"] + m["k"] * m["held"] / m["total"]))
    return (m["layers"] * (proj + moe)
            + scores * (m["full"] * attended(seq, 0)
                        + (m["layers"] - m["full"])
                        * attended(seq, m["window"]))
            + 2 * m["d"] * m["vocab"])


def expert_load(c: dict, ctx: dict | None) -> tuple[float, float]:
    """(assignments, touched held experts) of ONE expert layer in ONE
    decode step, reckoned as ``mla_moe_decoder.expert_load`` reckons
    them — ``rows`` tokens a step, each picking ``k`` of ``E``
    uniformly — but NOT from all the slots: from the rows that differ.
    The program routes every slot in every step, but the idle ones all
    hold the same state (zero logits, token 0, position 0) and route
    ALIKE, and this cell's slots fill slowly — an admission is 0.37 s of
    the chip, and the traced window (seconds 2-8) lies inside the
    opening burst. Reckoned from all 32 slots the routed products read
    103-105% of their roofline (my chip runs, PR 35). So ``rows`` is the
    run's mean LIVE slots (tokens kept over decode steps executed) plus
    one for the idle ones, where the run's counters say; the slots
    where they do not. Still high for the traced window itself, which
    holds fewer live slots than the run's mean (PERF.md section 7).
    Without a run: every held expert, no assignment."""
    m = _dims(c)
    if ctx is None:
        return 0.0, float(m["held"])
    rows = ctx["mix"]["slots"]
    k = ctx.get("counters") or {}
    if k.get("steps_executed"):
        rows = min(rows, k["tokens_kept"] / k["steps_executed"] + 1.0)
    share = m["k"] / m["total"]
    return (rows * share * m["held"],
            m["held"] * (1.0 - (1.0 - share) ** rows))


def window_share(c: dict, mix: dict) -> float:
    """Of the rows live in a decode step (a request of prompt P at its
    i-th step holds P + i), the share a sliding layer must read,
    ``min(P + i, sliding_window)``: summed over the mix's own fixed
    request shapes and their steps."""
    w = c["sliding_window"]
    live = inside = 0.0
    for p, a in traffic.request_shapes(mix, mix["pool_requests"]):
        p, a = int(p), int(a)
        live += a * p + a * (a - 1) / 2
        under = max(0, min(a, w - p))       # steps whose rows fit the window
        inside += under * p + under * (under - 1) / 2 + (a - under) * w
    return inside / live


def decode_step_bytes(c: dict, live_rows: float, ctx: dict | None = None,
                      dtype_bytes: int = 2) -> float:
    """Bytes ONE decode step over the whole batch must read: attention,
    router (float32) and shared-expert weights and the tied embedding
    once, a K and a V row (``kv_heads x head_dim`` each) a live token a
    full layer and a live token INSIDE THE WINDOW a sliding layer
    (:func:`window_share`), and a routed expert's three matrices for
    each (layer, held expert) TOUCHED (:func:`expert_load`)."""
    m = _dims(c)
    once = (m["layers"] * (_attn_params(m) + m["ns"] * _expert_params(m))
            + m["vocab"] * m["d"] + m["d"])
    inside = window_share(c, ctx["mix"]) if ctx is not None else 1.0
    rows = live_rows * (m["full"] + (m["layers"] - m["full"]) * inside)
    _, touched = expert_load(c, ctx)
    return ((once + rows * 2 * m["kv"] * m["hd"]
             + m["layers"] * touched * _expert_params(m)) * dtype_bytes
            + m["layers"] * _router_params(m) * 4)


def moe_experts_flops_bytes(c: dict, assignments: float, touched: float,
                            dtype_bytes: int = 2) -> tuple[float, float]:
    """What the routed-expert products (the ``tony_moe_gmm`` calls: gate,
    up, down) of ONE layer must do: 2 FLOPs a weight an assignment, and
    each touched expert's three matrices read once."""
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


#: seeded attention, made to behave as a trained model's (``assumed`` in
#: the configuration; readings in :func:`layer_weights`): the query
#: projection drawn 3 x wider (scores of standard deviation 3, a peaked
#: softmax), the output projection 4 x narrower
Q_GAIN, O_GAIN = 3.0, 0.25


def _shapes(c: dict) -> dict:
    """leaf -> (shape, the fan-in its normal is scaled by)."""
    m = _dims(c)
    d, h, kv, hd, f = m["d"], m["h"], m["kv"], m["hd"], m["f"]
    e, ns = m["held"], m["ns"]
    return {"wq": ((d, h, hd), d / Q_GAIN ** 2), "wk": ((d, kv, hd), d),
            "wv": ((d, kv, hd), d),
            "wo": ((h, hd, d), h * hd / O_GAIN ** 2),
            "router": ((d, m["total"]), d),
            "router_bias": ((m["total"],), None),
            "w_gate": ((e, d, f), d), "w_up": ((e, d, f), d),
            "w_down": ((e, f, d), f),
            "shared_gate": ((ns, d, f), d), "shared_up": ((ns, d, f), d),
            "shared_down": ((ns, f, d), f)}


def layer_weights(seed, li, c: dict, dtype, kind: str) -> dict:
    """Layer ``li``'s leaves (unstacked). Traced or concrete ``li``.
    Router and ``router_bias`` float32, as the program holds them; the
    router's columns of UNIT NORM, drawn in ANTITHETIC PAIRS ``(w, -w)``
    (``mla_moe_decoder.layer_weights`` says why: under seeded weights
    the normed activations share a common component that makes an expert
    hot or cold for a whole seed; a trained model gets this balance from
    its training). ``router_bias``: the layer's TYPE, 0 or 1 over all
    experts, inert in the pick (module docstring).

    ``wq`` is drawn ``Q_GAIN`` = 3 times wider than fan-in scaling and
    ``wo`` ``O_GAIN`` = 1/4 as wide. With every matrix at fan-in scale
    the scores are standard normal, the softmax over thousands of keys
    is nearly flat, and attention is an AVERAGE: it passes whatever the
    positions of a request have in common at full gain and averages the
    token away, so the normed stream's common share grew 0.1% -> 0.8% ->
    8% -> 36% over the four layers at 2,048 positions (0.4 -> 3 -> 21 ->
    54% at 512), and 8-of-128 routing turned that into every token
    picking the same experts: 0.45-0.69 held assignments a token in
    layer 3 where 1.0 is the share, 5-10 of 16 held experts touched by
    32 rows where 14 are reckoned (float32 reference on the CPU at the
    published widths, PR 35). A trained model's attention is peaked and
    its stream carries the token. At 3 and 1/4 the common share stays
    under 0.8% (512 positions) and 0.25% (2,048) in all four layers,
    held assignments read 0.96-1.04 a token, 12-16 of 16 touched by 32
    rows, and attention is 13% of a layer's update energy (a third of
    its amplitude) — not the 1% a flat softmax leaves it at 4,096 keys,
    which is also why no serving cell's ``correct`` tells an int8 cache
    (PERF.md section 7). 3 alone left 10% in layer 3; 1/8 on ``wo``
    alone held 0.5% and made attention a hundredth of the output."""
    m = _dims(c)
    ks = jax.random.split(weights.layer_key(seed, li), len(_LEAVES))
    shapes = _shapes(c)
    is_full = jnp.asarray([t == FULL for t in c["layer_types"]],
                          jnp.float32)[li]
    out = {}
    for key, name in zip(ks, _LEAVES):
        shape, fan_in = shapes[name]
        if name == "router_bias":
            out[name] = jnp.broadcast_to(is_full, shape)
        elif name == "router":
            half = _normal(key, (shape[0], shape[1] // 2), fan_in,
                           jnp.float32)
            half = half / jnp.linalg.norm(half, axis=0, keepdims=True)
            out[name] = jnp.stack([half, -half], axis=-1).reshape(shape)
        else:
            out[name] = _normal(key, shape, fan_in, dtype)
    out["attn_norm"] = jnp.ones((m["d"],), dtype)
    return out


def outer_weights(seed, c: dict, dtype) -> dict:
    """The embedding — also the head — and the final norm. The embedding
    rows are drawn at variance ``1 / hidden_size`` (the program's own
    initializer), NOT at unit variance as ``mla_moe_decoder`` draws its
    untied one: the head is TIED, and the stream keeps a token's own row
    all the way to it, so at unit variance a position's largest logit is
    its OWN token's by twenty standard deviations (``|e|^2`` against
    ``|e|`` for every other row) whatever the layers compute — every
    fault of ``tools/control_routed.py``, the held experts zeroed among
    them, read 0 mismatches (float32 reference on the CPU at the
    published widths, PR 35). At ``1 / hidden_size`` a row is a
    hundredth of what four layers add, the logits are of order 1, and
    the prediction is the layers'. What unit variance bought the latent
    family — the token still visible to the routers past layer 0 — the
    PARALLEL block has anyway: layer 0 reads ``LN(e)``, the row at full
    strength whatever its scale, and its experts' output, a function of
    the token alone, is a large part of the stream that layer 1 reads."""
    d, v = c["hidden_size"], c["vocab_size"]
    k_emb, _ = jax.random.split(weights.outer_key(seed))
    return {"embed": _normal(k_emb, (v, d), d, dtype),
            "final_norm": jnp.ones((d,), dtype)}


def make_params(seed: int, c: dict, dtype, shardings=None):
    """The whole pytree in the program's layout — one stacked group a
    program KIND (``window_moe``, ``full_moe``), ``blocks[kind][leaf]:
    [layers of that kind, ...]`` — in ONE jitted call."""
    types = c["layer_types"]
    kinds = {"window_moe": SLIDING, "full_moe": FULL}

    @functools.partial(jax.jit, out_shardings=shardings)
    def build(seed):
        blocks = {
            kind: jax.vmap(lambda li: layer_weights(
                seed, li, c, dtype, "moe"))(jnp.asarray(
                    [li for li, t in enumerate(types) if t == typ],
                    jnp.int32))
            for kind, typ in kinds.items() if typ in types}
        return dict(outer_weights(seed, c, dtype), blocks=blocks)

    return build(weights.as_seed(seed))


def leaf_name(li: int, leaf: str) -> str:
    return f"blocks/{leaf}/{li}"


def leaf_norms(tree: dict, minus: dict | None = None) -> dict:
    """For a train cell, which this family has none of: the program
    refuses to train a model with layer_kinds."""
    raise NotImplementedError(
        "the window / full attention family is served only: the program "
        "has no train step for it, so no cell compares leaf norms")


# -------------------------------------------------------------- reference
def layer_norm(x, g, c: dict):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True)
                              + c["layer_norm_eps"]) * g


def rope(x, positions, c: dict, off):
    """[B, S, H, hd] rotated by position over the whole head, halves
    convention; ``off`` (a traced 0/1): no rotation at all."""
    half = x.shape[-1] // 2
    theta = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(float(c["rope_theta"])) / half))
    ang = positions[:, :, None, None].astype(jnp.float32) * theta
    cos = jnp.where(off, 1.0, jnp.cos(ang))
    sin = jnp.where(off, 0.0, jnp.sin(ang))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, window: int, full):
    """Softmax attention, q [B, S, H, hd], k and v [B, S, KV, hd]: query
    i sees keys j <= i, and — unless ``full`` (a traced 0/1) — only
    those with i - j < window. Over blocks of query rows so that the
    float32 scores never exceed ~1 GiB; the H / KV queries of a K/V head
    are grouped, K and V never repeated."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    bq = s
    while b * h * bq * s * 4 > (1 << 30) and bq % 2 == 0 and bq > 16:
        bq //= 2
    kpos = jnp.arange(s)
    qg = q.reshape(b, s // bq, bq, kv, h // kv, d)

    def block(args):
        qb, i0 = args                                # [b, bq, kv, g, d]
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) * (d ** -0.5)
        dist = (i0 + jnp.arange(bq))[:, None] - kpos[None, :]
        mask = (dist >= 0) & (full | (dist < window))
        p = jax.nn.softmax(jnp.where(mask, sc, _NEG), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v)

    out = jax.lax.map(block, (jnp.moveaxis(qg, 1, 0),
                              jnp.arange(s // bq) * bq))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def _swiglu(h, gate, up, down):
    return jnp.einsum("tf,fd->td", jax.nn.silu(
        jnp.einsum("td,df->tf", h, gate)) * jnp.einsum("td,df->tf", h, up),
        down)


def route(h, p, c: dict):
    """h [T, d] → (picks [T, k], weights [T, k]) over ALL experts. The
    constant ``router_bias`` is added as the program adds it: it moves
    no pick."""
    z = jax.nn.sigmoid(jnp.einsum("td,de->te", h, p["router"]))
    _, picks = jax.lax.top_k(z + p["router_bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(z, picks, axis=-1)
    return picks, w / (jnp.sum(w, -1, keepdims=True) + 1e-20)


def experts(h, p, c: dict):
    """The held experts' part of the routed sum plus the mean of the
    shared experts, on h [T, d]: every held expert for every token,
    weighted by the pick (0 where it was not picked), one expert at a
    time."""
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
    shared, _ = jax.lax.scan(
        lambda acc, xs: (acc + _swiglu(h, *xs), None), jnp.zeros_like(h),
        (p["shared_gate"], p["shared_up"], p["shared_down"]))
    return routed + shared / p["shared_gate"].shape[0]


def layer_forward(x, p, c: dict, kind: str):
    """One layer on [B, S, d] float32; sliding or full by the layer's
    ``router_bias`` (module docstring)."""
    b, s, d = x.shape
    full = p["router_bias"][0] > 0.5
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = layer_norm(x, p["attn_norm"], c)
    q = rope(jnp.einsum("bsd,dhk->bshk", h, p["wq"]), pos, c, full)
    k = rope(jnp.einsum("bsd,dhk->bshk", h, p["wk"]), pos, c, full)
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    o = attention(q, k, v, c["sliding_window"], full)
    a = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    m = experts(h.reshape(b * s, d), p, c).reshape(b, s, d)
    return x + a + m


def head(o, x, c: dict):
    return jnp.einsum("bsd,vd->bsv", layer_norm(x, o["final_norm"], c),
                      o["embed"]) * c["logit_scale"]
