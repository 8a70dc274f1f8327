"""The state-space / attention hybrid decoder family (GraniteMoeHybrid's
layers with no routed experts, as granite-4.0-h-micro configures them):
``layer_types`` names each layer ``mamba`` — a Mamba-2 mixer, whose state
is a fixed-size recurrence — or ``attention`` — grouped-query attention
WITHOUT positional rotation (``position_embedding_type: nope``); every
layer ends in a dense SwiGLU; four scalars (``embedding_multiplier``,
``residual_multiplier``, ``attention_multiplier``, ``logits_scaling``)
and a head tied to the embedding. Provides what ``dense_decoder.py``'s
docstring lists. The program block it stands for is
``tony_tpu.models.transformer`` with ``layer_kinds`` of ``ssm_dense`` and
``full_dense`` (served through ``models/decode.py``; the program refuses
to train it).

One token ``t`` of the stream x (``eps`` = ``rms_norm_eps``; no bias in
any matrix; ``RMS(x; w) = x / sqrt(mean x^2 + eps) * w``):

- outer: ``x_0 = embedding_multiplier * E[token]``; ``logits =
  RMS(x_L; w_f) E^T / logits_scaling``.
- every layer: ``x <- x + residual_multiplier * mix(RMS(x; w_a))``, then
  ``x <- x + residual_multiplier * W_down(silu(W_gate h) * W_up h)``,
  ``h = RMS(x; w_m)``.
- ``attention``: q of ``num_attention_heads`` heads, k and v of
  ``num_key_value_heads`` (query head i reads K/V head ``i // (heads /
  kv_heads)``), no rotation, causal softmax of ``attention_multiplier *
  q.k`` — 1/64 at heads of 64, NOT 64^-1/2 — then ``W_o``.
- ``mamba``: ``[z | c | d] = h W_in`` split ``d_inner | conv_dim |
  n_heads``; ``c'_t = silu(b_c + sum_j w_c[j] * c_{t-K+1+j})`` (depthwise,
  causal, ``K = mamba_d_conv`` taps, zeros before position 0); ``c'`` split
  into ``u`` (``n_heads`` of ``d_head``), ``B``, ``C`` (``d_state`` a
  group); ``D_t = softplus(d_t + dt_bias)``; ``a = -exp(A_log)``; per
  head, ``S_-1 = 0``:

      S_t = exp(D_t a) S_{t-1} + D_t u_t (x) B_t      y_t = S_t C_t + D u_t

  ``mix = RMS(y_t * silu(z_t); w_g) W_out`` (gate first, then the norm,
  over all ``d_inner``: one group).

THE REFERENCE of the mixer is the SEQUENTIAL recurrence: a ``lax.scan``
over positions that carries the state and the conv's window, nothing
chunked, nothing imported from the program — so the program's chunked
prompt scan and its one-step kernel are both held by an implementation
that shares neither. ``layer_forward`` takes two more arguments than the
list asks, for ``tools/control_state.py`` alone: ``live`` [B, S], a mask
under which a position that is NOT live is skipped as an admission's
padding must be (the state takes the identity step, the conv's window
does not shift, attention does not see its key), and ``fault``, which
leaves ONE of those out. ``lib/reference.py`` passes neither.

``lib/reference.py`` looks the tokens up in the ``embed`` leaf itself, so
:func:`outer_weights` hands it ``embed`` ALREADY x ``embedding_multiplier``
(float32: one multiply, as the program's), and :func:`head` — the tied
head — divides the same leaf by it again: ``RMS(x) (12 E)^T / (12 x 8)``,
the same logits to float32's last bit or two. :func:`make_params` gives
the program the rows themselves.

Departures from the published model, shared with the program and noted in
the configuration file: separate gate and up matrices (published fused as
``shared_mlp.input_linear``: the same mathematics).
"""

from __future__ import annotations

import functools
import math
import os

from benchmark.lib import modelcfg, weights
from benchmark.lib.flops import attended
from benchmark.lib.lazyjax import jax, jnp

MAMBA, ATTENTION = "mamba", "attention"
#: the reference's two kinds of layer, and the program's kind of each
KINDS = {MAMBA: "ssm", ATTENTION: "attention"}
PROGRAM_KINDS = {"ssm": "ssm_dense", "attention": "full_dense"}
_MLP = ("w_gate", "w_up", "w_down")
#: the seeded leaves of each kind, in the order their keys are split
_LEAVES = {"ssm": ("w_in", "conv_w", "dt_bias", "A_log", "w_out") + _MLP,
           "attention": ("wq", "wk", "wv", "wo") + _MLP}
CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "w_in": (0,), "w_out": (0,),
            "w_gate": (0,), "w_up": (0,), "w_down": (0,)}
HEAD_LEAVES = ("final_norm", "embed")
_NEG = -1e30
KERNEL = "tony_ssm_step"
#: the faults ``tools/control_state.py`` reads, one at a time
FAULTS = ("state_zeroed", "tail_unmasked", "conv_from_tail", "attn_scale")

#: seeded weights made to behave as a trained model's (``assumed`` in the
#: configuration file; readings in :func:`layer_weights` and
#: :func:`outer_weights`)
Q_GAIN = K_GAIN = 5.0
EMBED_STD = 0.002
FINAL_NORM = 88.0


# ------------------------------------------------------ check and counts
def _dims(c: dict) -> dict:
    types = c["layer_types"]
    h, p = c["mamba_n_heads"], c["mamba_d_head"]
    g, n = c["mamba_n_groups"], c["mamba_d_state"]
    return {
        "d": c["hidden_size"], "h": c["num_attention_heads"],
        "kv": c["num_key_value_heads"],
        "hd": c["hidden_size"] // c["num_attention_heads"],
        "f": c["shared_intermediate_size"], "vocab": c["vocab_size"],
        "layers": c["num_hidden_layers"],
        "n_ssm": types.count(MAMBA), "n_attn": types.count(ATTENTION),
        "H": h, "P": p, "G": g, "N": n, "K": c["mamba_d_conv"],
        "inner": h * p, "conv": h * p + 2 * g * n,
        "in": 2 * h * p + 2 * g * n + h,
        "state_bytes": 2 if c["state_dtype"] == "bfloat16" else 4}


def check(c: dict, name: str) -> None:
    m = _dims(c)
    # a program from before the state-space kind cannot run this family:
    # say so at once, from the JAX-free parent (reading the source, not
    # importing it), not after a replica has made 6 GB of weights
    source = os.path.join(os.path.dirname(modelcfg.BENCH_DIR), "tony_tpu",
                          "models", "transformer.py")
    with open(source) as f:
        if '"ssm_dense"' not in f.read():
            raise ValueError(
                f"{name}: the program beside this benchmark has no "
                f"state-space layer kind (layer_kinds 'ssm_dense'): "
                f"{source}")
    if len(c["layer_types"]) != m["layers"] or any(
            t not in KINDS for t in c["layer_types"]) or not m["n_attn"]:
        raise ValueError(f"{name}: layer_types names mamba or attention "
                         f"for each of the {m['layers']} layers, an "
                         f"attention layer among them")
    if m["inner"] != c["mamba_expand"] * m["d"] or m["H"] % m["G"]:
        raise ValueError(f"{name}: mamba_n_heads x mamba_d_head is "
                         f"mamba_expand x hidden_size, and the groups "
                         f"divide the heads")
    if c["num_local_experts"] or c["num_experts_per_tok"]:
        raise ValueError(f"{name}: this family's feed-forward is the "
                         f"shared SwiGLU alone (num_local_experts 0)")
    if c["position_embedding_type"] != "nope" \
            or not c["tie_word_embeddings"] or c["hidden_act"] != "silu" \
            or c["attention_bias"] or c["mamba_proj_bias"] \
            or not c["mamba_conv_bias"] \
            or c["normalization_function"] != "rmsnorm" \
            or c["intermediate_size"] != m["f"]:
        raise ValueError(f"{name}: this family's block is RMSNorm, "
                         f"attention without positions or bias, a conv "
                         f"with a bias, projections without, a SwiGLU of "
                         f"shared_intermediate_size, a tied head")
    if m["h"] % m["kv"] or m["hd"] * m["h"] != m["d"]:
        raise ValueError(f"{name}: K/V heads divide the query heads, of "
                         f"hidden_size / num_attention_heads each")
    if c["state_dtype"] not in ("bfloat16", "float32"):
        raise ValueError(f"{name}: state_dtype is bfloat16 (the model's "
                         f"dtype, as upstream's cache) or float32")


def program_config(c: dict, **job):
    """``tony_tpu.models.transformer.TransformerConfig`` with
    ``layer_kinds`` (dtype and remat are the job script's). The stored
    state: the model's dtype where the file says bfloat16 — so a float32
    job on the CPU holds a float32 state —, else float32."""
    from tony_tpu.models import transformer as T
    m = _dims(c)
    return T.TransformerConfig(
        vocab_size=m["vocab"], d_model=m["d"], n_layers=m["layers"],
        n_heads=m["h"], n_kv_heads=m["kv"], head_dim=m["hd"], d_ff=m["f"],
        max_seq=c["max_position_embeddings"], rms_eps=c["rms_norm_eps"],
        layer_kinds=tuple(PROGRAM_KINDS[KINDS[t]]
                          for t in c["layer_types"]),
        ssm=T.StateSpace(
            n_heads=m["H"], head_dim=m["P"], d_state=m["N"],
            n_groups=m["G"], d_conv=m["K"], chunk=c["mamba_chunk_size"],
            state_dtype=None if c["state_dtype"] == "bfloat16"
            else "float32"),
        tie_embeddings=True, logit_scale=1.0 / c["logits_scaling"],
        embed_scale=float(c["embedding_multiplier"]),
        residual_scale=c["residual_multiplier"],
        attn_scale=c["attention_multiplier"], **job)


def layer_kinds(c: dict) -> list[str]:
    return [KINDS[t] for t in c["layer_types"]]


def _mlp_params(m: dict) -> int:
    return 3 * m["d"] * m["f"]


def _attn_matrices(m: dict) -> int:
    return 2 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]


def _ssm_matrices(m: dict) -> int:
    return m["d"] * m["in"] + m["inner"] * m["d"]


def _ssm_small(m: dict) -> tuple[int, int]:
    """(model-dtype, float32) parameters of a mixer outside its two
    matrices: the conv's taps and bias and the gated norm; dt_bias, A_log
    and D."""
    return m["conv"] * m["K"] + m["conv"] + m["inner"], 3 * m["H"]


def param_count(c: dict) -> int:
    m = _dims(c)
    ssm = _ssm_matrices(m) + sum(_ssm_small(m)) + _mlp_params(m) + 2 * m["d"]
    attn = _attn_matrices(m) + _mlp_params(m) + 2 * m["d"]
    return (m["n_ssm"] * ssm + m["n_attn"] * attn + m["vocab"] * m["d"]
            + m["d"])


def forward_flops_per_token(c: dict, seq: int) -> float:
    """Two a matmul weight (the tied head's among them); q.k and p.v over
    the keys a query attends in the attention layers; in a mixer the conv
    (2 a tap a channel) and the recurrence as the SEQUENTIAL form needs
    it — decay, outer product and add, the read ``S C``: 5 a state
    element — which the chunked form's extra products do not count."""
    m = _dims(c)
    mixer = (2 * _ssm_matrices(m) + 2 * m["K"] * m["conv"]
             + 5 * m["inner"] * m["N"])
    attn = 2 * _attn_matrices(m) + 4 * m["h"] * m["hd"] * attended(seq, 0)
    return (m["n_ssm"] * mixer + m["n_attn"] * attn
            + m["layers"] * 2 * _mlp_params(m) + 2 * m["d"] * m["vocab"])


def decode_step_bytes(c: dict, live_rows: float, ctx: dict | None = None,
                      dtype_bytes: int = 2) -> float:
    """Bytes ONE decode step over the whole batch must move: every weight
    once (the tied embedding IS the head's matrix; dt_bias, A_log and D
    float32); the recurrent state READ AND WRITTEN at its stored width,
    and the conv's window likewise, for EVERY slot of the run's mix —
    idle slots too: the program steps them —; a K and a V row a live
    token an attention layer. Without a run (``ctx`` None) there are no
    slots: the weights and the rows alone."""
    m = _dims(c)
    small, f32 = _ssm_small(m)
    once = ((m["n_ssm"] * (_ssm_matrices(m) + small) + m["n_attn"]
             * _attn_matrices(m) + m["layers"] * (_mlp_params(m)
                                                  + 2 * m["d"])
             + m["vocab"] * m["d"] + m["d"]) * dtype_bytes
            + m["n_ssm"] * f32 * 4)
    slots = ctx["mix"]["slots"] if ctx is not None else 0
    state = 2 * slots * m["n_ssm"] * (
        m["N"] * m["inner"] * m["state_bytes"]
        + (m["K"] - 1) * m["conv"] * dtype_bytes)
    rows = live_rows * m["n_attn"] * 2 * m["kv"] * m["hd"] * dtype_bytes
    return once + state + rows


def ssm_step_flops_bytes(c: dict, slots: int) -> tuple[float, float]:
    """What ONE launch of the state update (``tony_ssm_step``: one mixer,
    one position, every slot) must do: 5 FLOPs a state element (decay,
    outer product and add, the read), and the state read and written
    once at its stored width beside the step's float32 vectors (decay and
    input in, y out: a slot's ``d_inner`` each; B and C)."""
    m = _dims(c)
    return (5.0 * slots * m["inner"] * m["N"],
            slots * (2.0 * m["N"] * m["inner"] * m["state_bytes"]
                     + 4 * (3 * m["inner"] + 2 * m["G"] * m["N"])))


# ---------------------------------------------------------------- weights
def _rounded(w, dtype):
    """To ``dtype``, rounded to bfloat16 by an explicit
    ``reduce_precision`` first (``mla_moe_decoder._normal`` says why: the
    TPU compiler elides a float32 -> bfloat16 -> float32 round trip, and
    the reference would run on unrounded weights)."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        w = jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
    return w.astype(dtype)


def _normal(key, shape, fan_in, dtype):
    return _rounded(jax.random.normal(key, shape, jnp.float32)
                    * (fan_in ** -0.5), dtype)


def _shapes(c: dict, kind: str) -> dict:
    """leaf -> (shape, the fan-in its normal is scaled by)."""
    m = _dims(c)
    d, f = m["d"], m["f"]
    mlp = {"w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f)}
    if kind == "ssm":
        return dict(mlp, w_in=((d, m["in"]), d),
                    w_out=((m["inner"], d), m["inner"]))
    h, kv, hd = m["h"], m["kv"], m["hd"]
    return dict(mlp, wq=((d, h, hd), d / Q_GAIN ** 2),
                wk=((d, kv, hd), d / K_GAIN ** 2), wv=((d, kv, hd), d),
                wo=((h, hd, d), h * hd))


def layer_weights(seed, li, c: dict, dtype, kind: str) -> dict:
    """Layer ``li``'s leaves (unstacked). Traced or concrete ``li``.

    A mixer whose recurrence MATTERS. Upstream's modelling code starts
    ``dt_bias`` at 1 and ``A_log`` at ``log(1..n_heads)``, under which
    ``exp(dt a)`` is 0.27 at best and a state forgets within a few
    tokens: ``correct`` could then not tell a lost state. These are
    Mamba-2's own (``mamba_ssm/modules/mamba2.py``): steps log-uniform in
    [0.001, 0.1] through ``dt_bias = softplus^-1(dt)``, ``-a`` uniform in
    [1, 16] — a head remembers between 1 and 1,000 positions —, ``D`` 1,
    conv taps uniform in +-0.5 (fan-in 4), conv bias 0; matrices fan-in
    normal; ``dt_bias``, ``A_log``, ``D`` float32 as the program holds
    them.

    An attention whose scale matters. At fan-in scale ``q.k`` has
    standard deviation 8 over heads of 64, and ``attention_multiplier``
    1/64 makes the scores' 0.125: a flat softmax, an average of the
    values, which neither an int8 cache nor the wrong scale would move
    (PERF.md section 7, PR 35). A model trained under 1/64 grows its
    queries and keys; ``wq`` and ``wk`` are drawn ``Q_GAIN`` = ``K_GAIN``
    = 5 times wider, scores of standard deviation 3.1 — the peaked
    softmax PR 35 chose for the same reason."""
    m = _dims(c)
    names = _LEAVES[kind]
    ks = dict(zip(names, jax.random.split(weights.layer_key(seed, li),
                                          len(names))))
    out = {n: _normal(ks[n], shape, fan_in, dtype)
           for n, (shape, fan_in) in _shapes(c, kind).items()}
    out["attn_norm"] = jnp.ones((m["d"],), dtype)
    out["mlp_norm"] = jnp.ones((m["d"],), dtype)
    if kind != "ssm":
        return out
    u = jax.random.uniform(ks["dt_bias"], (m["H"],), jnp.float32)
    dt = jnp.exp(math.log(1e-3) + u * math.log(1e2))
    out.update(
        conv_w=_rounded(jax.random.uniform(
            ks["conv_w"], (m["K"], m["conv"]), jnp.float32, -0.5, 0.5),
            dtype),
        conv_b=jnp.zeros((m["conv"],), dtype),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        A_log=jnp.log(jax.random.uniform(ks["A_log"], (m["H"],),
                                         jnp.float32, 1.0, 16.0)),
        D=jnp.ones((m["H"],), jnp.float32),
        gate_norm=jnp.ones((m["inner"],), dtype))
    return out


def _embedding(seed, c: dict, dtype):
    k_emb, _ = jax.random.split(weights.outer_key(seed))
    return _rounded(jax.random.normal(
        k_emb, (c["vocab_size"], c["hidden_size"]), jnp.float32)
        * EMBED_STD, dtype)


def outer_weights(seed, c: dict, dtype) -> dict:
    """For the reference: ``embed``, the rows x ``embedding_multiplier``
    in float32 (``lib/reference.py`` looks tokens up in it and upcasts
    nothing further; :func:`head` divides by the multiplier), and the
    final norm.

    The rows' scale. The head is TIED and the stream carries 12 E[token]
    to it, so with rows of standard deviation s a position's OWN token
    reads ``12 s^2 2048 / (8 rms(x_L))`` where every other row reads a
    standard deviation of ``s sqrt(2048) / 8``: a ratio of ``543 s /
    rms(x_L)`` — tens of standard deviations at the program's own ``s =
    2048^-1/2``, and then every fault reads 0 mismatches because the
    prediction is the token's own row and not the layers' (PR 35's
    finding, ``window_full_moe_decoder.outer_weights``). At ``EMBED_STD``
    the ratio is about one, and layer 0 still reads the token at full
    strength (its norm divides by the rows' own rms, far above eps). The
    final norm's weight is the constant ``FINAL_NORM``, which makes the
    logits of order 1 (a weight value: the four multipliers run as
    published). Readings: the configuration file's
    ``assumed.initializer``."""
    return {"embed": _embedding(seed, c, dtype).astype(jnp.float32)
            * float(c["embedding_multiplier"]),
            "final_norm": jnp.full((c["hidden_size"],), FINAL_NORM, dtype)}


def make_params(seed: int, c: dict, dtype, shardings=None):
    """The whole pytree in the program's layout — one stacked group a
    program KIND (``ssm_dense``, ``full_dense``), ``blocks[kind][leaf]:
    [layers of that kind, ...]``, the embedding once — in ONE jitted
    call."""
    kinds = layer_kinds(c)

    @functools.partial(jax.jit, out_shardings=shardings)
    def build(seed):
        blocks = {
            PROGRAM_KINDS[kind]: jax.vmap(lambda li: layer_weights(
                seed, li, c, dtype, kind))(jnp.asarray(
                    [li for li, k in enumerate(kinds) if k == kind],
                    jnp.int32))
            for kind in dict.fromkeys(kinds)}
        return {"embed": _embedding(seed, c, dtype),
                "final_norm": outer_weights(seed, c, dtype)["final_norm"],
                "blocks": blocks}

    return build(weights.as_seed(seed))


def leaf_name(li: int, leaf: str) -> str:
    return f"blocks/{leaf}/{li}"


def leaf_norms(tree: dict, minus: dict | None = None) -> dict:
    """For a train cell, which this family has none of: the program
    refuses to train a model with layer_kinds."""
    raise NotImplementedError(
        "the state-space hybrid family is served only: the program has no "
        "train step for it, so no cell compares leaf norms")


# -------------------------------------------------------------- reference
def rms_norm(x, w, c: dict):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + c["rms_norm_eps"]) * w


def attention(q, k, v, scale: float, live=None):
    """Causal softmax attention WITHOUT positions, q [B, S, H, hd], k and
    v [B, S, KV, hd], scores x ``scale``; ``live`` [B, S]: keys a query
    may see at all. Over blocks of query rows so that the float32 scores
    never exceed ~1 GiB; the H / KV queries of a K/V head are grouped, K
    and V never repeated."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    bq = s
    while b * h * bq * s * 4 > (1 << 30) and bq % 2 == 0 and bq > 16:
        bq //= 2
    kpos = jnp.arange(s)
    seen = jnp.ones((b, s), bool) if live is None else live
    qg = q.reshape(b, s // bq, bq, kv, h // kv, d)

    def block(args):
        qb, i0 = args                                # [b, bq, kv, g, d]
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) * scale
        mask = ((i0 + jnp.arange(bq))[:, None] >= kpos[None, :])[None] \
            & seen[:, None, :]
        p = jax.nn.softmax(jnp.where(mask[:, None, None], sc, _NEG),
                           axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v)

    out = jax.lax.map(block, (jnp.moveaxis(qg, 1, 0),
                              jnp.arange(s // bq) * bq))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def ssm_mixer(h, p, c: dict, live=None, fault: str = ""):
    """The Mamba-2 mixer on normed h [B, S, d], one position after
    another: the scan carries (the conv's last K - 1 inputs, the state
    [B, H, P, N]). ``live`` / ``fault``: the module docstring."""
    m = _dims(c)
    b, s, _ = h.shape
    hh, pp, g, n, taps = m["H"], m["P"], m["G"], m["N"], m["K"] - 1
    zcd = jnp.einsum("bsd,df->bsf", h, p["w_in"])
    z, c_in, d_t = (zcd[..., :m["inner"]],
                    zcd[..., m["inner"]:m["inner"] + m["conv"]],
                    zcd[..., m["inner"] + m["conv"]:])
    dt = jax.nn.softplus(d_t + p["dt_bias"])                     # [B, S, H]
    a = -jnp.exp(p["A_log"])
    on = jnp.ones((b, s), bool) if live is None else live
    # the first live position after a gap: where decode takes over
    takeover = on & jnp.concatenate(
        [jnp.zeros((b, 1), bool), ~on[:, :-1]], axis=1)

    def step(carry, xs):
        window, state = carry                # [B, K-1, conv], [B, H, P, N]
        c_t, dt_t, on_t, first = xs
        full = jnp.concatenate([window, c_t[:, None]], axis=1)
        conv = jax.nn.silu(p["conv_b"] + jnp.sum(full * p["conv_w"], axis=1))
        shifts = on_t | (fault == "conv_from_tail")
        window = jnp.where(shifts[:, None, None], full[:, 1:], window)
        u = conv[:, :m["inner"]].reshape(b, hh, pp)
        bb = jnp.repeat(conv[:, m["inner"]:m["inner"] + g * n]
                        .reshape(b, g, n), hh // g, axis=1)
        cc = jnp.repeat(conv[:, m["inner"] + g * n:].reshape(b, g, n),
                        hh // g, axis=1)
        if fault != "tail_unmasked":
            dt_t = jnp.where(on_t[:, None], dt_t, 0.0)
        if fault == "state_zeroed":
            state = jnp.where(first[:, None, None, None], 0.0, state)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * u)[..., None] * bb[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", state, cc) + p["D"][:, None] * u
        return (window, state), y

    init = (jnp.zeros((b, taps, m["conv"]), jnp.float32),
            jnp.zeros((b, hh, pp, n), jnp.float32))
    _, ys = jax.lax.scan(step, init, (
        jnp.moveaxis(c_in, 1, 0), jnp.moveaxis(dt, 1, 0), on.T, takeover.T))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, m["inner"])
    return jnp.einsum("bsf,fd->bsd",
                      rms_norm(y * jax.nn.silu(z), p["gate_norm"], c),
                      p["w_out"])


def layer_forward(x, p, c: dict, kind: str, live=None, fault: str = ""):
    """One layer on [B, S, d] float32."""
    h = rms_norm(x, p["attn_norm"], c)
    if kind == "ssm":
        mix = ssm_mixer(h, p, c, live, fault)
    else:
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
        scale = (q.shape[-1] ** -0.5 if fault == "attn_scale"
                 else c["attention_multiplier"])
        mix = jnp.einsum("bshk,hkd->bsd", attention(q, k, v, scale, live),
                         p["wo"])
    x = x + c["residual_multiplier"] * mix
    h = rms_norm(x, p["mlp_norm"], c)
    inner = (jax.nn.silu(jnp.einsum("bsd,df->bsf", h, p["w_gate"]))
             * jnp.einsum("bsd,df->bsf", h, p["w_up"]))
    return x + c["residual_multiplier"] * jnp.einsum("bsf,fd->bsd", inner,
                                                     p["w_down"])


def head(o, x, c: dict):
    return jnp.einsum("bsd,vd->bsv", rms_norm(x, o["final_norm"], c),
                      o["embed"]) / (c["embedding_multiplier"]
                                     * c["logits_scaling"])
