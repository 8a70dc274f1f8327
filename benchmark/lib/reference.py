"""The plain reference: a model in straightforward float32 ``jax.numpy``
at ``default_matmul_precision("highest")`` — no kernels, no cache, no
batching tricks, nothing imported from the program and nothing the program
has made. This module is the DRIVER: embedding lookup -> the layers of the
configuration's family, one jitted program per layer KIND with the layer
index traced -> the family's head; the loss, its gradient one row at a
time, the optimizer's first two steps, the served tokens' gaps. What one
layer computes, which leaves it has and how they are seeded is the
family's (``benchmark/families/<name>.py``). The driver regenerates the
seeded weights layer by layer, upcasts them, and runs a few sequences at
a time, so it fits beside nothing. What deals rows over devices (the
four-chip cell) belongs here too, not in a family.

``mode`` recomputes the same mathematics with the matmul weights rounded
to a lower precision over the contraction axes the family names —
``"int8"`` (per-output-channel absmax, the scheme of the program's own
``models/quantize.py``) or ``"fp8"`` (e4m3 after the same scaling). That
is the CONTROL of the training cells, whose step has no lower precision of
its own: it must come out as not correct. (The serving cells' control is
the program's own int8 weights and int8 cache,
``tools/control_serve.py``.) ``None`` is the reference itself.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from .lazyjax import jax, jnp
from .modelcfg import PROGRAM_RMS_EPS, family


def _lower(w, mode, axes):
    """Round a float32 weight through ``mode`` and back."""
    if mode is None:
        return w
    amax = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True), 1e-30)
    if mode == "int8":
        scale = amax / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if mode == "fp8":
        scale = amax / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"unknown control precision {mode!r}")


def rms_norm(x, w):
    rms = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                        + PROGRAM_RMS_EPS)
    return x * rms * w


def cross_entropy_sum(logits, targets):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


class Reference:
    """The reference for one configuration and seed. Every method is one
    jitted program that makes the weights it needs from the seed inside
    itself; ``li`` is traced, so all layers of one kind share one
    compilation."""

    def __init__(self, c: dict, seed: int, mode: str | None = None,
                 weight_dtype="bfloat16") -> None:
        fam = family(c)
        self.seed = np.uint32(seed % (1 << 32))
        self.kinds = fam.layer_kinds(c)
        #: gradient name -> (layer, leaf) of a layer's leaf; an outer leaf
        #: goes by its own name and is not here
        self.where: dict[str, tuple[int, str]] = {}
        self._family = fam
        hi = functools.partial(jax.default_matmul_precision, "highest")

        def f32(tree):
            return {n: _lower(w.astype(jnp.float32), mode, fam.CONTRACT[n])
                    if n in fam.CONTRACT else w.astype(jnp.float32)
                    for n, w in tree.items()}

        def layer_p(seed, li, kind):
            return f32(fam.layer_weights(seed, li, c, weight_dtype, kind))

        def outer_p(seed):
            return f32(fam.outer_weights(seed, c, weight_dtype))

        @jax.jit
        def embed(seed, tokens):
            return outer_p(seed)["embed"][tokens]

        def layer_programs(kind):
            @jax.jit
            def layer_fwd(seed, li, x):
                with hi():
                    return fam.layer_forward(x, layer_p(seed, li, kind), c,
                                             kind)

            @jax.jit
            def layer_bwd(seed, li, x, dy):
                with hi():
                    _, vjp = jax.vjp(
                        lambda p, x: fam.layer_forward(x, p, c, kind),
                        layer_p(seed, li, kind), x)
                    return vjp(dy)

            return layer_fwd, layer_bwd

        def head(o, x):
            return fam.head(o, x, c)

        @jax.jit
        def head_loss(seed, x, targets, count):
            """(sum CE) / count for these rows, with its gradients."""
            with hi():
                o = outer_p(seed)
                o = {n: o[n] for n in fam.HEAD_LEAVES}
                loss, (go, dx) = jax.value_and_grad(
                    lambda o, x: cross_entropy_sum(head(o, x), targets)
                    / count, argnums=(0, 1))(o, x)
                return loss, go, dx

        @jax.jit
        def head_logits(seed, x):
            with hi():
                return head(outer_p(seed), x)

        @jax.jit
        def head_gaps(seed, x, nxt):
            """[B, S]: how far the logit of ``nxt`` lies below the best."""
            with hi():
                lg = head(outer_p(seed), x)
            picked = jnp.take_along_axis(lg, nxt[..., None], -1)[..., 0]
            return jnp.max(lg, axis=-1) - picked

        v, d = jax.eval_shape(outer_p, self.seed)["embed"].shape

        @jax.jit
        def embed_grad(tokens, dx):
            return jnp.zeros((v, d), jnp.float32).at[tokens.reshape(-1)].add(
                dx.reshape(-1, d))

        self._embed = embed
        self._layer = {kind: layer_programs(kind)
                       for kind in dict.fromkeys(self.kinds)}
        self._head_loss, self._head_logits = head_loss, head_logits
        self._head_gaps = head_gaps
        self._embed_grad = embed_grad

    # ---------------------------------------------------------- forward
    def hidden(self, tokens, keep: bool = False):
        """tokens [B, S] (on the device the work should run on) → last
        hidden state, or every layer's input when ``keep``."""
        x = self._embed(self.seed, tokens)
        xs = [x]
        for li, kind in enumerate(self.kinds):
            x = self._layer[kind][0](self.seed, np.int32(li), x)
            if keep:
                xs.append(x)
        return xs if keep else x

    def logits(self, tokens):
        return self._head_logits(self.seed, self.hidden(tokens))

    # ------------------------------------------------------- one gradient
    def loss_and_grads(self, inputs: np.ndarray, targets: np.ndarray):
        """Mean next-token loss of one batch and its gradient, one row at
        a time. Returns (loss, {name: array}): a layer's leaf under the
        family's ``leaf_name(li, leaf)``, an outer leaf under its own."""
        count = np.float32(inputs.size)
        rows = [{"tok": inputs[r:r + 1], "tgt": targets[r:r + 1],
                 "xs": self.hidden(inputs[r:r + 1], keep=True)}
                for r in range(inputs.shape[0])]
        loss, grads = 0.0, {}

        def add(name, g):
            grads[name] = g if name not in grads else grads[name] + g

        for row in rows:
            ls, go, row["dx"] = self._head_loss(
                self.seed, row["xs"].pop(), row["tgt"], count)
            loss = loss + float(ls)
            for n in self._family.HEAD_LEAVES:
                add(n, go[n])
        for li in reversed(range(len(self.kinds))):
            for row in rows:
                gp, row["dx"] = self._layer[self.kinds[li]][1](
                    self.seed, np.int32(li), row["xs"].pop(), row["dx"])
                for n, g in gp.items():
                    name = self._family.leaf_name(li, n)
                    self.where[name] = (li, n)
                    add(name, g)
        for row in rows:
            add("embed", self._embed_grad(row["tok"], row["dx"]))
        return loss, grads


def _sumsq(tree) -> float:
    return float(sum(float(jnp.sum(jnp.square(g))) for g in tree.values()))


def train_two_steps(c: dict, seed: int, batches, opt: dict,
                    mode: str | None = None,
                    weight_dtype="bfloat16") -> dict:
    """Follow the program's first two optimizer steps (optax
    ``clip_by_global_norm(1) -> adamw`` under linear warm-up from 0, as
    ``tony_tpu.models.train.default_optimizer`` builds it) in float32.
    Step 0 runs at learning rate 0, so both gradients are taken at the
    seeded weights and the parameters' change after two steps is step 1's
    update. Returns the two losses, each leaf's norm of the first clipped
    gradient and of the change."""
    fam = family(c)
    ref = Reference(c, seed, mode, weight_dtype)
    (l0, g0), (l1, g1) = (ref.loss_and_grads(i, t) for i, t in batches[:2])
    b1, b2, eps = 0.9, 0.999, 1e-8
    clip = [min(1.0, 1.0 / math.sqrt(_sumsq(g))) for g in (g0, g1)]
    lr1 = opt["lr"] * min(1.0, 1.0 / opt["warmup_steps"])
    wd = opt["weight_decay"]

    @jax.jit
    def finish(ga, gb, p, ca, cb):
        ga, gb = ga * ca, gb * cb
        mu = b1 * (1 - b1) * ga + (1 - b1) * gb
        nu = b2 * (1 - b2) * ga * ga + (1 - b2) * gb * gb
        u = (mu / (1 - b1 ** 2)) / (jnp.sqrt(nu / (1 - b2 ** 2)) + eps)
        delta = -lr1 * (u + wd * p)
        return jnp.sqrt(jnp.sum(ga * ga)), jnp.sqrt(jnp.sum(delta * delta))

    out = {"loss": [l0, l1], "grad_norm": {}, "delta_norm": {},
           "global_grad_norm": [math.sqrt(_sumsq(g)) for g in (g0, g1)]}
    outer_w = None
    for name in g0:
        if name in ref.where:
            # the change is of the TRUE weights, whatever ``mode``
            li, leaf = ref.where[name]
            p = fam.layer_weights(ref.seed, np.int32(li), c, weight_dtype,
                                  ref.kinds[li])[leaf]
        else:
            if outer_w is None:
                outer_w = fam.outer_weights(ref.seed, c, weight_dtype)
            p = outer_w[name]
        gn, dn = finish(g0[name], g1[name], p.astype(jnp.float32),
                        np.float32(clip[0]), np.float32(clip[1]))
        out["grad_norm"][name] = float(gn)
        out["delta_norm"][name] = float(dn)
    return out


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """The widest gap between the program's norm and the reference's over
    the leaves, each measured against the reference's norm of that leaf or
    of the median leaf, whichever is larger."""
    median = float(np.median(list(reference.values())))
    worst, where = 0.0, ""
    for name, r in reference.items():
        gap = abs(program[name] - r) / max(r, median)
        if gap >= worst:
            worst, where = gap, name
    return worst, where


def served_token_gaps(c: dict, seed: int, samples, widths, rows: int = 8,
                      weight_dtype="bfloat16") -> list[np.ndarray]:
    """For served requests ``[(prompt, tokens), ...]``: run the reference
    once over each prompt with its served tokens and return, per request,
    the gaps by which each served token's logit lies below the reference's
    best at its position (0 where the served token IS the reference's
    best). Requests go through in blocks of ``rows``, each padded to the
    narrowest of ``widths`` that holds it, so a run compiles one program
    per width whatever it served."""
    ref = Reference(c, seed, None, weight_dtype)
    widths = sorted(widths)
    by_width: dict[int, list[int]] = {}
    for i, (prompt, toks) in enumerate(samples):
        n = len(prompt) + len(toks)
        by_width.setdefault(next(w for w in widths if w >= n), []).append(i)
    out: list = [None] * len(samples)
    for width, members in sorted(by_width.items()):
        for b in range(0, len(members), rows):
            block = members[b:b + rows]
            seq = np.zeros((rows, width + 1), np.int32)
            for r, i in enumerate(block):
                prompt, toks = samples[i]
                seq[r, :len(prompt) + len(toks)] = list(prompt) + list(toks)
            gaps = np.asarray(ref._head_gaps(
                ref.seed, ref.hidden(seq[:, :-1]), seq[:, 1:]))
            for r, i in enumerate(block):
                n, m = len(samples[i][0]), len(samples[i][1])
                out[i] = gaps[r, n - 1:n + m - 1]   # positions predicting toks
    return out
