"""What a block keeps for its backward (``tony_tpu/models/remat.py``): the
ladder is arithmetic over bytes, every rung is the same function as rung
0, and the train step's safety net steps down when the compiler refuses a
rung for memory."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tony_tpu.models import remat
from tony_tpu.models import transformer as T
from tony_tpu.models.train import init_state, make_train_step
from tony_tpu.parallel.mesh import make_mesh
from tony_tpu.runtime import metrics as metrics_mod

GIB = 1 << 30
V5E_LIMIT = int(15.75 * GIB)            # a v5e's ``bytes_limit``

#: the benchmark's train cell: Mistral-7B widths, 4 layers, 2 x 8,192
MISTRAL_L4 = T.TransformerConfig(
    vocab_size=32000, d_model=4096, n_layers=4, n_heads=32, n_kv_heads=8,
    d_ff=14336, max_seq=32768, attn_window=4096, dtype=jnp.bfloat16)
#: its state (bf16 weights and two bf16 moments) and gradients, in bytes
CELL_STATE, CELL_GRADS = 6_807_576_588, 2_269_192_192


def _left(cfg, b, s, limit, held, grads, mesh=None, rules=()):
    """Bytes left as :func:`remat.decide` reckons them, nothing else
    resident."""
    return (limit - held - limit // 64 - grads
            - remat.working_bytes(cfg, b, s, mesh, rules))


def test_ladder_is_cumulative_and_named_in_the_block():
    """Each rung keeps a superset of the one below, rung 0 nothing; and
    every name the ladder counts bytes for is a name the block (or the
    flash kernel's forward rule) marks — a renamed tensor would silently
    fall off the ladder."""
    assert remat.names(0) == () and remat.policy(0) is None
    for r in range(1, len(remat.LADDER)):
        assert set(remat.names(r - 1)) < set(remat.names(r))
    cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32)
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: T.lm_loss(p, batch, cfg)))(params))
    from tony_tpu.ops.attention import flash_attention
    q = jax.ShapeDtypeStruct((1, 128, 2, 64), jnp.float32)
    text += str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(q, k, v).sum()))(q, q, q))
    for name in remat.names(len(remat.LADDER) - 1):
        assert f"name={name}" in text, name
    assert set(remat._named_tensors(cfg, 2, 32, None, ())) == set(
        remat.names(len(remat.LADDER) - 1))


def test_rung_bytes_of_the_train_cell():
    """ISSUE 31's table: bytes a token a layer in bf16 at Mistral's
    widths, over 16,384 tokens x 4 layers."""
    per = [0, 8192 + 128, 12288 + 8192, 28672, 28672]
    want = np.cumsum(per) * 16384 * 4
    assert remat.rung_bytes(MISTRAL_L4, 2, 8192) == want.tolist()
    # a head narrower than the 128 lanes is stored in whole tiles
    phi = T.TransformerConfig(vocab_size=32064, d_model=3072, n_layers=4,
                              n_heads=32, d_ff=8192, dtype=jnp.bfloat16)
    assert remat.rung_bytes(phi, 2, 8192)[1] == 4 * 16384 * (
        32 * 128 * 2 + 32 * 4)


@pytest.mark.parametrize("case,want", [
    # the cell on one v5e: the described-chip compile admits rung 3
    # (15.90 GB) and refuses rung 4 (tests/test_chip_compile.py)
    ("train-cell", (3,)),
    # the planned four-chip cell "compiles at 15.0 GB of 15.75" at rung 0
    ("four-chip-0.75GB", (0, 1)),
    ("no-reading", (0,)), ("pp2", (0,)), ("cp2", (0,)),
    ("ceiling-1", (1,)), ("nothing-left", (0,))])
def test_choose(case, want):
    rungs = remat.rung_bytes(MISTRAL_L4, 2, 8192)
    left = _left(MISTRAL_L4, 2, 8192, V5E_LIMIT, CELL_STATE, CELL_GRADS)
    got = {
        "train-cell": lambda: remat.choose(rungs, left),
        "four-chip-0.75GB": lambda: remat.choose(rungs, int(0.75e9)),
        "no-reading": lambda: remat.choose(rungs, None),
        "pp2": lambda: remat.choose(rungs, left, {"pp": 2, "dp": 2}),
        "cp2": lambda: remat.choose(rungs, left, {"cp": 2}),
        "ceiling-1": lambda: remat.choose(rungs, left, {"dp": 1}, 1),
        "nothing-left": lambda: remat.choose(rungs, -5 * GIB),
    }[case]()
    assert got in want


def test_choose_is_monotone_in_free_bytes():
    rungs = remat.rung_bytes(MISTRAL_L4, 2, 8192)
    picks = [remat.choose(rungs, left)
             for left in range(-GIB, 8 * GIB, GIB // 8)]
    assert picks == sorted(picks) and picks[0] == 0 and picks[-1] == 4
    for r, need in enumerate(rungs[1:], 1):
        assert remat.choose(rungs, need) == r == 1 + remat.choose(
            rungs, need - 1)


def test_sharding_divides_the_bytes():
    """``tp`` divides widths and ``dp`` tokens: on the planned
    ``dp=2,tp=2`` mesh a device keeps a quarter of the wide tensors and
    of the kernel's operands (8 K/V heads split over tp like Q's), half
    of the output projection; K/V heads that tp does not divide reach
    the kernel expanded to Q's."""
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    one = remat.rung_bytes(MISTRAL_L4, 4, 8192)
    four = remat.rung_bytes(MISTRAL_L4, 4, 8192, mesh, T.DEFAULT_RULES)
    assert four[1] * 4 == one[1]
    assert (four[4] - four[2]) * 4 == one[4] - one[2]
    # a device's 16,384 tokens x 4 layers: half of the heads of q, k and
    # v, the whole output projection
    assert four[2] - four[1] == 4 * 16384 * (4096 + 2 * 1024 + 8192)
    odd = MISTRAL_L4.scaled(n_kv_heads=1)
    q_and_out = 4 * 16384 * (4096 + 8192)
    assert remat.rung_bytes(odd, 4, 8192, mesh, T.DEFAULT_RULES)[2] - \
        four[1] == q_and_out + 4 * 16384 * 2 * 4096


def test_decide_reads_the_device_and_the_scope(monkeypatch):
    """The cell's numbers through :func:`remat.decide`: a v5e's limit, the
    step's state in the scope, a few MB of prefetched batches beside it →
    rung 3, in the gauges; no reading → rung 0; ``full`` pins rung 0
    without asking the device."""
    reg = metrics_mod.get_default()
    sc = remat.Scope(held=CELL_STATE)
    monkeypatch.setattr(remat, "device_memory",
                        lambda: (V5E_LIMIT, CELL_STATE + 3_000_000))
    with remat.scope(sc):
        assert remat.decide(MISTRAL_L4, 2, 8192, CELL_GRADS) == 3
    assert sc.rung == 3
    assert reg.gauge("tony_train_saved_rung").value == 3
    assert reg.gauge("tony_train_saved_bytes").value == 3_766_484_992
    # two hosts a few MB apart read the same; a second copy of the
    # parameters held beside the state costs a rung
    for extra, want in ((40_000_000, 3), (CELL_GRADS, 2)):
        monkeypatch.setattr(remat, "device_memory", lambda e=extra: (
            V5E_LIMIT, CELL_STATE + e))
        with remat.scope(remat.Scope(held=CELL_STATE)):
            assert remat.decide(MISTRAL_L4, 2, 8192, CELL_GRADS) == want
    monkeypatch.setattr(remat, "device_memory", lambda: None)
    assert remat.decide(MISTRAL_L4, 2, 8192, CELL_GRADS) == 0
    assert reg.gauge("tony_train_saved_rung").value == 0

    def never():
        raise AssertionError("'full' asks the device nothing")
    monkeypatch.setattr(remat, "device_memory", never)
    assert remat.decide(MISTRAL_L4.scaled(remat_policy="full"), 2, 8192,
                        CELL_GRADS) == 0


def test_cpu_gives_no_reading():
    assert remat.device_memory() is None


def _tiny_step(monkeypatch, fail_above=None):
    """(step, state, batch, rungs the loss was traced at) on the ``tiny``
    preset with a device that has room for everything. The COMPILER
    refuses, as the chip's does for memory, every program traced at a
    rung over ``fail_above`` — after the trace, which JAX has cached by
    then: a step-down has to be a new trace (found on the chip, PR 31: a
    fresh ``jax.jit`` of the same function was handed the cached one and
    failed for ever)."""
    from jax._src import compiler
    monkeypatch.setattr(remat, "device_memory", lambda: (1 << 50, 0))
    cfg = T.PRESETS["tiny"].scaled(dtype=jnp.float32)
    seen = []

    def loss(p, b):
        out = T.lm_loss(p, b, cfg)
        seen.append(remat._SCOPE.get().rung)
        return out

    really_compile = compiler.compile_or_get_cached

    def refusing(*args, **kwargs):
        if fail_above is not None and seen and seen[-1] > fail_above:
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
                "out of memory in memory space hbm (planted)")
        return really_compile(*args, **kwargs)
    monkeypatch.setattr(compiler, "compile_or_get_cached", refusing)

    opt = optax.sgd(0.1)
    step = make_train_step(loss, opt)
    state = init_state(T.init_params(jax.random.PRNGKey(0), cfg), opt)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                              cfg.vocab_size)
    seen.clear()
    return step, state, {"tokens": toks}, seen


def test_safety_net_steps_down_and_counts(monkeypatch):
    downs = metrics_mod.get_default().counter(
        "tony_train_saved_step_downs_total")
    before = downs.value
    step, state, batch, seen = _tiny_step(monkeypatch, fail_above=2)
    state, m = step(state, batch)
    assert seen == [4, 3, 2] and downs.value - before == 2
    assert np.isfinite(float(m["loss"]))
    assert metrics_mod.get_default().gauge(
        "tony_train_saved_rung").value == 2
    # the rebuilt step is the one that runs from here on, and a later
    # trace (the committed state of the second call) stays under the
    # lowered ceiling
    for _ in range(2):
        state, _ = step(state, batch)
    assert seen[:3] == [4, 3, 2] and set(seen[3:]) <= {2}
    assert int(state["step"]) == 3 and downs.value - before == 2


def test_safety_net_lets_other_failures_through(monkeypatch):
    step, state, batch, seen = _tiny_step(monkeypatch, fail_above=-1)
    with pytest.raises(jax.errors.JaxRuntimeError, match="planted"):
        step(state, batch)
    assert seen == [4, 3, 2, 1, 0]           # rung 0 has nowhere to go

    def loss(p, b):
        raise jax.errors.JaxRuntimeError("INTERNAL: not about memory")
    with pytest.raises(jax.errors.JaxRuntimeError, match="INTERNAL"):
        make_train_step(loss, optax.sgd(0.1))(state, batch)


def test_unknown_policy_fails_at_config_time():
    cfg = T.PRESETS["tiny"]
    assert cfg.remat_policy == "fit"
    for old in ("bogus", "dots", "attn"):
        with pytest.raises(ValueError, match="remat_policy"):
            cfg.scaled(remat_policy=old)
        with pytest.raises(ValueError, match="remat_policy"):
            cfg.scaled(remat=False, remat_policy=old)
