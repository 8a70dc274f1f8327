"""The seam between the harness and a model family
(``benchmark/families/<name>.py``): every configuration loads through its
family and the family's counts agree with the trees it and the program
build; the dense family's weights are bit for bit what the harness made
before the family existed; a configuration without a family, or one its
family cannot express, is refused with the reason; every family provides
the whole list; and the parent process of a run stays JAX-free."""

import glob
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import modelcfg

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CONFIGS = [e["name"] for e in json.load(_f)["configs"]]
FAMILIES = sorted(os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(BENCH, "families", "*.py")) if not p.endswith("__init__.py"))
#: what ``dense_decoder.py``'s docstring lists
PROVIDES = ("check", "program_config", "layer_kinds", "param_count",
            "forward_flops_per_token", "decode_step_bytes", "make_params",
            "layer_weights", "outer_weights", "leaf_name", "leaf_norms",
            "layer_forward", "head", "CONTRACT", "HEAD_LEAVES")
SEED = 2**31 + 11
#: sha256 of ``tiny.json``'s weights at SEED on the CPU backend, taken on
#: the commit before the family seam (9dc9e7f: ``lib/weights.py``'s
#: ``make_params``, ``layer`` and ``outer``) and never since
PARENT_DIGESTS = {
    ("float32", "make_params"):
        "faa3277e1b4e1f867c1128d804d6c6197f9e4cb5fa014eee8e53acfd1b82ad2f",
    ("float32", "layers"):
        "27a136a988d4851e4ca536760545d3b47726b3ac6551863358d9dc45ac613781",
    ("float32", "outer"):
        "92e7eeec02cb4dae15e88a8c46bfe1fa964af947f09e95118a5daaceaabaaa94",
    ("bfloat16", "make_params"):
        "f0c224062c0e0504773e8cd2c0eac96596c1c31b83c4c0e6a9db5fbcc068a224",
    ("bfloat16", "layers"):
        "3f6a82db545f767152e6b26fabb257607c9a9c0e8ca0fc1809a12be7f3d4d325",
    ("bfloat16", "outer"):
        "95a4dfd8a4e10f3a500e9027910b9aba706626f4f8ca0e4d48f55593480571ac",
}


def _size(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def _digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config", CONFIGS)
def test_counts_are_the_trees(config):
    """Published sizes, so shapes only: the family's parameter count is
    the size of the tree it would make and of the program's own init for
    the configuration object it returns; layer by layer it is the same
    tree."""
    from tony_tpu.models import transformer as T
    c = modelcfg.load(config)
    fam = modelcfg.family(c)
    made = jax.eval_shape(lambda: fam.make_params(7, c, jnp.bfloat16))
    cfg = fam.program_config(c, dtype=jnp.bfloat16)
    own = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    assert fam.param_count(c) == _size(made) == _size(own)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), made) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), own)
    kinds = fam.layer_kinds(c)
    assert len(kinds) == c["num_hidden_layers"]
    one_by_one = sum(_size(jax.eval_shape(
        lambda li=li, kind=kind: fam.layer_weights(
            np.uint32(7), np.int32(li), c, jnp.bfloat16, kind)))
        for li, kind in enumerate(kinds))
    outer = jax.eval_shape(lambda: fam.outer_weights(np.uint32(7), c,
                                                     jnp.bfloat16))
    assert one_by_one + _size(outer) == fam.param_count(c)
    assert set(fam.HEAD_LEAVES) <= set(outer) and "embed" in outer
    assert fam.forward_flops_per_token(c, 1024) > 2 * fam.param_count(c) \
        - 2 * _size(outer["embed"])
    assert fam.decode_step_bytes(c, 0.0, None) == 2 * (
        fam.param_count(c) - _size(outer["embed"]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dense_weights_are_the_parents_bit_for_bit(dtype):
    c = modelcfg.load(os.path.join(HERE, "tiny.json"))
    fam = modelcfg.family(c)
    got = {
        "make_params": fam.make_params(SEED, c, dtype),
        "layers": {str(li): fam.layer_weights(np.uint32(SEED), np.int32(li),
                                              c, dtype, kind)
                   for li, kind in enumerate(fam.layer_kinds(c))},
        "outer": fam.outer_weights(np.uint32(SEED), c, dtype)}
    for what, tree in got.items():
        assert _digest(tree) == PARENT_DIGESTS[dtype, what], what


def _tiny(tmp_path, **changes):
    with open(os.path.join(HERE, "tiny.json")) as f:
        c = json.load(f)
    c.update(changes)
    path = os.path.join(tmp_path, "changed.json")
    with open(path, "w") as f:
        json.dump({k: v for k, v in c.items() if v is not None}, f)
    return path


def test_refused_with_the_reason(tmp_path):
    with pytest.raises(ValueError, match="no \"family\" key"):
        modelcfg.load(_tiny(tmp_path, family=None))
    with pytest.raises(ValueError, match="RoPE base 10000"):
        modelcfg.load(_tiny(tmp_path, rope_theta=50000.0))
    with pytest.raises(ValueError, match="head_dim x heads"):
        modelcfg.load(_tiny(tmp_path, head_dim=16))
    with pytest.raises(ModuleNotFoundError, match="no_such_family"):
        modelcfg.load(_tiny(tmp_path, family="no_such_family"))
    # a path ending in .py is a file beside the configuration
    with pytest.raises(FileNotFoundError, match="not_here.py"):
        modelcfg.load(_tiny(tmp_path, family="not_here.py"))


@pytest.mark.parametrize("name", FAMILIES)
def test_family_provides_the_whole_list(name):
    fam = modelcfg.family({"family": name})
    missing = [n for n in PROVIDES if not hasattr(fam, n)]
    assert not missing, f"families/{name}.py lacks {missing}"


def test_the_parent_of_a_run_never_imports_jax():
    """``run.py``'s process reads every cell's configuration, its family's
    check and counts, and every family module, and JAX is not loaded."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from benchmark import run\n"
        "from benchmark.lib import flops, modelcfg\n"
        f"bench = json.load(open({os.path.join(ROOT, 'BENCHMARK.json')!r}))\n"
        "for w in bench['workloads']:\n"
        "    _, cell, c, mix = run.load_cell(w['name'])\n"
        "    fam = modelcfg.family(c)\n"
        "    assert fam.param_count(c) > 0\n"
        "    assert flops.train_flops_per_token(c, 1024) > 0\n"
        "    assert fam.decode_step_bytes(c, 1.0, None) > 0\n"
        f"for name in {FAMILIES!r}:\n"
        "    modelcfg.family({'family': name})\n"
        "assert 'jax' not in sys.modules, 'the parent imported jax'\n"
        "print('cells', len(bench['workloads']))\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip().startswith("cells ")
