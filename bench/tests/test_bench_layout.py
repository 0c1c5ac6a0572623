"""The benchmark makes the program's weights itself; their tree must be the
one the program's own init builds, leaf for leaf, at the rehearsal sizes."""
import os
import sys

import jax

from bench import harness

sys.path.insert(0, os.path.join(harness.ROOT, "src"))


def bench_tree(name):
    cfg, mod = harness.load_config(
        {"name": name, "file": f"bench/configs/{name}.json"}, rehearsal=True)
    return cfg, mod, jax.eval_shape(lambda k: mod.make_params(cfg, k),
                                    jax.random.PRNGKey(0))


def same(a, b):
    fa, ta = jax.tree_util.tree_flatten_with_path(a)
    fb, tb = jax.tree_util.tree_flatten_with_path(b)
    assert ta == tb
    for (pa, x), (_, y) in zip(fa, fb):
        assert (x.shape, x.dtype) == (y.shape, y.dtype), pa


def test_swin_tree_is_the_programs():
    from repro.models import swin
    from repro.parallel.sharding import split_tree

    cfg, mod, tree = bench_tree("swin-moe-small")
    prog = mod.Program(cfg, {"batch": 2})
    want = jax.eval_shape(lambda k: split_tree(swin.init_swin(k, prog.scfg))[0],
                          jax.random.PRNGKey(0))
    same(tree, want)


def test_qwen3_tree_is_the_programs():
    from repro.models import lm

    cfg, mod, tree = bench_tree("qwen3-moe-30b-a3b-4l")
    prog = mod.Program(cfg, {})
    want, _ = lm.abstract_params(prog.mcfg)
    same(tree, want)
