"""The program's Swin-MoE train step (``launch.steps.make_swin_train_step``)
against the copy of it that the ``swin_train`` benchmark cell times
(``Program.step_fn`` of bench/configs/swin-moe-small.py): at the
configuration's rehearsal sizes, one step from the same weights and batch
gives bitwise-equal loss and parameters."""
import dataclasses
import os
import sys

import jax
import numpy as np

from repro.launch import steps
from repro.optim import adamw

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_program_step_matches_benchmark_copy():
    sys.path.insert(0, ROOT)
    try:
        from bench import generator, harness
        from bench.drivers import train_batches
    finally:
        sys.path.remove(ROOT)

    benchmark = harness.load_benchmark()
    cell = harness.find(benchmark["workloads"], "swin_train", "workload")
    entry = harness.find(benchmark["configs"], cell["config"], "config")
    c, mod = harness.load_config(entry, rehearsal=True)
    traffic = generator.load(cell["traffic"], rehearsal=True)
    prog = mod.Program(c, traffic)
    assert prog.pcfg.impl == c["kernel_impl"] == "pallas"
    assert c["moe_impl"] == "hexa"

    cfg = dataclasses.replace(prog.scfg, moe=dataclasses.replace(
        prog.scfg.moe, aux_weight=c["aux_weight"]))
    opt_cfg = adamw.OptimizerConfig(**c["optimizer"], master_fp32=False)
    assert opt_cfg == prog.opt_cfg

    key = jax.random.PRNGKey(20)
    state = prog.init_state(key)
    batch = train_batches.make_batches(traffic, jax.random.fold_in(key, 1))[0]

    (want_params, _), want_loss = jax.jit(prog.step_fn())(state, batch)
    params, _, loss = jax.jit(steps.make_swin_train_step(
        cfg, prog.pcfg, opt_cfg))(*state, batch["images"], batch["labels"])

    assert np.asarray(loss).tobytes() == np.asarray(want_loss).tobytes()
    got, tree = jax.tree.flatten(params)
    want, want_tree = jax.tree.flatten(want_params)
    assert tree == want_tree
    for a, b in zip(got, want):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # The step moved the weights: the comparison is not of two copies of
    # the initial state.
    assert any(not np.array_equal(a, b) for a, b in
               zip(got, jax.tree.leaves(prog.params_of(state))))
