"""A whole training cell at its rehearsal sizes on the CPU, past the
harness's look for a chip: sound, it comes out correct; with the timed
path broken underneath, or with the fp8 control in its place, it does
not."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import compare, generator, harness, run
from bench.drivers import train_batches

CELL = "swin_train"


def run_cell(monkeypatch, capsys, patch=None) -> dict:
    if patch is not None:
        load = harness.load_config

        def patched(entry, rehearsal=False):
            cfg, mod = load(entry, rehearsal=rehearsal)
            patch(mod)
            return cfg, mod

        monkeypatch.setattr(harness, "load_config", patched)
    assert run.main(["--workload", CELL, "--seed", str(2 ** 35 + 11),
                     "--seconds", "0.5", "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(monkeypatch, capsys):
    out = run_cell(monkeypatch, capsys)
    assert out["correct"] and out["rehearsal"] and "metrics" not in out
    assert out["device"]["platform"] == "cpu"
    assert set(out["checks"]) == set(harness.load_limits(CELL))


def unchanged(mod):
    """Every step returns the state it was given (and the true loss)."""
    step_fn = mod.Program.step_fn

    def broken(self):
        step = step_fn(self)

        def train(state, batch):
            _, loss = step(jax.tree.map(jnp.copy, state), batch)
            return state, loss
        return train

    mod.Program.step_fn = broken


def half_batch(mod):
    """Every step drops the second half of its batch."""
    step_fn = mod.Program.step_fn

    def broken(self):
        step = step_fn(self)

        def train(state, batch):
            return step(state, {k: v[:len(v) // 2] for k, v in batch.items()})
        return train

    mod.Program.step_fn = broken


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_broken_step_is_not_correct(monkeypatch, capsys, fault):
    out = run_cell(monkeypatch, capsys, patch=fault)
    assert not out["correct"]


def test_fp8_control_fails_a_limit():
    """The reference with every matmul operand in float8, in the program's
    place, against the float32 reference, at the rehearsal sizes."""
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], CELL, "workload")
    cfg, mod = harness.load_config(
        harness.find(bench["configs"], cell["config"], "config"),
        rehearsal=True)
    traffic = generator.load(cell["traffic"], rehearsal=True)
    limits = harness.load_limits(CELL, rehearsal=True)
    key = generator.seed_key(3)
    batches = train_batches.make_batches(traffic, jax.random.fold_in(key, 1))
    truth = train_batches.reference_numbers(mod.Reference(cfg), key, batches)
    low = train_batches.reference_numbers(
        mod.Reference(cfg, control=True), key, batches)
    numbers = compare.train_numbers(low, truth)
    assert any(numbers[k] > limits[k] for k in limits), numbers
