"""A whole serving cell at its rehearsal sizes on the CPU, past the
harness's look for a chip: sound, it comes out correct; with a served token
altered where it is produced, or with the fp8 control's tokens in its
place, it does not."""
import json

import numpy as np
import pytest

from bench import generator, harness, run
from bench.drivers import open_loop

CELL = "qwen3_decode"
#: The cell as BENCHMARK.json gives it, or as its files give it where
#: BENCHMARK.json does not hold it (yet).
ENTRY = {"name": CELL, "config": "qwen3-moe-30b-a3b-4l",
         "traffic": "azure_conv_open", "chips": 1, "why": "rehearsal"}
CONFIG = {"name": "qwen3-moe-30b-a3b-4l", "reduced": ["num_hidden_layers"],
          "file": "bench/configs/qwen3-moe-30b-a3b-4l.json"}


LOAD = harness.load_benchmark


def benchmark() -> dict:
    bench = LOAD()
    if all(w["name"] != CELL for w in bench["workloads"]):
        bench["workloads"] = bench["workloads"] + [ENTRY]
    if all(c["name"] != CONFIG["name"] for c in bench["configs"]):
        bench["configs"] = bench["configs"] + [CONFIG]
    return bench


@pytest.fixture(autouse=True)
def with_cell(monkeypatch):
    monkeypatch.setattr(harness, "load_benchmark", benchmark)


def run_cell(monkeypatch, capsys) -> dict:
    assert run.main(["--workload", CELL, "--seed", str(2 ** 36 + 5),
                     "--seconds", "4", "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(monkeypatch, capsys):
    out = run_cell(monkeypatch, capsys)
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) == set(harness.load_limits(CELL))


def test_altered_token_is_not_correct(monkeypatch, capsys):
    from repro.launch import serve

    pick = serve.next_token

    def altered(logits_row, req):
        return (pick(logits_row, req) + 1) % np.asarray(logits_row).shape[-1]

    monkeypatch.setattr(serve, "next_token", altered)
    out = run_cell(monkeypatch, capsys)
    assert not out["correct"]


def test_fp8_control_fails_the_limit():
    """The fp8 reference's first tokens, in place of the served ones, at
    the positions of requests the program served."""
    bench = benchmark()
    cell = harness.find(bench["workloads"], CELL, "workload")
    cfg, mod = harness.load_config(
        harness.find(bench["configs"], cell["config"], "config"),
        rehearsal=True)
    traffic = generator.load(cell["traffic"], rehearsal=True)
    limits = harness.load_limits(CELL, rehearsal=True)
    seed = 9
    _, stream, loop, _ = open_loop.start(cfg, mod, traffic, seed)
    done = []
    while len(done) < 8:
        done += loop.window(0.5)["done"]
    numbers = open_loop.reference_numbers(
        mod.Reference(cfg, stream.max_total),
        mod.make_params(cfg, open_loop.weights_key(seed)),
        open_loop.pick_sample(done, seed), control=True)
    assert any(numbers[k] > v for k, v in limits.items()), numbers
