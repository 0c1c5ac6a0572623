"""The traffic generators: deterministic in their seed, and true to the
distributions their data files state."""
import json
import os
import statistics

import numpy as np
import pytest

from bench import generator, harness
from bench.drivers import open_loop, train_batches

TRAFFIC = os.path.join(os.path.dirname(generator.__file__), "traffic")
MIX = "azure_conv_open"


def test_quantile_grid_uniform_hits_every_length_once():
    grid = generator.quantile_grid({"dist": "uniform", "min": 8, "max": 32},
                                   25)
    assert sorted(grid) == list(range(8, 33))


def test_quantile_grid_lognormal_median_and_clip():
    dist = {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 64,
            "max": 1024}
    grid = generator.quantile_grid(dist, 513)
    assert statistics.median(grid) == 256
    assert grid.min() == 64 and grid.max() == 1024
    # Share below the median's e-fold drop: Phi(-1/0.7) = 7.7%.
    assert np.mean(grid < 256 / np.e) == pytest.approx(0.0766, abs=0.003)


def test_residual_grid_is_the_length_biased_remainder():
    # Lengths uniform over 1..100: P(R = r) is proportional to 101 - r, so
    # the mean remainder is (E[L^2] / E[L] + 1) / 2 = 34.
    dist = {"dist": "uniform", "min": 1, "max": 100}
    grid = generator.residual_grid(dist, 2000)
    assert grid.min() >= 1 and grid.max() <= 100
    assert grid.mean() == pytest.approx(34.0, rel=0.01)
    assert np.mean(grid <= 50) == pytest.approx(0.75, abs=0.01)


def test_exponential_grid_is_a_poisson_process_of_rate_one():
    gaps = generator.exponential_grid(1000)
    assert gaps.mean() == pytest.approx(1.0)
    assert np.median(gaps) == pytest.approx(np.log(2), rel=0.01)
    assert np.mean(gaps > 2.0) == pytest.approx(np.exp(-2.0), abs=0.005)


def stream(seed, n=40):
    s = open_loop.Stream(generator.load(MIX), seed, vocab=151936)
    return s, [s.next() for _ in range(n)]


def test_open_loop_is_deterministic_in_its_seed():
    (_, a), (_, b) = stream(2 ** 40 + 3), stream(2 ** 40 + 3)
    assert all(ga == gb and (pa == pb).all() and na == nb
               for (ga, pa, na), (gb, pb, nb) in zip(a, b))
    _, c = stream(7)
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in c]


def test_every_seed_gets_the_same_lengths():
    spec = generator.load(MIX)
    n = spec["grid"]
    (sa, a), (sb, b) = stream(1, n), stream(2, n)
    assert sorted(len(p) for _, p, _ in a) == sorted(len(p) for _, p, _ in b)
    assert sorted(m for _, _, m in a) == sorted(m for _, _, m in b)
    assert sorted(g for g, _, _ in a) == pytest.approx(
        sorted(g for g, _, _ in b))
    assert sum(g for g, _, _ in a) == pytest.approx(n / spec["rate_per_s"])
    assert all(p.max() < 151936 and p.dtype == np.int32 for _, p, _ in a)
    assert sorted(len(p) for p, _ in sa.warm()) == sorted(
        len(p) for p, _ in sb.warm())
    assert len(sa.warm()) == spec["in_flight"]


def test_lengths_have_the_stated_medians():
    spec = generator.load(MIX)
    s, reqs = stream(3, spec["grid"])
    assert statistics.median(len(p) for _, p, _ in reqs) == pytest.approx(
        spec["prompt_len"]["median"], abs=2)
    assert statistics.median(m for _, _, m in reqs) == pytest.approx(
        spec["output_len"]["median"], abs=2)
    assert s.max_total == (spec["prompt_len"]["max"]
                           + spec["output_len"]["max"])


def test_rehearsal_replaces_keys():
    full = generator.load(MIX)
    tiny = generator.load(MIX, rehearsal=True)
    assert tiny["slots"] < full["slots"]
    assert tiny["prompt_len"]["dist"] == full["prompt_len"]["dist"]
    assert "rehearsal" not in full


def test_make_batches_is_deterministic_and_distinct():
    import jax

    spec = {"kind": "train_batches", "batch": 2, "batches": 3, "inputs": {
        "images": {"dist": "normal", "shape": [4, 4, 3]},
        "labels": {"dist": "uniform_int", "high": 10},
        "tokens": {"dist": "uniform_int", "high": 50, "shape": [6]},
        "targets": {"next_of": "tokens"},
        "mask": {"ones_of": "tokens"}}}
    key = generator.seed_key(2 ** 33 + 5)
    a = train_batches.make_batches(spec, key)
    b = train_batches.make_batches(spec, key)
    assert len(a) == 3
    for x, y in zip(a, b):
        for k in x:
            assert np.array_equal(np.asarray(x[k]), np.asarray(y[k]))
    assert a[0]["images"].shape == (2, 4, 4, 3)
    assert not np.array_equal(np.asarray(a[0]["images"]),
                              np.asarray(a[1]["images"]))
    labels = np.asarray(a[0]["labels"])
    assert labels.dtype == np.int32 and labels.min() >= 0 and labels.max() < 10
    tokens = np.asarray(a[0]["tokens"])
    assert np.array_equal(np.asarray(a[0]["targets"]),
                          np.roll(tokens, -1, axis=1))
    assert np.asarray(a[0]["mask"]).sum() == tokens.size
    other = train_batches.make_batches(spec, jax.random.fold_in(key, 1))
    assert not np.array_equal(np.asarray(other[0]["images"]),
                              np.asarray(a[0]["images"]))


def test_seed_key_takes_large_seeds_and_refuses_negative():
    import jax

    k1 = generator.seed_key(5)
    k2 = generator.seed_key(5 + 2 ** 32)
    assert not np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k2))
    with pytest.raises(ValueError):
        generator.seed_key(-1)


def test_a_kind_without_a_driver_is_refused():
    with pytest.raises(ValueError):
        harness.load_driver("no_such_kind")
    with pytest.raises(ValueError):
        harness.load_driver("../run")


@pytest.mark.parametrize("name", sorted(
    os.path.splitext(f)[0] for f in os.listdir(TRAFFIC)))
def test_traffic_files_are_valid(name):
    spec = generator.load(name)
    driver = harness.load_driver(spec["kind"])
    assert callable(driver.run) and callable(driver.readings)
    with open(os.path.join(TRAFFIC, f"{name}.json")) as f:
        json.load(f)
