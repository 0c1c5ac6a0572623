"""Property/parity layer for the two-level interconnect cost model
(parallel.autotune.Topology, DESIGN.md §10).

Four pinned properties:

  (1) flat degeneracy — a topology whose node holds the whole group prices
      every collective with the SAME EXPRESSION as the topology-less
      roofline, so ``Topology(intra_bw=hw.link_bw, ...)`` is bitwise equal
      to today's ``layer_latency``/``choose_mode``/``crossover_tokens``;
  (2) latency monotone in the inter-node traffic: more tokens never
      cheapens a collective, and shrinking ``inter_bw`` never speeds one up;
  (3) the crossover moves the right way: as ``inter_bw/intra_bw`` shrinks,
      data-centric's per-node weight staging amortises the slow links and
      the model->data crossover moves to FEWER (never more) tokens;
  (4) hierarchical dispatch crosses nodes with <= the flat schedule's
      bytes for every (top_k, node_size) — the Bernoulli overlap factor
      ``(nn-1)(1-(1-1/nn)^k) <= k(nn-1)/nn``, and the staged hierarchical
      schedule's inter-node share of ``moe_coll_bytes`` <= the flat ring's.

Each property runs over a deterministic grid (so the module passes with or
without hypothesis installed); with hypothesis present the same checks are
additionally driven over sampled shapes.
"""
import dataclasses
import itertools

import pytest

from repro.parallel import autotune as at
from repro.parallel.autotune import (
    Topology,
    V5E,
    choose_mode,
    crossover_tokens,
    dispatch_inter_bytes,
    layer_latency,
    layer_latency_uneven,
    moe_coll_bytes,
)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # deterministic grid still runs
    HAVE_HYPOTHESIS = False

MODES = ("model_centric", "data_centric")
SHAPES = [  # (d, f, e, k)
    (64, 256, 4, 2),
    (1024, 4096, 16, 2),
    (2048, 768, 64, 8),
]


# ---------------------------------------------------------------- helpers
# (shared by the grid tests and the hypothesis drivers)

def check_flat_degenerate(d, f, e, k, n_dev, tokens):
    """Flat topology (single node) == topology-less pricing, bitwise."""
    flat = Topology(intra_bw=V5E.link_bw, inter_bw=1.0, node_size=n_dev)
    hw = dataclasses.replace(V5E, topology=flat)
    assert flat.is_flat(n_dev)
    for mode in MODES:
        a = layer_latency(mode, tokens, d, f, e, k, n_dev)
        b = layer_latency(mode, tokens, d, f, e, k, n_dev, hw)
        assert a == b, (mode, tokens, a, b)  # bitwise, not allclose
    assert (choose_mode(tokens, d, f, e, k, n_dev=n_dev)
            == choose_mode(tokens, d, f, e, k, n_dev=n_dev, hw=hw))
    assert (crossover_tokens(d, f, e, k, n_dev=n_dev)
            == crossover_tokens(d, f, e, k, n_dev=n_dev, hw=hw))


def check_monotone(d, f, e, k, n_dev, topo):
    """Coll cost non-decreasing in tokens; non-increasing in inter_bw."""
    hw = dataclasses.replace(V5E, topology=topo)
    for mode in MODES:
        lats = [layer_latency(mode, t, d, f, e, k, n_dev, hw)
                for t in (2 ** i for i in range(4, 16))]
        assert all(b >= a for a, b in zip(lats, lats[1:])), (mode, lats)
    slower = dataclasses.replace(
        V5E, topology=dataclasses.replace(topo, inter_bw=topo.inter_bw / 4))
    for mode in MODES:
        for t in (64, 4096, 65536):
            assert (layer_latency(mode, t, d, f, e, k, n_dev, slower)
                    >= layer_latency(mode, t, d, f, e, k, n_dev, hw)), mode


def check_crossover_shift(d, f, e, k, n_dev, node_size):
    """crossover(slow inter) <= crossover(fast inter): data-centric wins
    earlier as the cross-node fabric degrades."""
    prev = None
    for inter in (50e9, 12.5e9, 3e9, 1e9):
        topo = Topology(intra_bw=50e9, inter_bw=inter, node_size=node_size)
        hw = dataclasses.replace(V5E, topology=topo)
        co = crossover_tokens(d, f, e, k, n_dev=n_dev, hw=hw)
        if co is not None and prev is not None:
            assert co <= prev, (inter, co, prev)
        if co is not None:
            prev = co


def check_dispatch_bytes(tokens, d, k, n_dev, node_size):
    """Hierarchical dispatch's expected inter-node bytes <= flat's, and the
    staged schedule's inter share of moe_coll_bytes <= the flat ring's."""
    hier = dispatch_inter_bytes(tokens, d, k, n_dev=n_dev,
                                node_size=node_size, hierarchical=True)
    flat = dispatch_inter_bytes(tokens, d, k, n_dev=n_dev,
                                node_size=node_size, hierarchical=False)
    assert 0.0 <= hier <= flat + 1e-9, (hier, flat)
    topo = Topology(intra_bw=50e9, inter_bw=12.5e9, node_size=node_size)
    for mode in MODES:
        _, inter_h = moe_coll_bytes(mode, tokens, d, 4 * d, 8, k,
                                    n_dev=n_dev, topology=topo,
                                    hierarchical=True)
        _, inter_f = moe_coll_bytes(mode, tokens, d, 4 * d, 8, k,
                                    n_dev=n_dev, topology=topo,
                                    hierarchical=False)
        assert inter_h <= inter_f + 1e-9, (mode, inter_h, inter_f)


# ---------------------------------------------------------------- the grid

@pytest.mark.parametrize("d,f,e,k", SHAPES)
def test_flat_topology_bitwise_degenerate(d, f, e, k):
    for n_dev in (2, 4, 8, 16):
        for tokens in (16, 1024, 65536):
            check_flat_degenerate(d, f, e, k, n_dev, tokens)


def test_single_device_and_parse_and_validation():
    t = Topology.parse("50e9:12.5e9:4")
    assert t == Topology(intra_bw=50e9, inter_bw=12.5e9, node_size=4)
    assert t.n_nodes(8) == 2 and t.n_nodes(4) == 1
    assert t.is_flat(4) and not t.is_flat(5)
    with pytest.raises(ValueError):
        Topology.parse("50e9:12.5e9")
    with pytest.raises(ValueError):
        Topology(intra_bw=-1.0)
    with pytest.raises(ValueError):
        Topology(node_size=0)


@pytest.mark.parametrize("d,f,e,k", SHAPES)
def test_latency_monotone_in_inter_bytes(d, f, e, k):
    for n_dev, ns in ((8, 4), (16, 4), (16, 8)):
        check_monotone(d, f, e, k, n_dev,
                       Topology(intra_bw=50e9, inter_bw=12.5e9, node_size=ns))


@pytest.mark.parametrize("d,f,e,k", SHAPES)
def test_crossover_shifts_toward_data_centric(d, f, e, k):
    for n_dev, ns in ((8, 2), (16, 4)):
        check_crossover_shift(d, f, e, k, n_dev, ns)


def test_crossover_shift_reference_case():
    """The DESIGN.md §10 worked example, pinned numerically."""
    d, f, e, k, n = 1024, 4096, 16, 2, 16
    fast = dataclasses.replace(
        V5E, topology=Topology(intra_bw=50e9, inter_bw=50e9, node_size=4))
    slow = dataclasses.replace(
        V5E, topology=Topology(intra_bw=50e9, inter_bw=12.5e9, node_size=4))
    assert crossover_tokens(d, f, e, k, n_dev=n, hw=fast) == 65536
    assert crossover_tokens(d, f, e, k, n_dev=n, hw=slow) == 32768


def test_dispatch_bytes_hier_le_flat_grid():
    for tokens, d in ((64, 32), (4096, 1024)):
        for n_dev, ns, k in itertools.product(
                (4, 8, 16, 32), (1, 2, 4, 8), (1, 2, 4, 8)):
            check_dispatch_bytes(tokens, d, k, n_dev, ns)


def test_dispatch_single_node_moves_nothing_across():
    assert dispatch_inter_bytes(4096, 64, 2, n_dev=4, node_size=4) == 0.0
    topo = Topology(intra_bw=50e9, inter_bw=1e9, node_size=8)
    intra, inter = moe_coll_bytes("model_centric", 4096, 64, 256, 8, 2,
                                  n_dev=8, topology=topo)
    assert inter == 0.0 and intra > 0.0


def test_uneven_roofline_prices_topology():
    """layer_latency_uneven threads the same per-level collective costs:
    a slower inter fabric can only increase the uneven max-latency."""
    d, f, e, k = 1024, 4096, 16, 2
    lat = [1.0, 1.0, 1.5, 1.5]
    topo = Topology(intra_bw=50e9, inter_bw=12.5e9, node_size=2)
    hw = dataclasses.replace(V5E, topology=topo)
    slower = dataclasses.replace(
        V5E, topology=dataclasses.replace(topo, inter_bw=1e9))
    for mode in MODES:
        a = layer_latency_uneven(mode, 65536, d, f, e, k, lat, hw=hw)
        b = layer_latency_uneven(mode, 65536, d, f, e, k, lat, hw=slower)
        assert b >= a, mode
    flat = Topology(intra_bw=V5E.link_bw, inter_bw=1.0, node_size=4)
    hwf = dataclasses.replace(V5E, topology=flat)
    for mode in MODES:
        assert (layer_latency_uneven(mode, 65536, d, f, e, k, lat)
                == layer_latency_uneven(mode, 65536, d, f, e, k, lat, hw=hwf))


# ------------------------------------------------- hypothesis-driven sweep

if HAVE_HYPOTHESIS:

    @st.composite
    def _topo_case(draw):
        d = draw(st.sampled_from([32, 64, 512, 1024, 4096]))
        f = draw(st.sampled_from([128, 768, 4096, 14336]))
        e = draw(st.sampled_from([4, 8, 16, 64]))
        k = draw(st.integers(1, min(e, 8)))
        n_dev = draw(st.sampled_from([2, 4, 8, 16, 32]))
        node_size = draw(st.sampled_from([1, 2, 4, 8, 16]))
        tokens = draw(st.sampled_from([16, 256, 4096, 65536]))
        return d, f, e, k, n_dev, node_size, tokens

    @given(_topo_case())
    @settings(max_examples=40, deadline=None)
    def test_flat_degenerate_property(case):
        d, f, e, k, n_dev, _, tokens = case
        check_flat_degenerate(d, f, e, k, n_dev, tokens)

    @given(_topo_case())
    @settings(max_examples=40, deadline=None)
    def test_monotone_property(case):
        d, f, e, k, n_dev, node_size, _ = case
        check_monotone(
            d, f, e, k, n_dev,
            Topology(intra_bw=50e9, inter_bw=12.5e9, node_size=node_size))

    @given(_topo_case())
    @settings(max_examples=40, deadline=None)
    def test_crossover_shift_property(case):
        d, f, e, k, n_dev, node_size, _ = case
        check_crossover_shift(d, f, e, k, n_dev, node_size)

    @given(_topo_case())
    @settings(max_examples=60, deadline=None)
    def test_dispatch_bytes_property(case):
        d, _, _, k, n_dev, node_size, tokens = case
        check_dispatch_bytes(tokens, d, k, n_dev, node_size)


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,known", [
    ("cpu", "cpu", True),            # CPU runs price the v5e they rehearse
    ("tpu", "TPU v5 lite", True),
    ("tpu", "TPU v9 imaginary", False),
    ("gpu", "NVIDIA H100", False),
])
def test_hardware_profile_keyed_by_device_kind(monkeypatch, platform, kind,
                                               known):
    monkeypatch.setattr(at.jax, "devices",
                        lambda: [_FakeDevice(platform, kind)])
    if known:
        assert at.hardware_profile() is at.V5E
    else:
        with pytest.raises(ValueError, match="no hardware profile"):
            at.hardware_profile()
