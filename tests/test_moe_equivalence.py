"""The central correctness claim: the expert-specific (Hexa) path computes
EXACTLY what per-token expert evaluation computes — forward and gradients —
for every impl, both expert body types, fused and unfused backward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, espec
from repro.core.reindex import build_reindex
from repro.core.routing import route
from repro.kernels import ops, ref


def _params(e, d, f, glu, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    p = {"router": jax.random.normal(ks[0], (d, e)) * 0.2}
    if glu:
        p["w_gate"] = jax.random.normal(ks[1], (e, d, f)) * 0.2
        p["w_up"] = jax.random.normal(ks[2], (e, d, f)) * 0.2
        p["w_down"] = jax.random.normal(ks[3], (e, f, d)) * 0.2
    else:
        p["w1"] = jax.random.normal(ks[1], (e, d, f)) * 0.2
        p["b1"] = jax.random.normal(ks[4], (e, f)) * 0.2
        p["w2"] = jax.random.normal(ks[2], (e, f, d)) * 0.2
        p["b2"] = jax.random.normal(ks[5], (e, d)) * 0.2
    return p


N, D, F, E, K, BLK = 48, 16, 24, 4, 2, 8


@pytest.mark.parametrize("impl", ["blocked", "pallas", "ref"])
@pytest.mark.parametrize("glu", [True, False])
def test_forward_matches_per_token_oracle(impl, glu):
    p = _params(E, D, F, glu)
    x = jax.random.normal(jax.random.PRNGKey(9), (N, D))
    out = espec.hexa_moe_ffn(
        x, p, num_experts=E, top_k=K, act="gelu" if not glu else "silu",
        glu=glu, blk=BLK, impl=impl,
    )
    r = route(x, p["router"], K)
    if glu:
        oracle = ref.moe_ffn_per_token(
            x, r.expert_idx, r.gates,
            p["w_gate"], jnp.zeros((E, F)), p["w_down"], jnp.zeros((E, D)),
            lambda h: jax.nn.silu(h),
        )
        # glu oracle needs the up-projection too: compute directly
        def token_fn(xt, et, gt):
            def slot(e):
                return (jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_up"][e])
                        ) @ p["w_down"][e]
            ys = jax.vmap(slot)(et)
            return jnp.sum(ys * gt[:, None], axis=0)
        oracle = jax.vmap(token_fn)(x, r.expert_idx, r.gates)
    else:
        oracle = ref.moe_ffn_per_token(
            x, r.expert_idx, r.gates, p["w1"], p["b1"], p["w2"], p["b2"],
            jax.nn.gelu,
        )
    np.testing.assert_allclose(
        np.asarray(out.y), np.asarray(oracle), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("impl", ["blocked", "pallas"])
@pytest.mark.parametrize("fused", [True, False])
def test_gradients_match_oracle(impl, fused):
    glu = False
    p = _params(E, D, F, glu)
    x = jax.random.normal(jax.random.PRNGKey(7), (N, D))
    tgt = jax.random.normal(jax.random.PRNGKey(8), (N, D))

    ops.set_fused_backward(fused)
    try:
        def loss_hexa(p):
            out = espec.hexa_moe_ffn(
                x, p, num_experts=E, top_k=K, act="gelu", glu=glu,
                blk=BLK, impl=impl,
            )
            return jnp.sum((out.y - tgt) ** 2)

        def loss_oracle(p):
            r = route(x, p["router"], K)
            y = ref.moe_ffn_per_token(
                x, r.expert_idx, r.gates, p["w1"], p["b1"], p["w2"], p["b2"],
                jax.nn.gelu,
            )
            return jnp.sum((y - tgt) ** 2)

        g1 = jax.grad(loss_hexa)(p)
        g2 = jax.grad(loss_oracle)(p)
        for k in g1:
            np.testing.assert_allclose(
                np.asarray(g1[k]), np.asarray(g2[k]),
                rtol=5e-4, atol=5e-4, err_msg=f"{impl} fused={fused} {k}",
            )
    finally:
        ops.set_fused_backward(True)


def test_hexa_equals_no_drop_dispatch():
    """dispatch/combine with infinite capacity == hexa exactly."""
    p = _params(E, D, F, glu=False, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (N, D))
    r = route(x, p["router"], K)
    out = espec.hexa_moe_ffn(
        x, p, num_experts=E, top_k=K, act="gelu", glu=False, blk=BLK,
        impl="blocked",
    )
    base = baselines.grouped_dense_moe(
        x, r, p["w1"], p["b1"], p["w2"], p["b2"], act=jax.nn.gelu
    )
    np.testing.assert_allclose(
        np.asarray(out.y), np.asarray(base), rtol=2e-5, atol=2e-5
    )


def test_dispatch_capacity_drops_tokens():
    """Tiny capacity must change (degrade) the result — the redundancy /
    quality trade the paper eliminates."""
    p = _params(E, D, F, glu=False, seed=5)
    x = jax.random.normal(jax.random.PRNGKey(6), (N, D))
    r = route(x, p["router"], K)
    full = baselines.grouped_dense_moe(
        x, r, p["w1"], p["b1"], p["w2"], p["b2"], act=jax.nn.gelu
    )
    tight = baselines.dispatch_combine_moe(
        x, r, p["w1"], p["b1"], p["w2"], p["b2"], act=jax.nn.gelu,
        capacity=2,
    )
    assert np.abs(np.asarray(full) - np.asarray(tight)).max() > 1e-3
