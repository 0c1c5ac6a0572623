"""The fused window-attention kernel against the XLA formulation it
replaced, through ``models.swin._window_attention``, in interpret mode.

The reference below is the attention core as the model computed it before
the kernel: split into heads, scaled QKᵀ plus the relative-position bias,
softmax, P·V. Each case checks the block's output, the gradients of a
scalar of it with respect to the input, the projections and ``rel_bias``
(the qkv gradient reaches ``qkv_w``, ``qkv_b`` and the input), and the
kernel's own qkv and bias gradients.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import window_attention as wa
from repro.models import swin


def _xla_core(qkv, bias, heads):
    n, c = bias.shape[0], qkv.shape[1] // 3
    hd = c // heads
    q, k, v = jnp.split(qkv.reshape(-1, n, 3, heads, hd), 3, 2)
    q, k, v = (t[:, :, 0] for t in (q, k, v))
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * hd ** -0.5
    logits = logits + bias.transpose(2, 0, 1)[None]
    attn = jax.nn.softmax(logits, -1).astype(qkv.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(-1, c)


def _block(p, x, heads, window, shift):
    """A block's attention as ``swin_forward`` runs it."""
    if shift:
        x = jnp.roll(x, (-shift, -shift), axis=(1, 2))
    y = swin._window_attention(p, x, heads, window, 1e-5)
    if shift:
        y = jnp.roll(y, (shift, shift), axis=(1, 2))
    return y


# (batch, feature map side, heads, shift): head_dim 32 throughout. An even
# window count packs two windows into each score tile; one window alone
# takes a tile of its own.
CASES = {
    "h3": (1, 14, 3, 0),
    "h3_shifted": (1, 14, 3, 3),
    "h12_shifted": (1, 14, 12, 3),
    "window_clamped_to_4": (2, 4, 3, 0),
    "h6_one_window_per_tile": (1, 7, 6, 0),
    "h3_two_grid_steps_shifted": (4, 56, 3, 3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_window_attention_matches_xla(case, monkeypatch):
    batch, side, heads, shift = CASES[case]
    c, window = 32 * heads, 7
    w_eff = min(window, side)
    n, nw = w_eff * w_eff, batch * (side // w_eff) ** 2
    tile = wa.windows_per_tile(nw, n)
    g = wa.window_attention_blocks(nw // tile, tile * n, c, heads, 4)
    assert nw % (tile * g) == 0
    if case.startswith("h3_two_grid_steps"):
        assert nw // (tile * g) >= 2

    ks = jax.random.split(jax.random.PRNGKey(sum(map(ord, case))), 4)
    p = {k: v.value for k, v in
         swin._init_window_attn(ks[0], c, heads, window, jnp.float32).items()}
    # Weights large enough that the softmax is far from uniform.
    p["rel_bias"] = p["rel_bias"] * 50.0
    p["qkv_w"] = p["qkv_w"] * 5.0
    x = jax.random.normal(ks[1], (batch, side, side, c))
    dy = jax.random.normal(ks[2], (batch, side, side, c))

    def run():
        # A new function each time, so the reference is traced afresh.
        def step(p, x, dy):
            y, vjp = jax.vjp(
                lambda p, x: _block(p, x, heads, window, shift), p, x)
            return y, *vjp(dy)
        return jax.jit(step)(p, x, dy)

    got = run()
    with monkeypatch.context() as m:
        m.setattr(swin, "window_attention", _xla_core)
        want = run()
    y, got_g = got[0], got[1:]
    y_ref, want_g = want[0], want[1:]
    np.testing.assert_allclose(y, y_ref, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(y_ref).max()))
    for name in ("qkv_w", "qkv_b", "proj_w", "proj_b", "rel_bias"):
        scale = float(jnp.abs(want_g[0][name]).max())
        np.testing.assert_allclose(got_g[0][name], want_g[0][name],
                                   rtol=1e-4, atol=1e-5 * scale, err_msg=name)
    np.testing.assert_allclose(got_g[1], want_g[1], rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want_g[1]).max()))

    # The kernel's own cotangents: dqkv and the gathered bias's gradient.
    qkv = jax.random.normal(ks[3], (nw * n, 3 * c)) * 2.0
    bias = p["rel_bias"][swin._rel_bias_index(w_eff)]
    do = jax.random.normal(ks[2], (nw * n, c))
    outs = []
    for core in (wa.window_attention, _xla_core):
        def step(a, b, do):
            o, vjp = jax.vjp(lambda a, b: core(a, b, heads), a, b)
            return o, *vjp(do)
        outs.append(jax.jit(step)(qkv, bias, do))
    for a, b in zip(*outs):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))
