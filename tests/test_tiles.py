"""The expert kernels' tile rule (kernels/tiles.py) as pure functions of
shapes: whole-expert blocks where they fit VMEM, 128-multiples that divide
the dimensions where they do not, and the grid-step count it gives a
Swin-MoE-Small train step."""
import pytest

from repro.common import round_up
from repro.kernels import tiles
from repro.kernels.esffn import esffn_blocks, esffn_vmem
from repro.kernels.esfk import esfk_blocks, esfk_vmem
from repro.kernels.esmm import esmm_blocks, esmm_vmem

BLK = 128
# Swin-MoE-Small at batch 64, float32: (d, hidden, tokens, MoE layers) of
# stages 3 and 4; 8 experts, top-1.
SWIN_STAGES = [(384, 1536, 64 * 14 * 14, 9), (768, 3072, 64 * 7 * 7, 1)]
SWIN_E = 8
# qwen3-moe-30b-a3b: d 2048, expert hidden 768, bf16 activations.
QD, QF = 2048, 768


def _swin_rows(tokens):
    return round_up(tokens + SWIN_E * (BLK - 1), BLK)


def _split_ok(dims, blocks, footprint, quant=None):
    for d, b in zip(dims, blocks):
        assert d % b == 0 and (b == d or b % 128 == 0), (dims, blocks)
        if quant:
            assert b % quant == 0, (blocks, quant)
    assert footprint <= tiles.VMEM_BUDGET


@pytest.mark.parametrize("dim,tile,want", [
    (1536, 1, [1536, 768, 512, 384, 256, 128]),
    (384, 1, [384, 128]),
    (128, 1, [128]),
    (24, 1, [24]),
    (768, 256, [768, 256]),
    (200, 1, [200]),
])
def test_candidates(dim, tile, want):
    assert tiles.candidates(dim, tile) == want


@pytest.mark.parametrize("d,f", [(d, f) for d, f, _, _ in SWIN_STAGES])
@pytest.mark.parametrize("kernel", ["esmm", "esmm_down", "esfk",
                                    "esfk_down", "esffn_mlp"])
def test_swin_widths_take_whole_experts(kernel, d, f):
    if kernel == "esffn_mlp":
        assert esffn_blocks(BLK, d, f, glu=False, x_itemsize=4,
                            w_itemsize=4, has_b1=True, has_b2=True) == f
        return
    d1, d2 = (f, d) if kernel.endswith("_down") else (d, f)
    if kernel.startswith("esmm"):
        got = esmm_blocks(BLK, d1, d2, x_itemsize=4, w_itemsize=4,
                          has_bias=True)
    else:
        got = esfk_blocks(BLK, d1, d2, itemsize1=4, itemsize2=4)
    assert got == (d1, d2)


@pytest.mark.parametrize("bm", [16, 128])
@pytest.mark.parametrize("kernel,quant", [
    ("esmm_up", None), ("esmm_down", None), ("esfk", None),
    ("esffn_glu", None),
    # dW of a quantized payload is never taken: no esfk case
    ("esmm_up", 128), ("esmm_down", 128), ("esffn_glu", 128),
])
def test_qwen_widths_fit_and_divide(kernel, quant, bm):
    w_item = 1 if quant else 2
    qt = None if quant is None else (quant, quant)
    if kernel == "esffn_glu":
        kw = dict(glu=True, x_itemsize=2, w_itemsize=w_item, quant_tiles=qt)
        bf = esffn_blocks(bm, QD, QF, **kw)
        _split_ok((QF,), (bf,), esffn_vmem(bm, QD, bf, **kw), quant)
    elif kernel == "esfk":
        b = esfk_blocks(bm, QD, QF, itemsize1=2, itemsize2=2)
        _split_ok((QD, QF), b,
                  esfk_vmem(bm, *b, itemsize1=2, itemsize2=2))
    else:
        d1, d2 = (QD, QF) if kernel == "esmm_up" else (QF, QD)
        kw = dict(x_itemsize=2, w_itemsize=w_item, quant_tiles=qt)
        b = esmm_blocks(bm, d1, d2, **kw)
        _split_ok((d1, d2), b, esmm_vmem(bm, *b, **kw), quant)


@pytest.mark.parametrize("kernel", ["esmm", "esfk", "esffn_glu"])
def test_too_wide_for_vmem_splits(kernel):
    """An 8192-wide bf16 GLU expert with a 28,672 hidden cannot sit in
    VMEM whole: the blocks split into 128-multiples that divide."""
    d, f = 8192, 28672
    if kernel == "esffn_glu":
        kw = dict(glu=True, x_itemsize=2, w_itemsize=2)
        bf = esffn_blocks(BLK, d, f, **kw)
        assert bf < f
        _split_ok((f,), (bf,), esffn_vmem(BLK, d, bf, **kw))
        # the largest candidate that fits
        bigger = [b for b in tiles.candidates(f) if b > bf]
        assert all(esffn_vmem(BLK, d, b, **kw) > tiles.VMEM_BUDGET
                   for b in bigger)
        return
    if kernel == "esmm":
        b = esmm_blocks(BLK, d, f, x_itemsize=2, w_itemsize=2)
        fp = esmm_vmem(BLK, *b, x_itemsize=2, w_itemsize=2)
    else:
        b = esfk_blocks(BLK, d, f, itemsize1=2, itemsize2=2)
        fp = esfk_vmem(BLK, *b, itemsize1=2, itemsize2=2)
    assert b != (d, f)
    _split_ok((d, f), b, fp)
    assert tiles.grid_steps((d, f), b) < tiles.grid_steps((d, f), (128, 128))


def _swin_step_calls():
    """(kernel, rows, d1, d2) of the 60 expert-kernel calls of one
    Swin-MoE-Small batch-64 train step: per MoE layer the esffn forward,
    and in the backward the z recompute, the transposed esmms of t and dX,
    and the esfks of (dW2, db2) and (dW1, db1) (``ops._esffn_mlp_bwd``)."""
    for d, f, tokens, layers in SWIN_STAGES:
        rows = _swin_rows(tokens)
        for _ in range(layers):
            yield "esffn", rows, d, f
            yield "esmm", rows, d, f      # z = xs @ w1 + b1
            yield "esmm", rows, d, f      # t = dys @ w2^T
            yield "esmm", rows, f, d      # dX = dz @ w1^T
            yield "esfk", rows, f, d      # (dW2, db2) from (h, dys)
            yield "esfk", rows, d, f      # (dW1, db1) from (xs, dz)


def _steps(kernel, rows, d1, d2, blocks):
    nm = rows // BLK
    if kernel == "esffn":
        return nm * tiles.grid_steps((d2,), blocks)
    return nm * tiles.grid_steps((d1, d2), blocks)


@pytest.mark.parametrize("rule", ["shape_derived", "fixed_128"])
def test_swin_step_grid_steps(rule):
    calls = list(_swin_step_calls())
    assert len(calls) == 60
    assert [_swin_rows(t) // BLK for _, _, t, _ in SWIN_STAGES] == [106, 33]
    total = 0
    for kernel, rows, d1, d2 in calls:
        if rule == "fixed_128":
            blocks = (128,) if kernel == "esffn" else (128, 128)
        elif kernel == "esffn":
            blocks = (esffn_blocks(BLK, d1, d2, glu=False, x_itemsize=4,
                                   w_itemsize=4, has_b1=True, has_b2=True),)
        elif kernel == "esmm":
            blocks = esmm_blocks(BLK, d1, d2, x_itemsize=4, w_itemsize=4,
                                 has_bias=True)
        else:
            blocks = esfk_blocks(BLK, d1, d2, itemsize1=4, itemsize2=4)
        total += _steps(kernel, rows, d1, d2, blocks)
    if rule == "fixed_128":
        assert 200_000 < total < 210_000
    else:
        # one grid step per row block in every call
        assert total == 6 * (9 * 106 + 33) < 10_000


@pytest.mark.parametrize("footprint", [1, 20 * 2**20, 100 * 2**20])
def test_vmem_limit_covers_footprint(footprint):
    lim = tiles.vmem_limit(footprint)
    assert min(footprint, tiles.VMEM_BYTES) <= lim <= tiles.VMEM_BYTES


@pytest.mark.parametrize("shape,itemsize,want", [
    ((128, 384), 4, 128 * 384 * 4),
    ((1, 1536), 4, 8 * 1536 * 4),      # one row pads to 8 sublanes
    ((128, 1), 4, 128 * 128 * 4),      # one column pads to 128 lanes
    ((1, 768), 2, 16 * 768 * 2),       # bf16 packs 16 rows a tile
    ((16, 6), 4, 16 * 128 * 4),
])
def test_buffer_bytes_pads_to_tiles(shape, itemsize, want):
    assert tiles.buffer_bytes(shape, itemsize) == want
