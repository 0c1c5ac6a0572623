"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode (the CPU path of every other kernel test) checks values but
not Mosaic's rules: block shapes against the (8, 128) tiling, aligned
slices, VMEM limits. These tests lower each kernel with ``interpret=False``
against a described ``v5e:2x2`` topology and compile it with the TPU
compiler that ships with libtpu — no chip is needed and nothing runs.

Widths are published ones: qwen3-moe-30b-a3b (d_model 2048, 128 experts,
expert d_ff 768, 32/4 heads of 128), Swin-MoE-Small stages 3 and 4 (d 384
and 768, hidden 4d, 8 experts, float32 with biases) and the window
attention of its four stages at batch 64. The expert kernels run
at their default blocks (``kernels/tiles.py``), so these compiles check the
footprint of the whole-expert VMEM tiles against the described chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load libtpu at a time, and the
test workers must all collect the same tests.
"""
from __future__ import annotations

import importlib.machinery
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest

from repro.core.reindex import gather_rows
from repro.kernels.esffn import esffn_glu_pallas, esffn_mlp_pallas
from repro.kernels.esfk import esfk_pallas
from repro.kernels.esmm import esmm_pallas
from repro.kernels.ess import ess_pallas
from repro.kernels.estmm import estmm_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.window_attention import (
    window_attention_blocks,
    window_attn_bwd_pallas,
    window_attn_fwd_pallas,
    windows_per_tile,
)

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32

# qwen3-moe-30b-a3b: 2048 tokens routed top-8 into 4096 sorted rows here.
D, E, F, BLK = 2048, 128, 768, 128
N, NP = 2048, 4096
NB = NP // BLK
# Swin-MoE-Small stage 3 at batch 32: 14x14 tokens per image, top-1.
SD, SF, SE = 384, 1536, 8
SN = 196 * 32
SNP = (SN // BLK + SE) * BLK
SNB = SNP // BLK
# Swin-MoE-Small at batch 64: (d, hidden, sorted rows) of stages 3 and 4,
# rows = round_up(tokens + E * (BLK - 1), BLK) for 12,544 and 3,136 tokens.
SWIN_B64 = {"stage3": (384, 1536, 13568), "stage4": (768, 3072, 4224)}
# Swin-MoE-Small window attention at batch 64, one entry per stage:
# (windows, tokens per window, 3C, heads); head_dim 32.
SWIN_ATTN_B64 = {"stage1": (4096, 49, 288, 3), "stage2": (1024, 49, 576, 6),
                 "stage3": (256, 49, 1152, 12), "stage4": (64, 49, 2304, 24)}
# Paged decode: 8 slots, 16-token pages, 64 logical pages per slot.
B, HQ, HKV, HD, PAGE, NPAGES, MAXP = 8, 32, 4, 128, 16, 512, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # libtpu would otherwise log under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an abstract argument on the first chip."""
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    return compiled


def _route(spec, n, np_rows, nb):
    return spec((n,), I32), spec((np_rows,), I32), spec((np_rows,), F32), \
        spec((nb,), I32)


@pytest.mark.parametrize("case", ["glu_bf16", "glu_int8", "mlp_f32_bias"])
def test_esffn_compiles(spec, case):
    # As kernels.ops calls it: the sorted rows gathered just before the call.
    if case == "mlp_f32_bias":
        _, rt, rg, be = _route(spec, SN, SNP, SNB)
        _compile(
            lambda x, rt, rg, be, w1, b1, w2, b2: esffn_mlp_pallas(
                gather_rows(x, rt), rg, be, w1, b1, w2, b2, act="gelu",
                interpret=False),
            spec((SN, SD), F32), rt, rg, be, spec((SE, SD, SF), F32),
            spec((SE, SF), F32), spec((SE, SF, SD), F32), spec((SE, SD), F32))
        return
    _, rt, rg, be = _route(spec, N, NP, NB)
    x = spec((N, D), BF16)
    if case == "glu_bf16":
        _compile(
            lambda x, rt, rg, be, wg, wu, wd: esffn_glu_pallas(
                gather_rows(x, rt), rg, be, wg, wu, wd, interpret=False),
            x, rt, rg, be, spec((E, D, F), BF16), spec((E, D, F), BF16),
            spec((E, F, D), BF16))
        return
    up_s, down_s = spec((E, D // 128, F // 128), F32), \
        spec((E, F // 128, D // 128), F32)
    _compile(
        lambda x, rt, rg, be, wg, sg, wu, su, wd, sd: esffn_glu_pallas(
            gather_rows(x, rt), rg, be, wg, wu, wd, w_scales=(sg, su, sd),
            interpret=False),
        x, rt, rg, be, spec((E, D, F), I8), up_s, spec((E, D, F), I8), up_s,
        spec((E, F, D), I8), down_s)


@pytest.mark.parametrize(
    "case", ["bf16", "bf16_transposed", "f32_bias", "int8"])
def test_esmm_compiles(spec, case):
    if case == "f32_bias":
        _compile(
            lambda x, w, b, be: esmm_pallas(x, w, b, be, interpret=False),
            spec((SNP, SD), F32), spec((SE, SD, SF), F32),
            spec((SE, SF), F32), spec((SNB,), I32))
    elif case == "int8":
        _compile(
            lambda x, w, s, be: esmm_pallas(
                x, w, None, be, w_scales=s, interpret=False),
            spec((NP, D), BF16), spec((E, D, F), I8),
            spec((E, D // 128, F // 128), F32), spec((NB,), I32))
    else:
        tr = case == "bf16_transposed"
        _compile(
            lambda x, w, be: esmm_pallas(
                x, w, None, be, transpose_rhs=tr, interpret=False),
            spec((NP, F if tr else D), BF16),
            spec((E, D, F), BF16), spec((NB,), I32))


@pytest.mark.parametrize("case", ["qwen_bf16", "swin_f32"])
def test_esfk_compiles(spec, case):
    if case == "qwen_bf16":
        args = (spec((NP, D), BF16), spec((NP, F), BF16), spec((NB,), I32),
                spec((E,), I32))
    else:
        args = (spec((SNP, SD), F32), spec((SNP, SF), F32),
                spec((SNB,), I32), spec((SE,), I32))
    _compile(lambda a, b, be, c: esfk_pallas(a, b, be, c, interpret=False),
             *args)


@pytest.mark.parametrize("stage", sorted(SWIN_B64))
@pytest.mark.parametrize(
    "kernel", ["esffn_mlp", "esmm", "esmm_transposed", "esfk"])
def test_swin_batch64_compiles(spec, stage, kernel):
    d, f, rows = SWIN_B64[stage]
    be, w1, w2 = spec((rows // BLK,), I32), spec((SE, d, f), F32), \
        spec((SE, f, d), F32)
    if kernel == "esffn_mlp":
        _compile(
            lambda x, rg, be, w1, b1, w2, b2: esffn_mlp_pallas(
                x, rg, be, w1, b1, w2, b2, act="gelu", interpret=False),
            spec((rows, d), F32), spec((rows,), F32), be, w1,
            spec((SE, f), F32), w2, spec((SE, d), F32))
    elif kernel == "esmm":
        # z = xs @ w1 + b1 in the forward's recompute
        _compile(
            lambda x, w, b, be: esmm_pallas(x, w, b, be, interpret=False),
            spec((rows, d), F32), w1, spec((SE, f), F32), be)
    elif kernel == "esmm_transposed":
        # dX = dz @ w1^T
        _compile(
            lambda x, w, be: esmm_pallas(
                x, w, None, be, transpose_rhs=True, interpret=False),
            spec((rows, f), F32), w1, be)
    else:
        # (dW1, db1) from (xs, dz)
        _compile(
            lambda a, b, be, c: esfk_pallas(a, b, be, c, interpret=False),
            spec((rows, d), F32), spec((rows, f), F32), be,
            spec((SE,), I32))


def test_estmm_compiles(spec):
    _compile(lambda a, b, be, c: estmm_pallas(a, b, be, c, interpret=False),
             spec((NP, D), BF16), spec((NP, F), BF16), spec((NB,), I32),
             spec((E,), I32))


def test_ess_compiles(spec):
    _compile(lambda a, be, c: ess_pallas(a, be, c, interpret=False),
             spec((SNP, SF), F32), spec((SNB,), I32), spec((SE,), I32))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_attention_compiles(spec, kv):
    q = spec((B, 1, HQ, HD), BF16)
    pool = spec((NPAGES, PAGE, HKV, HD), BF16 if kv == "bf16" else I8)
    pt, ln = spec((B, MAXP), I32), spec((B,), I32)
    if kv == "bf16":
        _compile(
            lambda q, k, v, pt, ln: paged_attention_pallas(
                q, k, v, pt, ln, interpret=False),
            q, pool, pool, pt, ln)
        return
    sc = spec((NPAGES, PAGE, HKV), F32)
    _compile(
        lambda q, k, v, ks, vs, pt, ln: paged_attention_pallas(
            q, k, v, pt, ln, k_scale=ks, v_scale=vs, interpret=False),
        q, pool, pool, sc, sc, pt, ln)


def test_flash_attention_compiles(spec):
    x = spec((2 * HQ, 2048, HD), BF16)
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
             x, x, x)


@pytest.mark.parametrize("stage", sorted(SWIN_ATTN_B64))
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_window_attention_compiles(spec, stage, direction):
    nw, n, c3, heads = SWIN_ATTN_B64[stage]
    # As the model calls it: windows packed into score tiles of t rows.
    p = windows_per_tile(nw, n)
    nt, t = nw // p, p * n
    g = window_attention_blocks(nt, t, c3 // 3, heads, 4)
    kw = dict(heads=heads, n=t, g=g, interpret=False)
    qkv, bias = spec((nw * n, c3), F32), spec((heads, t, t), F32)
    if direction == "fwd":
        compiled = _compile(
            lambda qkv, b: window_attn_fwd_pallas(qkv, b, **kw), qkv, bias)
    else:
        compiled = _compile(
            lambda qkv, do, lse, b: window_attn_bwd_pallas(
                qkv, do, lse, b, **kw),
            qkv, spec((nw * n, c3 // 3), F32), spec((nt, heads, t), F32),
            bias)
    # The op the device trace shows carries the kernel's name, which the
    # expert kernels' roofline pattern must not count.
    calls = re.findall(r"%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                       compiled.as_text())
    assert calls and all(
        c.startswith(f"window_attn_{direction}_pallas") for c in calls)
    assert not any(_expert_pattern().search(c) for c in calls)


def _expert_pattern():
    """``PATTERN`` of the expert kernels' roofline metric, read from its
    file in the benchmark."""
    path = os.path.join(os.path.dirname(__file__), os.pardir, "bench",
                        "metrics", "moe_kernel_roofline.train.py")
    loader = importlib.machinery.SourceFileLoader("_roofline", path)
    mod = types.ModuleType(loader.name)
    loader.exec_module(mod)
    return mod.PATTERN


def test_expert_roofline_pattern_skips_window_attention():
    pattern = _expert_pattern()
    assert pattern.search("esfk_pallas.3")
    for fn in (window_attn_fwd_pallas, window_attn_bwd_pallas):
        assert not pattern.search(fn.__name__)
        assert not pattern.search(f"{fn.__name__}.12")
