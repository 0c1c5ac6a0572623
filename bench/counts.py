"""Operations and bytes that the algorithm needs, from shapes alone.

These are lower bounds of the work: routed rows and not padded rows, each
weight read once, recomputation not counted. A roofline share built on
them cannot pass 100% unless a kernel's device time leaves out work.
"""
from __future__ import annotations


def matmul_flops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def expert_ffn(rows: int, d: int, f: int, *, experts: int, glu: bool,
               w_bytes: int, a_bytes: int, bias: bool = False) -> dict:
    """Forward of one expert FFN layer over ``rows`` routed (token, expert)
    rows: FLOPs, and bytes of the weights of the experts that get a row
    (at most ``min(experts, rows)``), read once, plus the rows in and out."""
    mats = 3 if glu else 2
    flops = mats * matmul_flops(rows, d, f)
    touched = min(experts, rows)
    nbytes = touched * mats * d * f * w_bytes + 2 * rows * d * a_bytes
    if bias:
        nbytes += touched * (f + d) * w_bytes
    return {"flops": flops, "bytes": nbytes}


def expert_ffn_backward(rows: int, d: int, f: int, *, experts: int,
                        glu: bool, w_bytes: int, a_bytes: int,
                        bias: bool = False) -> dict:
    """Backward of one expert FFN layer: the input gradient and the weight
    gradient of each matrix (twice the forward's FLOPs); the weights read
    and their gradients written once, and the rows' activations and their
    gradients read and written once."""
    fwd = expert_ffn(rows, d, f, experts=experts, glu=glu, w_bytes=w_bytes,
                     a_bytes=a_bytes, bias=bias)
    mats = 3 if glu else 2
    touched = min(experts, rows)
    w = touched * mats * d * f * w_bytes
    hidden = rows * f * a_bytes * (2 if glu else 1)
    nbytes = 2 * w + 4 * rows * d * a_bytes + hidden
    return {"flops": 2 * fwd["flops"], "bytes": nbytes}


def paged_decode_attention(context: int, *, heads: int, kv_heads: int,
                           head_dim: int, kv_bytes: int) -> dict:
    """One slot's decode attention over ``context`` cached tokens in one
    layer: QK and PV FLOPs; the K and V rows read once."""
    flops = 2 * matmul_flops(heads, head_dim, context)
    nbytes = 2 * context * kv_heads * head_dim * kv_bytes
    return {"flops": flops, "bytes": nbytes}


def lm_token_flops(cfg: dict, context: int, *, head: bool) -> int:
    """Forward FLOPs of one token of a decoder with MoE FFNs at position
    ``context``: projections, routed experts only, causal attention over
    ``context`` earlier tokens, and the output head where ``head``."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    f = cfg["moe_intermediate_size"]
    per_layer = (matmul_flops(1, d, (hq + 2 * hkv) * hd)
                 + matmul_flops(1, hq * hd, d)
                 + 2 * matmul_flops(hq, hd, context)
                 + matmul_flops(1, d, e)
                 + k * 3 * matmul_flops(1, d, f))
    flops = cfg["num_hidden_layers"] * per_layer
    if head:
        flops += matmul_flops(1, d, cfg["vocab_size"])
    return flops


def swin_forward_flops(cfg: dict) -> int:
    """Forward FLOPs of one image through a Swin-MoE with top-k routing:
    patch embedding, window attention, routed expert (or dense) MLPs,
    routers, patch merging and the head."""
    size = cfg["img_size"] // cfg["patch_size"]
    flops = matmul_flops(size * size, cfg["patch_size"] ** 2
                         * cfg["in_chans"], cfg["dims"][0])
    win = cfg["window"]
    for s, depth in enumerate(cfg["depths"]):
        c = cfg["dims"][s]
        tokens = size * size
        w = min(win, size)
        hidden = int(cfg["mlp_ratio"] * c)
        for b in range(depth):
            flops += matmul_flops(tokens, c, 3 * c)          # qkv
            flops += 2 * matmul_flops(tokens, c, w * w)      # QK^T, AV
            flops += matmul_flops(tokens, c, c)              # proj
            moe = s in cfg["moe_stages"] and b % 2 == 1
            k = cfg["top_k"] if moe else 1
            flops += k * 2 * matmul_flops(tokens, c, hidden)
            if moe:
                flops += matmul_flops(tokens, c, cfg["num_experts"])
        if s < len(cfg["depths"]) - 1:
            flops += matmul_flops(tokens // 4, 4 * c, 2 * c)
            size //= 2
    flops += matmul_flops(1, cfg["dims"][-1], cfg["num_classes"])
    return flops
