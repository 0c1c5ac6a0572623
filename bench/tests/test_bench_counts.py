"""FLOP and byte counters and the peak table, against hand calculations at
tiny shapes."""
import pytest

from bench import counts, peaks


def test_matmul_and_expert_ffn():
    assert counts.matmul_flops(2, 3, 4) == 48
    # 3 routed rows, GLU: 3 matrices of 2*3*4*5 FLOPs; 3 of 8 experts
    # touched, 3*4*5 bf16 weights each per matrix; rows in and out.
    assert counts.expert_ffn(3, 4, 5, experts=8, glu=True, w_bytes=2,
                             a_bytes=2) == {"flops": 360, "bytes": 408}
    # 10 rows over 2 experts, 2 matrices with biases, float32:
    # weights 2*2*4*5*4 = 320, rows 2*10*4*4 = 320, biases 2*(5+4)*4 = 72.
    fwd = counts.expert_ffn(10, 4, 5, experts=2, glu=False, w_bytes=4,
                            a_bytes=4, bias=True)
    assert fwd == {"flops": 800, "bytes": 712}
    # Backward: twice the FLOPs; weights read and gradients written
    # (640), rows' activations and gradients (4*10*4*4 = 640), the
    # hidden activations (10*5*4 = 200).
    bwd = counts.expert_ffn_backward(10, 4, 5, experts=2, glu=False,
                                     w_bytes=4, a_bytes=4, bias=True)
    assert bwd == {"flops": 1600, "bytes": 1480}


def test_paged_decode_attention():
    n = counts.paged_decode_attention(10, heads=4, kv_heads=2, head_dim=8,
                                      kv_bytes=2)
    # QK and PV: 2 * (2*4*8*10); K and V rows: 2*10*2*8*2.
    assert n == {"flops": 1280, "bytes": 640}


def test_lm_token_flops():
    cfg = {"hidden_size": 4, "head_dim": 2, "num_attention_heads": 2,
           "num_key_value_heads": 1, "num_experts": 4,
           "num_experts_per_tok": 2, "moe_intermediate_size": 3,
           "num_hidden_layers": 1, "vocab_size": 10}
    # qkv 2*4*8 = 64, out 2*4*4 = 32, attention 2*(2*2*2*5) = 80, router
    # 2*4*4 = 32, experts 2*3*(2*4*3) = 144; head 2*4*10 = 80.
    assert counts.lm_token_flops(cfg, 5, head=False) == 352
    assert counts.lm_token_flops(cfg, 5, head=True) == 432


@pytest.mark.parametrize("moe,want", [(False, 1816), (True, 3704)])
def test_swin_forward_flops(moe, want):
    cfg = {"img_size": 8, "patch_size": 4, "in_chans": 1, "dims": [4],
           "depths": [2 if moe else 1], "window": 2, "mlp_ratio": 2.0,
           "num_classes": 3, "moe_stages": [0] if moe else [],
           "top_k": 2, "num_experts": 3}
    # 4 tokens of width 4. Patch embedding 2*4*16*4 = 512; a block: qkv
    # 384, attention 256, projection 128, MLP 512; head 24. The MoE block
    # runs its MLP twice (top-2) and a router of 2*4*4*3 = 96.
    assert counts.swin_forward_flops(cfg) == want


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.least_time_s(1.0, 1.0, "cpu")


def test_least_time_is_the_larger_bound():
    # 197 GFLOP at 197 TFLOP/s is 1 ms; 819 MB at 819 GB/s is 1 ms.
    assert peaks.least_time_s(197e9, 1.0, "TPU v5 lite") == pytest.approx(1e-3)
    assert peaks.least_time_s(1.0, 1638e6, "TPU v5 lite") == pytest.approx(2e-3)
