"""FLOP and byte formulas on hand-worked shapes."""
import pytest

from bench import counts
from bench.peaks import PEAKS, UnknownDevice, peaks_of

FLOWFORMER = {"n_layers": 6, "d_model": 512, "n_heads": 8, "n_kv_heads": 8,
              "d_ff": 2048, "vocab_size": 32768, "act": "gelu",
              "attention": {"chunk_size": 128}}
GRANITE = {"n_layers": 9, "d_model": 4096, "n_heads": 32, "n_kv_heads": 8,
           "d_ff": 14336, "vocab_size": 49152, "act": "swiglu",
           "attention": {"chunk_size": 128}}


def test_chunk_flops_by_hand():
    # g=1, c=2, d=dv=1: scores 2*1*4*1, intra 2*1*4*1, inter 2*1*2*1*1,
    # update 2*2*1*1
    assert counts.flow_chunk_flops(1, 2, 1, 1) == 8 + 8 + 4 + 4


def test_fused_forward_by_hand():
    flops, nbytes = counts.flow_fused_fwd(bh=2, g=1, n=4, d=1, dv=1, chunk=2)
    assert flops == 2 * 2 * 24
    # per row: q,k,v,out 4 positions x 1 x 2 bytes = 32; state (4+1+1)*4
    assert nbytes == 2 * (32 + 24 + 4)


def test_decode_by_hand():
    flops, nbytes = counts.flow_decode(bh=3, g=2, d=2, dv=2)
    assert flops == 3 * (2 * 4 + 2 * 2 * 4 + 8 * 2 * 2)
    state = (4 * 2 + 1 + 4) * 4
    io = (4 + 2 + 2 + 4) * 2
    assert nbytes == 3 * (2 * state + io + 4)


def test_backward_is_twice_the_forward_products():
    f, _ = counts.flow_fused_fwd(16, 4, 2048, 128, 128, 128)
    b, _ = counts.flow_fused_bwd(16, 4, 2048, 128, 128, 128)
    assert b == 2 * f


def test_model_flops():
    # flowformer: per layer 4*512^2 + 2*512*2048 = 3,145,728 weights;
    # head 512 * 32768 = 16,777,216
    assert counts.layer_matmul_params(FLOWFORMER) == 3_145_728
    weights = 6 * 3_145_728 + 16_777_216
    attn = 8 * counts.flow_chunk_flops(1, 128, 64, 64) / 128
    assert counts.train_token_flops(FLOWFORMER) == pytest.approx(
        6 * weights + 3 * 6 * attn)
    # granite: 2*4096*4096 + 2*4096*1024 + 3*4096*14336 per layer
    assert counts.layer_matmul_params(GRANITE) == 218_103_808
    assert counts.decode_token_flops(GRANITE) == pytest.approx(
        9 * (2 * 218_103_808 + counts.flow_decode(8, 4, 128, 128)[0])
        + 2 * 4096 * 49152)
    assert counts.prefill_flops(GRANITE, 1000, 2) == pytest.approx(
        1000 * 9 * (2 * 218_103_808 + counts.attn_flops_per_token(GRANITE))
        + 2 * 2 * 4096 * 49152)


def test_least_time_and_peaks():
    p = peaks_of("TPU v5 lite")
    t, bound = counts.least_time(197e12, 1.0, p)
    assert (t, bound) == (1.0, "compute")
    t, bound = counts.least_time(1.0, 819e9, p)
    assert (t, bound) == (1.0, "memory")
    assert set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(UnknownDevice):
        peaks_of("TPU v9 imaginary")
