"""FLOP and byte formulas on hand-worked shapes."""
import dataclasses
import importlib

import pytest

from bench import counts, harness
from bench.peaks import PEAKS, UnknownDevice, peaks_of

FLOWFORMER = {"n_layers": 6, "d_model": 512, "n_heads": 8, "n_kv_heads": 8,
              "d_ff": 2048, "vocab_size": 32768, "act": "gelu",
              "attention": {"chunk_size": 128}}
GRANITE = {"n_layers": 9, "d_model": 4096, "n_heads": 32, "n_kv_heads": 8,
           "d_ff": 14336, "vocab_size": 49152, "act": "swiglu",
           "attention": {"chunk_size": 128}}
# DeepSeek-V2-Lite, one chip's share of an 8-chip expert-parallel layer:
# 1 dense + 5 expert layers, 8 of 64 experts held, vocab 12800 of 102400
DEEPSEEK_SHARE = {
    "n_layers": 6, "n_dense_layers": 1, "d_model": 2048, "n_heads": 16,
    "n_kv_heads": 16, "d_ff": 10944, "vocab_size": 12800, "act": "swiglu",
    "attention": {"chunk_size": 128},
    "mla": {"kv_lora_rank": 512, "q_lora_rank": 0, "rope_head_dim": 64,
            "nope_head_dim": 128, "v_head_dim": 128},
    "moe": {"n_experts": 8, "router_width": 64, "top_k": 6, "n_shared": 2,
            "d_ff_expert": 1408}}


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
    assert counts.layer_params(FLOWFORMER, 0) == 3_145_728
    weights = 6 * 3_145_728 + 16_777_216
    attn = 8 * counts.flow_chunk_flops(1, 128, 64, 64) / 128
    assert counts.train_token_flops(FLOWFORMER) == pytest.approx(
        6 * weights + 3 * 6 * attn)
    # granite: 2*4096*4096 + 2*4096*1024 + 3*4096*14336 per layer
    assert counts.layer_params(GRANITE, 8) == 218_103_808
    assert counts.decode_token_flops(GRANITE) == pytest.approx(
        9 * (2 * 218_103_808 + counts.flow_decode(8, 4, 128, 128)[0])
        + 2 * 4096 * 49152)
    assert counts.prefill_flops(GRANITE, 1000, 2) == pytest.approx(
        1000 * 9 * (2 * 218_103_808 + counts.attn_flops_per_token(GRANITE))
        + 2 * 2 * 4096 * 49152)


def test_existing_cell_counts_unchanged():
    model = harness.load_json(harness.BENCH / "configs"
                              / "flowformer-lm-xla.json")["model"]
    assert counts.train_token_flops(model) == 220987392.0


def test_deepseek_v2_lite_share_by_hand():
    m = DEEPSEEK_SHARE
    assert counts.attn_dims(m) == (16, 1, 192, 128)
    # wq 2048*16*192 + kv_down 2048*576 + kv_up 512*16*256 + wo 16*128*2048
    attn = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    assert counts.attn_params(m) == attn == 13_762_560
    # router 2048*64; 2 shared + 6*8/64 routed experts of 3*2048*1408
    assert counts.layer_params(m, 1) == attn + 131_072 + 2.75 * 8_650_752
    assert counts.layer_params(m, 5) == 37_683_200
    # the leading dense layer: 3*2048*10944
    assert counts.layer_params(m, 0) == attn + 67_239_936 == 81_002_496
    assert counts.head_params(m) == 26_214_400
    # 16 heads x (2*128^2*192 + 2*128^2*128 + 2*2*128*192*128) / 128
    assert counts.attn_flops_per_token(m) == 2_883_584
    assert counts.train_token_flops(m) == (
        6 * (81_002_496 + 5 * 37_683_200 + 26_214_400) + 3 * 6 * 2_883_584)
    assert counts.train_token_flops(m) == 1_825_701_888


@pytest.mark.parametrize("preset", ["deepseek_v2_lite_16b",
                                    "granite_moe_3b_a800m"])
def test_expert_counts_match_program_weights(preset):
    """With every held expert reached, the weights a token passes are the
    program's projection weights (every ``w`` leaf) and the head.  A token
    reaches every held expert where it picks as many experts as the router
    scores (``router_width``, absent or 0: ``n_experts``); ``top_k``
    changes no weight, so it is set on the counted side alone."""
    import jax

    from repro.models import lm

    cfg = importlib.import_module(f"repro.configs.{preset}").smoke_config()
    shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    w = sum(x.size for path, x in leaves if path[-1].key == "w")
    model = dataclasses.asdict(cfg)
    moe = model["moe"]
    moe["top_k"] = moe.get("router_width") or moe["n_experts"]
    assert w + shapes["head"]["table"].size == (
        counts.layers_params(model) + counts.head_params(model))


def test_least_time_and_peaks():
    p = peaks_of("TPU v5 lite")
    t, bound = counts.least_time(197e12, 1.0, p)
    assert (t, bound) == (1.0, "compute")
    t, bound = counts.least_time(1.0, 819e9, p)
    assert (t, bound) == (1.0, "memory")
    assert set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(UnknownDevice):
        peaks_of("TPU v9 imaginary")
