"""FLOP and byte counts against a hand count of one small layer."""
from chipbench import flops

# one layer: d=8, 2 heads of 4, 1 KV head, MLP width 16, vocab 10
M = {"model_type": "llama", "d_model": 8, "n_layers": 1, "n_heads": 2,
     "n_kv": 1, "head_dim": 4, "d_ff": 16, "vocab": 10}


def test_matmuls_of_one_token():
    # q 8x8, k 8x4, v 8x4, o 8x8, MLP 3 x 8x16: 64+32+32+64+384 = 576 MACs
    assert flops.matmul_per_token(M) == 2 * 576


def test_causal_attention_pairs():
    # 3 queries after 5 cached keys see 6, 7 and 8 keys
    assert flops.attention_pairs(3, 5) == 6 + 7 + 8
    # QK^T and PV: 2 heads x 4 dims x 2 matmuls x 2 FLOPs per pair
    assert flops.attention(M, 3, 5) == 21 * 2 * 4 * 2 * 2


def test_prefill_and_decode():
    lm = 2 * 8 * 10
    assert flops.prefill(M, 3, 5) == 3 * 1152 + 21 * 32 + lm
    assert flops.decode(M, 7) == 1152 + 8 * 32 + lm


def test_flash_call_bytes():
    f, b = flops.flash_call(M, 3, 5)
    assert f == flops.attention(M, 3, 5)
    # Q and O: 3 tokens x 2 heads x 4; K and V: 8 tokens x 1 head x 4
    assert b == 2 * (2 * 3 * 2 * 4 + 2 * 8 * 1 * 4)


def test_smollm_weights_match_parameter_count():
    import json

    from chipbench import model
    from chipbench.spec import HERE
    m = model.dims(json.loads(
        (HERE / "configs" / "smollm-360m.json").read_text()))
    # per-token matmuls are twice the layer parameters (heads unpadded):
    # q and o 960 x 960, k and v 960 x 320, MLP 3 x 960 x 2560
    layer = 960 * 960 * 2 + 2 * 960 * 320 + 3 * 960 * 2560
    assert flops.matmul_per_token(m) == 2 * 32 * layer
    # with the tied embedding, the published 3.62e8 parameters
    assert abs(32 * layer + 49152 * 960 - 3.62e8) < 0.01e8


def test_a_config_the_program_cannot_build_is_refused():
    import json

    import pytest

    from chipbench import model
    from chipbench.spec import HERE
    conf = json.loads((HERE / "configs" / "smollm-360m.json").read_text())
    for k, v in [("hidden_act", "gelu_pytorch_tanh"),
                 ("norm_type", "layer_norm"), ("use_bias", True),
                 ("mlp_type", "default"), ("attention_bias", True),
                 ("rope_scaling", {"type": "linear", "factor": 4.0}),
                 ("sliding_window", 4096), ("model_type", "starcoder2")]:
        with pytest.raises(ValueError):
            model.dims(dict(conf, **{k: v}))
