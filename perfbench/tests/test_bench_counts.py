"""The benchmark's FLOP and byte counts against hand counts."""

import pytest

from perfbench import catalog, counts, peaks


# Phi-3-mini-4k's sizes (microsoft/Phi-3-mini-4k-instruct) at 16 of
# its 32 layers: multi-head attention, as many KV heads as query heads
PHI3 = {"hidden_size": 3072, "num_attention_heads": 32,
        "num_key_value_heads": 32, "intermediate_size": 8192,
        "vocab_size": 32064, "num_hidden_layers": 16}


def conf(name):
    return catalog.load_json(catalog.HERE / "configs" / f"{name}.json")


def test_qwen2_train_flops_per_token_by_hand():
    # per layer: q 896*896, k and v 2*896*128, o 896*896, MLP 3*896*4864
    per_layer = 896 * 896 * 2 + 2 * 896 * 128 + 3 * 896 * 4864
    assert counts.layer_matmul_params(conf("qwen2_05b")) == per_layer
    attn = 2 * 1024 * 64 * 14            # half of the square, per token
    head = 2 * 896 * 151936
    fwd = 24 * (2 * per_layer + attn) + head
    got = counts.train_flops_per_token(conf("qwen2_05b"), 1024)
    assert got == 3 * fwd
    assert got == pytest.approx(3.096e9, rel=1e-3)


def test_phi3_train_flops_per_token_by_hand():
    per_layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    fwd = 16 * (2 * per_layer + 2 * 4096 * 96 * 32) + 2 * 3072 * 32064
    assert counts.train_flops_per_token(PHI3, 4096) == 3 * fwd
    assert 3 * fwd == pytest.approx(12.67e9, rel=1e-3)


def test_attention_work_counts_half_square_and_kv_heads():
    # qwen2 at 8 x 1024: 14 query heads, 2 KV heads, head size 64
    w = counts.attention_fwd_work(conf("qwen2_05b"), 8, 1024)
    assert w["flops"] == 2 * 8 * 14 * 1024 * 1024 * 64
    assert w["bytes"] == 2 * 8 * 1024 * 64 * (2 * 14 + 2 * 2)
    # phi3: as many KV heads as query heads
    w = counts.attention_fwd_work(PHI3, 1, 4096)
    assert w["bytes"] == 2 * 4096 * 96 * (4 * 32)


def test_roofline_names_its_bound_and_peaks_refuse_unknown_kinds():
    v5e = peaks.peaks_for("TPU v5 lite")
    t, bound = counts.roofline_seconds({"flops": 197e12, "bytes": 1.0}, v5e)
    assert (t, bound) == (pytest.approx(1.0), "flops")
    t, bound = counts.roofline_seconds({"flops": 1.0, "bytes": 819e9}, v5e)
    assert (t, bound) == (pytest.approx(1.0), "bytes")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
