"""Phi-3-mini 3.8B [hf:microsoft/Phi-3-mini-4k-instruct; arXiv:2404.14219]
— dense, RoPE SwiGLU, multi-head attention (32 query and 32 KV heads of
96), 4096-token context, rope_theta 10000.

The checkpoint's fused ``qkv_proj`` and ``gate_up_proj`` are
concatenations of the separate q/k/v and gate/up projections held here.
Its config.json also sets ``sliding_window`` 2047, which this config
does not run (full causal attention): at 2048 tokens or fewer the two
differ by at most the one pair of the last query and the first key.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="phi3_mini", family="dense", num_layers=32, d_model=3072,
    num_heads=32, num_kv_heads=32, d_ff=8192, vocab_size=32064,
    head_dim=96, mlp="swiglu", rope_theta=10000.0,
    source="hf:microsoft/Phi-3-mini-4k-instruct; arXiv:2404.14219",
)
