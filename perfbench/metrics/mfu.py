"""Model FLOP/s utilization of the traced run: the FLOPs that forward
and backward need per token (``counts.train_flops_per_token``: no
recomputation, no embedding FLOPs, causal attention at half the
square), times tokens/s, over chips times the chip's bf16 peak."""

from perfbench import counts


def read(record):
    if record["peak"] is None:
        return None
    flops = counts.train_flops_per_token(record["config"],
                                         record["traffic"]["seq_len"])
    return 100.0 * flops * record["tokens_per_s"] / (
        record["chips"] * record["peak"]["bf16_flops_per_s"])
