"""Operations and bytes that the model and its kernels need.

These are the benchmark's own counts, kept apart from the program's
cost formulas so that no change to the program can move them.  They
count the work the algorithm needs, not what a kernel happens to do:

- matmuls count 2 FLOPs per multiply-add;
- causal attention counts half of the score square;
- the input embedding is a gather and counts no FLOPs;
- training counts the forward pass three times (forward, and the two
  matmuls of the backward pass per forward matmul); recomputation under
  rematerialization counts nothing;
- norms, softmax and elementwise work count nothing (they are a few
  FLOPs per element against hundreds per matmul output).

The model is described by its configuration file's keys (Hugging Face
``config.json`` names).
"""

from __future__ import annotations


def dims(model: dict) -> dict:
    """The sizes the counts need, from a configuration file's keys."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return {
        "d": d, "h": h, "kv": model["num_key_value_heads"],
        "hd": model.get("head_dim") or d // h,
        "ff": model["intermediate_size"], "vocab": model["vocab_size"],
        "layers": model["num_hidden_layers"],
    }


def layer_matmul_params(model: dict) -> int:
    """Weights of one decoder layer's matmuls: q, k, v, o and a SwiGLU
    MLP (gate, up, down)."""
    m = dims(model)
    d, qd, kvd = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"]
    return d * qd + 2 * d * kvd + qd * d + 3 * d * m["ff"]


def attention_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward FLOPs of causal attention per token and layer: the
    scores and the weighted sum over half of the square, averaged over
    the sequence (2 * 2 * S^2/2 * hd * H over S tokens)."""
    m = dims(model)
    return 2.0 * seq_len * m["hd"] * m["h"]


def forward_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward FLOPs per token: every layer's matmuls and causal
    attention, and the output head.  The embedding gather counts 0."""
    m = dims(model)
    per_layer = 2.0 * layer_matmul_params(model) + \
        attention_flops_per_token(model, seq_len)
    return m["layers"] * per_layer + 2.0 * m["d"] * m["vocab"]


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """FLOPs that one training token needs: forward and backward,
    three times the forward pass, with nothing counted for
    recomputation."""
    return 3.0 * forward_flops_per_token(model, seq_len)


def attention_fwd_work(model: dict, batch: int, seq_len: int,
                       bytes_per_elem: int = 2) -> dict:
    """FLOPs and HBM bytes of one causal attention forward call over a
    whole batch: scores and weighted sum over half of the square at H
    heads; q and o read and written at H heads, k and v read at the KV
    head count, each element once, in the served type (bf16)."""
    m = dims(model)
    return attention_work(batch, m["h"], m["kv"], seq_len, m["hd"],
                          bytes_per_elem)


def attention_work(batch: int, heads: float, kv_heads: float, seq_len: int,
                   head_dim: int, bytes_per_elem: int = 2) -> dict:
    """FLOPs and bytes of causal attention over ``batch`` sequences at
    ``heads`` query heads and ``kv_heads`` KV heads (see
    :func:`attention_fwd_work`)."""
    flops = 2.0 * batch * heads * seq_len * seq_len * head_dim
    elems = batch * seq_len * head_dim * (2 * heads + 2 * kv_heads)
    return {"flops": flops, "bytes": float(elems * bytes_per_elem)}


def roofline_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """The least time ``work`` takes on one chip, and which bound sets
    it (``flops`` or ``bytes``)."""
    t_f = work["flops"] / peak["bf16_flops_per_s"]
    t_b = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
