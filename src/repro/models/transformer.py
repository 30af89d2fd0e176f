"""Unified model stack covering all assigned architectures.

A model is ``init_params(cfg, key)`` + ``forward(cfg, params, ...)`` +
``init_cache``/``decode_step`` — pure functions over pytrees.

Depth is executed as ``jax.lax.scan`` over *super-blocks*: the layer
pattern's period (1 for homogeneous stacks, 3 for RecurrentGemma's
rglru/rglru/local, 8 for xLSTM's 7:1 mix) defines one super-block whose
parameters are stacked ``num_layers // period`` deep.  This keeps the
jaxpr/HLO O(1) in depth — llama3-405B's 126 layers lower as fast as 2 —
and is the structural analogue of the paper's §4.4 repeated-layer
grouping: the NDA sees each layer kind exactly once and its sharding
decisions apply to every repetition.  Left-over layers (num_layers mod
period) run unscanned as the ``tail``.

Modality frontends are stubs per the assignment: VLM configs take
precomputed patch embeddings, the audio encoder takes precomputed frame
embeddings (``input_specs`` provides them).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.sharding import constrain


def block_kinds(cfg) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """(period kinds, tail kinds)."""
    pattern = cfg.pattern
    period = len(cfg.block_pattern) or 1
    n_scan = cfg.num_layers // period
    return pattern[:period], pattern[n_scan * period:]


def n_scan_blocks(cfg) -> int:
    period = len(cfg.block_pattern) or 1
    return cfg.num_layers // period


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_block(cfg, kind, key, *, decoder_cross=False):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {}
    if kind in ("attn", "local"):
        p["mix"] = L.init_attn(cfg, k1)
    elif kind == "rglru":
        p["mix"] = L.init_rglru(cfg, k1)
    elif kind == "mlstm":
        p["mix"] = L.init_mlstm(cfg, k1)
    elif kind == "slstm":
        p["mix"] = L.init_slstm(cfg, k1)
    else:
        raise ValueError(kind)
    if decoder_cross:
        p["cross"] = L.init_attn(cfg, k3)
    if cfg.d_ff > 0:
        if cfg.num_experts and kind in ("attn", "local"):
            p["ffn"] = L.init_moe(cfg, k2)
        else:
            p["ffn"] = L.init_mlp(cfg, k2)
    return p


def _stacked(cfg, kind, key, n, **kw):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_block(cfg, kind, k, **kw))(keys)


def init_params(cfg, key):
    d, v = cfg.d_model, cfg.vocab_size
    period_kinds, tail_kinds = block_kinds(cfg)
    n_scan = n_scan_blocks(cfg)
    ks = iter(jax.random.split(key,
                               6 + len(period_kinds) + len(tail_kinds)))
    cross = cfg.is_encoder_decoder
    params = {
        "embed": L._dense_init(next(ks), (v, d), cfg.dtype, scale=1.0),
        "layers": tuple(_stacked(cfg, kind, next(ks), n_scan,
                                 decoder_cross=cross)
                        for kind in period_kinds),
        "tail": tuple(init_block(cfg, kind, next(ks), decoder_cross=cross)
                      for kind in tail_kinds),
        "final_ln": jnp.ones((d,), cfg.dtype),
        "unembed": L._dense_init(next(ks), (d, v), cfg.dtype),
    }
    if cfg.is_encoder_decoder:
        params["enc_layers"] = _stacked(cfg, "attn", next(ks),
                                        cfg.encoder_layers)
        params["enc_ln"] = jnp.ones((d,), cfg.dtype)
    return params


def param_specs(cfg):
    """Parameter ShapeDtypeStructs without allocating (for the dry-run)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def param_logical_axes(cfg, params):
    """Logical dim names for every param leaf (for TOAST's logical
    projection and the manual baseline).  Disambiguates key collisions
    (attention ``wo`` vs MLP ``wo``) by the parent block key, and places
    the ``experts`` name on MoE-stacked dims only."""

    def names(path, leaf):
        keys = [p.key if hasattr(p, "key") else str(p) for p in path]
        key = keys[-1]
        parent = next((k for k in reversed(keys[:-1])
                       if k in ("mix", "ffn", "cross")), "")
        e = cfg.num_experts
        base = None
        if key == "embed":
            base = ("vocab", "embed")
        elif key == "unembed":
            base = ("embed", "vocab")
        elif key == "wq" or (key == "W" and parent == "mix"):
            base = ("embed", "heads")
        elif key in ("wk", "wv"):
            base = ("embed", "kv_heads")
        elif key == "R":
            base = ("heads", None, None)
        elif key in ("wx", "wy"):
            base = ("embed", "rnn")
        elif key in ("ga_w", "ga_b", "gi_w", "gi_b", "lam", "conv_b"):
            base = ("rnn",)
        elif key == "conv_w":
            base = (None, "rnn")
        elif key in ("wi", "wf") and parent == "mix":   # mLSTM gates
            base = ("embed", "heads")
        elif key == "wo" and parent == "mix":
            rnn_w = (cfg.d_model * 3) // 2
            base = ("rnn", "embed") if leaf.shape[-2] == rnn_w else \
                ("heads", "embed")
        elif key == "wg" and e and leaf.shape[-1] == e:
            base = ("embed", "experts")                  # MoE router
        elif key in ("wi", "wg", "wgate", "dense_wi", "dense_wg"):
            base = ("embed", "hidden")
        elif key in ("wo", "dense_wo"):
            base = ("hidden", "embed")
        if base is None:
            return (None,) * leaf.ndim
        # MoE expert stacking: put "experts" on the expert-count dim
        extra = leaf.ndim - len(base)
        prefix = [None] * extra
        if e and extra >= 1 and key in ("wi", "wgate", "wo") and \
                parent == "ffn":
            for i in range(extra):
                if leaf.shape[i] == e and (extra == 1 or i > 0):
                    prefix[i] = "experts"
                    break
        if extra < 0:
            return tuple(base[-leaf.ndim:])
        return tuple(prefix) + base

    return jax.tree_util.tree_map_with_path(names, params)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def apply_block(cfg, kind, p, x, positions, *, causal=True, enc_out=None):
    if kind == "attn":
        x = L.attn_apply(cfg, p["mix"], x, positions,
                         window=cfg.sliding_window, is_causal=causal)
    elif kind == "local":
        x = L.attn_apply(cfg, p["mix"], x, positions,
                         window=cfg.local_window, is_causal=causal)
    elif kind == "rglru":
        x = L.rglru_apply(cfg, p["mix"], x)
    elif kind == "mlstm":
        x = L.mlstm_apply(cfg, p["mix"], x)
    elif kind == "slstm":
        x = L.slstm_apply(cfg, p["mix"], x)
    if "cross" in p and enc_out is not None:
        x = L.attn_apply(cfg, p["cross"], x, positions, enc_out=enc_out)
    if "ffn" in p:
        if cfg.num_experts and kind in ("attn", "local"):
            x = L.moe_apply(cfg, p["ffn"], x)
        else:
            x = L.mlp_apply(cfg, p["ffn"], x)
    return x


def _run_layers(cfg, params, h, positions, *, causal=True, enc_out=None):
    period_kinds, tail_kinds = block_kinds(cfg)

    def super_block(h, pslices):
        for kind, p in zip(period_kinds, pslices):
            h = apply_block(cfg, kind, p, h, positions, causal=causal,
                            enc_out=enc_out)
        h = constrain(h, ("act_batch", "seq", "embed"))
        return h

    body = super_block
    if cfg.remat:
        if cfg.remat_policy == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_saveable)
        else:
            body = jax.checkpoint(body)

    if n_scan_blocks(cfg) > 0 and params["layers"]:
        h, _ = jax.lax.scan(lambda c, xs: (body(c, xs), None),
                            h, params["layers"])
    for kind, p in zip(tail_kinds, params["tail"]):
        h = apply_block(cfg, kind, p, h, positions, causal=causal,
                        enc_out=enc_out)
    return h


def encode(cfg, params, frames):
    """Audio/vision encoder over precomputed frame embeddings (stub
    frontend per assignment)."""
    B, S, _ = frames.shape
    positions = jnp.arange(S)[None, :]
    h = frames.astype(cfg.dtype)

    def enc_block(h, p):
        h = L.attn_apply(cfg, p["mix"], h, positions, is_causal=False)
        h = L.mlp_apply(cfg, p["ffn"], h)
        return h

    body = jax.checkpoint(enc_block) if cfg.remat else enc_block
    h, _ = jax.lax.scan(lambda c, xs: (body(c, xs), None),
                        h, params["enc_layers"])
    return L.rmsnorm(h, params["enc_ln"])


def embed_tokens(cfg, params, tokens):
    h = jnp.take(params["embed"], tokens, axis=0)
    return h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)


def forward(cfg, params, tokens, *, patch_embeds=None, frames=None):
    """Logits for a full sequence (train / prefill).

    tokens: (B, S) int32.  patch_embeds: (B, P, D) for vlm.  frames:
    (B, S_enc, D) for encoder-decoder audio models.
    """
    enc_out = encode(cfg, params, frames) if frames is not None else None
    h = embed_tokens(cfg, params, tokens)
    if patch_embeds is not None:
        h = jnp.concatenate([patch_embeds.astype(h.dtype), h], axis=1)
    h = constrain(h, ("act_batch", "seq", "embed"))
    S = h.shape[1]
    positions = jnp.arange(S)[None, :]
    h = _run_layers(cfg, params, h, positions, enc_out=enc_out)
    with jax.named_scope("head_loss"):
        h = L.rmsnorm(h, params["final_ln"])
        logits = h @ params["unembed"]
        if cfg.logits_vocab_shard:
            # an axis shards one dim per tensor: prefer vocab over seq
            # here — CE then reduces over the sharded vocab locally (small
            # all-reduce) instead of materialising seq-sharded fp32 logits
            # + a vocab all-gather in the backward pass.
            return constrain(logits, ("act_batch", None, "vocab"))
        return constrain(logits, ("act_batch", "seq", "vocab"))


# ---------------------------------------------------------------------------
# decode (KV / recurrent caches)
# ---------------------------------------------------------------------------


def _block_cache(cfg, kind, batch, max_seq):
    if kind == "attn":
        return L.attn_init_cache(cfg, batch, max_seq, cfg.sliding_window)
    if kind == "local":
        return L.attn_init_cache(cfg, batch, max_seq, cfg.local_window)
    if kind == "rglru":
        return L.rglru_init_cache(cfg, batch)
    if kind == "mlstm":
        return L.mlstm_init_cache(cfg, batch)
    if kind == "slstm":
        return L.slstm_init_cache(cfg, batch)
    raise ValueError(kind)


def init_cache(cfg, batch, max_seq):
    period_kinds, tail_kinds = block_kinds(cfg)
    n_scan = n_scan_blocks(cfg)

    def stack(tree, n):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)

    return {
        "layers": tuple(stack(_block_cache(cfg, kind, batch, max_seq), n_scan)
                        for kind in period_kinds),
        "tail": tuple(_block_cache(cfg, kind, batch, max_seq)
                      for kind in tail_kinds),
    }


def decode_block(cfg, kind, p, x, cache, pos, *, enc_out=None):
    if kind == "attn":
        x, cache = L.attn_decode(cfg, p["mix"], x, cache, pos,
                                 window=cfg.sliding_window)
    elif kind == "local":
        x, cache = L.attn_decode(cfg, p["mix"], x, cache, pos,
                                 window=cfg.local_window)
    elif kind == "rglru":
        x, cache = L.rglru_decode(cfg, p["mix"], x, cache, pos)
    elif kind == "mlstm":
        x, cache = L.mlstm_decode(cfg, p["mix"], x, cache, pos)
    elif kind == "slstm":
        x, cache = L.slstm_decode(cfg, p["mix"], x, cache, pos)
    if "cross" in p and enc_out is not None:
        x, _ = L.attn_decode(cfg, p["cross"], x, None, pos, enc_out=enc_out)
    if "ffn" in p:
        if cfg.num_experts and kind in ("attn", "local"):
            x = L.moe_apply(cfg, p["ffn"], x)
        else:
            x = L.mlp_apply(cfg, p["ffn"], x)
    return x, cache


def decode_step(cfg, params, cache, token, pos, *, enc_out=None):
    """One autoregressive step.  token: (B, 1) int32; pos: scalar int32."""
    period_kinds, tail_kinds = block_kinds(cfg)
    h = embed_tokens(cfg, params, token)
    h = constrain(h, ("act_batch", None, "embed"))

    def body(h, xs):
        pslices, cslices = xs
        new_c = []
        for kind, p, c in zip(period_kinds, pslices, cslices):
            h, c2 = decode_block(cfg, kind, p, h, c, pos, enc_out=enc_out)
            new_c.append(c2)
        return h, tuple(new_c)

    if n_scan_blocks(cfg) > 0 and params["layers"]:
        h, new_layer_cache = jax.lax.scan(
            body, h, (params["layers"], cache["layers"]))
    else:
        new_layer_cache = cache["layers"]
    new_tail = []
    for kind, p, c in zip(tail_kinds, params["tail"], cache["tail"]):
        h, c2 = decode_block(cfg, kind, p, h, c, pos, enc_out=enc_out)
        new_tail.append(c2)
    h = L.rmsnorm(h, params["final_ln"])
    logits = h @ params["unembed"]
    return logits, {"layers": new_layer_cache, "tail": tuple(new_tail)}
