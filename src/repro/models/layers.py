"""Model building blocks, pure JAX.

Block kinds (selected by ``ModelConfig.block_pattern``):

- ``attn``  — GQA attention with RoPE; full, sliding-window (Mixtral) or
  encoder (non-causal) masking; KV-cache (ring buffer when windowed).
- ``local`` — local attention (RecurrentGemma), a windowed ``attn``.
- ``rglru`` — Griffin RG-LRU recurrent block (depthwise causal conv4 +
  gated linear recurrence via associative scan).
- ``mlstm`` — xLSTM matrix-memory block: parallel (quadratic, stabilised)
  form for train/prefill, recurrent matrix state for decode.
- ``slstm`` — xLSTM scalar-memory block with exponential gating,
  ``lax.scan`` over time.

Every block is pre-norm residual.  MLPs are SwiGLU or GELU; MoE blocks use
top-k routing with capacity-bounded gather/scatter dispatch (Switch-style),
optionally with Arctic's dense residual path.

All activations are annotated with logical dim names via
``sharding.constrain`` so a TOAST plan (or the manual baseline) can pin
them; with no rules installed the annotations are no-ops.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kernel_ops
from repro.models.sharding import constrain

# ---------------------------------------------------------------------------
# common
# ---------------------------------------------------------------------------


def _norm_init(key, shape, dtype):
    return jnp.ones(shape, dtype)


def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale or 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rmsnorm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def rope(x, positions, theta):
    """x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) *
                    (math.log(theta) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs        # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attn(cfg, key, cross=False):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, \
        cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "ln": _norm_init(ks[0], (d,), cfg.dtype),
        "wq": _dense_init(ks[1], (d, h * hd), cfg.dtype),
        "wk": _dense_init(ks[2], (d, kv * hd), cfg.dtype),
        "wv": _dense_init(ks[3], (d, kv * hd), cfg.dtype),
        "wo": _dense_init(ks[4], (h * hd, d), cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), cfg.dtype)
        p["bk"] = jnp.zeros((kv * hd,), cfg.dtype)
        p["bv"] = jnp.zeros((kv * hd,), cfg.dtype)
    return p


def _project_qkv(cfg, p, xq, xkv, q_positions, kv_positions, use_rope=True):
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = xq @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if cfg.qkv_bias and "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(*xq.shape[:-1], h, hd)
    k = k.reshape(*xkv.shape[:-1], kv, hd)
    v = v.reshape(*xkv.shape[:-1], kv, hd)
    if use_rope:
        q = rope(q, q_positions, cfg.rope_theta)
        k = rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def attn_core(cfg, q, k, v, mask):
    """GQA attention. q: (B,S,H,hd); k,v: (B,T,KV,hd);
    mask: (B,S,T) or (S,T) bool or None."""
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = h // kv
    B, S = q.shape[0], q.shape[1]
    T = k.shape[1]
    qg = q.reshape(B, S, kv, g, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if getattr(cfg, "score_shard_dim", "q") == "kv":
        scores = constrain(scores, ("act_batch", "kv_heads", None, None, "seq"))
    else:
        scores = constrain(scores, ("act_batch", "kv_heads", None, "seq", None))
    if mask is not None:
        m = mask[:, None, None] if mask.ndim == 3 else mask[None, None, None]
        scores = jnp.where(m, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, h * hd)


def causal_mask(S, T, offset=0, window=0):
    """(S, T) mask; offset = absolute position of query 0 minus key 0."""
    qp = jnp.arange(S)[:, None] + offset
    kp = jnp.arange(T)[None, :]
    m = qp >= kp
    if window:
        m &= (qp - kp) < window
    return m


def attn_apply(cfg, p, x, positions, *, window=0, is_causal=True,
               enc_out=None):
    """Full-sequence attention (train / prefill)."""
    h = rmsnorm(x, p["ln"])
    if enc_out is not None:                      # cross attention
        enc_out = enc_out.astype(x.dtype)
        T = enc_out.shape[1]
        kv_pos = jnp.arange(T)[None, :]
        q, k, v = _project_qkv(cfg, p, h, enc_out, positions, kv_pos,
                               use_rope=False)
        mask = None
    else:
        q, k, v = _project_qkv(cfg, p, h, h, positions, positions)
        if getattr(cfg, "use_pallas", False) and window == 0:
            # fused kernel path: expand GQA groups so the fused op's
            # head dim is shared across q/k/v (mappable by the plan),
            # then dispatch through kernels.ops — traced as a single
            # kernel:flash_attention IR op
            g = cfg.num_heads // cfg.num_kv_heads
            kf = jnp.repeat(k, g, axis=2) if g > 1 else k
            vf = jnp.repeat(v, g, axis=2) if g > 1 else v
            out = kernel_ops.attention(q, kf, vf, causal=is_causal)
            out = out.reshape(*out.shape[:2], -1)
            out = constrain(out, ("act_batch", "seq", "heads"))
            return x + (out @ p["wo"])
        S = x.shape[1]
        mask = causal_mask(S, S, 0, window) if is_causal else None
    out = attn_core(cfg, q, k, v, mask)
    out = constrain(out, ("act_batch", "seq", "heads"))
    return x + (out @ p["wo"])


def attn_init_cache(cfg, batch, max_seq, window=0, dtype=None):
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    T = min(window, max_seq) if window else max_seq
    dtype = dtype or cfg.dtype
    return {
        "k": jnp.zeros((batch, T, kvh, hd), dtype),
        "v": jnp.zeros((batch, T, kvh, hd), dtype),
        "slot_pos": jnp.full((T,), -1, jnp.int32),
    }


def attn_decode(cfg, p, x, cache, pos, *, window=0, enc_out=None):
    """One-token decode. x: (B,1,D); pos: scalar int32."""
    h = rmsnorm(x, p["ln"])
    if enc_out is not None:
        enc_out = enc_out.astype(x.dtype)
        T = enc_out.shape[1]
        kv_pos = jnp.arange(T)[None, :]
        q, k, v = _project_qkv(cfg, p, h, enc_out, pos[None, None], kv_pos,
                               use_rope=False)
        out = attn_core(cfg, q, k, v, None)
        return x + (out @ p["wo"]), cache
    q, k_new, v_new = _project_qkv(cfg, p, h, h, pos[None, None],
                                   pos[None, None])
    T = cache["k"].shape[1]
    slot = (pos % T).astype(jnp.int32)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
    slot_pos = jax.lax.dynamic_update_slice(
        cache["slot_pos"], pos[None].astype(jnp.int32), (slot,))
    valid = (slot_pos >= 0) & (slot_pos <= pos)
    if window:
        valid &= (pos - slot_pos) < window
    out = attn_core(cfg, q, k, v, valid[None, None, :])
    return x + (out @ p["wo"]), {"k": k, "v": v, "slot_pos": slot_pos}


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------


def init_mlp(cfg, key):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    p = {"ln": _norm_init(ks[0], (d,), cfg.dtype),
         "wi": _dense_init(ks[1], (d, f), cfg.dtype),
         "wo": _dense_init(ks[2], (f, d), cfg.dtype)}
    if cfg.mlp == "swiglu":
        p["wg"] = _dense_init(ks[3], (d, f), cfg.dtype)
    return p


def mlp_apply(cfg, p, x):
    with jax.named_scope("mlp"):
        h = rmsnorm(x, p["ln"])
        u = h @ p["wi"]
        u = constrain(u, ("act_batch", "seq", "hidden"))
        if cfg.mlp == "swiglu":
            u = jax.nn.silu(h @ p["wg"]) * u
        else:
            u = jax.nn.gelu(u)
        return x + (u @ p["wo"])


def init_moe(cfg, key):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 8)
    p = {"ln": _norm_init(ks[0], (d,), cfg.dtype),
         "wg": _dense_init(ks[1], (d, e), cfg.dtype),
         "wi": _dense_init(ks[2], (e, d, f), cfg.dtype),
         "wgate": _dense_init(ks[3], (e, d, f), cfg.dtype),
         "wo": _dense_init(ks[4], (e, f, d), cfg.dtype)}
    if cfg.moe_dense_residual:
        p["dense_wi"] = _dense_init(ks[5], (d, f), cfg.dtype)
        p["dense_wg"] = _dense_init(ks[6], (d, f), cfg.dtype)
        p["dense_wo"] = _dense_init(ks[7], (f, d), cfg.dtype)
    return p


def moe_apply(cfg, p, x, capacity_factor=None):
    """Top-k routing with per-expert capacity (gather/scatter dispatch).

    Tokens beyond an expert's capacity are dropped (standard Switch-style
    behaviour); capacity_factor defaults from the config.

    Dispatch modes (cfg.moe_dispatch):
    - "global": one token pool of B*S — but the reshape merges the batch
      dim, so the token dimension is a fresh NDA color and every dispatch
      buffer is unsharded (measured ~118 GiB/device for mixtral train_4k).
    - "batch": route per batch row (DP-local routing, what EP+DP systems
      deploy) — dispatch buffers keep the batch color and shard with it.
      See EXPERIMENTS.md §Perf iteration 1.
    """
    capacity_factor = capacity_factor or cfg.moe_capacity_factor
    h = rmsnorm(x, p["ln"])
    if cfg.moe_dispatch == "local":
        y = _moe_dispatch_local(cfg, p, h, capacity_factor,
                                cfg.moe_local_pools)
    elif cfg.moe_dispatch == "batch":
        y = _moe_dispatch_batch(cfg, p, h, capacity_factor)
    else:
        y = _moe_dispatch_global(cfg, p, h, capacity_factor)
    if cfg.moe_dense_residual:
        u = jax.nn.silu(h @ p["dense_wg"]) * (h @ p["dense_wi"])
        y = y + u @ p["dense_wo"]
    return x + y


def _router(cfg, p, h):
    """Top-k routing weights as a dense (..., E) matrix."""
    e, k = cfg.num_experts, cfg.experts_per_token
    logits = (h @ p["wg"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    W = jnp.zeros(probs.shape, jnp.float32)
    for j in range(k):
        W = W + jax.nn.one_hot(topi[..., j], e, dtype=jnp.float32) * \
            topw[..., j:j + 1]
    return W


def _expert_ffn(p, xe):
    """xe: (..., E, C, d) with stacked expert weights (E, d, f)."""
    he = jax.nn.silu(jnp.einsum("...ecd,edf->...ecf", xe, p["wgate"])) * \
        jnp.einsum("...ecd,edf->...ecf", xe, p["wi"])
    he = constrain(he, ("act_batch", "experts", None, "hidden")[-he.ndim:])
    return jnp.einsum("...ecf,efd->...ecd", he, p["wo"])


def _moe_dispatch_global(cfg, p, h, capacity_factor):
    B, S, d = h.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    xf = h.reshape(B * S, d)
    T = B * S
    W = _router(cfg, p, xf)                                     # (T, E)
    C = max(1, min(T, int(math.ceil(k * T / e * capacity_factor))))
    wsel, tsel = jax.lax.top_k(W.T, C)                          # (E, C)
    xe = jnp.take(xf, tsel.reshape(-1), axis=0).reshape(e, C, d)
    xe = constrain(xe, ("experts", None, None))
    ye = _expert_ffn(p, xe) * wsel[..., None].astype(h.dtype)
    y = jnp.zeros((T, d), h.dtype).at[tsel.reshape(-1)].add(
        ye.reshape(e * C, d))
    return y.reshape(B, S, d)


def _moe_dispatch_batch(cfg, p, h, capacity_factor):
    B, S, d = h.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    W = _router(cfg, p, h)                                      # (B, S, E)
    C = max(1, min(S, int(math.ceil(k * S / e * capacity_factor))))
    wsel, tsel = jax.lax.top_k(W.transpose(0, 2, 1), C)         # (B, E, C)
    xe = jnp.take_along_axis(
        h[:, None], tsel[..., None], axis=2)                    # (B,E,C,d)
    xe = constrain(xe, ("act_batch", "experts", None, None))
    ye = _expert_ffn(p, xe) * wsel[..., None].astype(h.dtype)
    ye = constrain(ye, ("act_batch", "experts", None, None))

    def combine(tb, yeb):
        out = jnp.zeros((S, d), h.dtype)
        return out.at[tb.reshape(-1)].add(yeb.reshape(-1, d))

    return jax.vmap(combine)(tsel, ye)


def _moe_dispatch_local(cfg, p, h, capacity_factor, pools):
    """Route within (batch row x seq pool): with `pools` equal to the seq
    sharding degree, dispatch gathers/scatters are device-local — no
    all-gather of the hidden states (EXPERIMENTS.md §Perf iteration H1d).
    Capacity is enforced per pool (the EP analogue of DP-local routing)."""
    B, S, d = h.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    pools = max(1, min(pools or 1, S))
    Sl = S // pools
    hp = h.reshape(B, pools, Sl, d)
    hp = constrain(hp, ("act_batch", "seq", None, None))
    W = _router(cfg, p, hp)                                  # (B,P,Sl,E)
    C = max(1, min(Sl, int(math.ceil(k * Sl / e * capacity_factor))))
    wsel, tsel = jax.lax.top_k(W.transpose(0, 1, 3, 2), C)   # (B,P,E,C)
    xe = jnp.take_along_axis(
        hp[:, :, None], tsel[..., None], axis=3)             # (B,P,E,C,d)
    xe = constrain(xe, ("act_batch", "seq", "experts", None, None))
    ye = _expert_ffn(p, xe) * wsel[..., None].astype(h.dtype)

    def combine(tb, yeb):
        out = jnp.zeros((Sl, d), h.dtype)
        return out.at[tb.reshape(-1)].add(yeb.reshape(-1, d))

    y = jax.vmap(jax.vmap(combine))(tsel, ye)                # (B,P,Sl,d)
    return y.reshape(B, S, d)


# ---------------------------------------------------------------------------
# RG-LRU (Griffin)
# ---------------------------------------------------------------------------

_RG_C = 8.0
_CONV_K = 4


def _rnn_width(cfg):
    return (cfg.d_model * 3) // 2


def init_rglru(cfg, key):
    d = cfg.d_model
    r = _rnn_width(cfg)
    ks = jax.random.split(key, 8)
    return {
        "ln": _norm_init(ks[0], (d,), cfg.dtype),
        "wx": _dense_init(ks[1], (d, r), cfg.dtype),
        "wy": _dense_init(ks[2], (d, r), cfg.dtype),
        "wo": _dense_init(ks[3], (r, d), cfg.dtype),
        "conv_w": _dense_init(ks[4], (_CONV_K, r), cfg.dtype, scale=0.5),
        "conv_b": jnp.zeros((r,), cfg.dtype),
        # diagonal gate parametrisation (per-channel weight + bias)
        "ga_w": _dense_init(ks[5], (r,), cfg.dtype, scale=1.0),
        "ga_b": jnp.zeros((r,), cfg.dtype),
        "gi_w": _dense_init(ks[6], (r,), cfg.dtype, scale=1.0),
        "gi_b": jnp.zeros((r,), cfg.dtype),
        # Λ init so a = σ(Λ)^c starts near 0.9..0.999
        "lam": (jax.random.uniform(ks[7], (r,), jnp.float32) * 2 + 4
                ).astype(cfg.dtype),
    }


def _causal_conv4(u, w, b, state=None):
    """Depthwise causal conv, kernel 4.  u: (B,S,r); state: (B,3,r)."""
    if state is None:
        pad = jnp.zeros_like(u[:, :_CONV_K - 1])
    else:
        pad = state
    ext = jnp.concatenate([pad, u], axis=1)                 # (B, S+3, r)
    S = u.shape[1]
    out = sum(ext[:, i:i + S] * w[_CONV_K - 1 - i] for i in range(_CONV_K))
    new_state = ext[:, -( _CONV_K - 1):]
    return out + b, new_state


def _rglru_gates(p, u):
    rt = jax.nn.sigmoid(u * p["ga_w"] + p["ga_b"]).astype(jnp.float32)
    it = jax.nn.sigmoid(u * p["gi_w"] + p["gi_b"]).astype(jnp.float32)
    log_a = -_RG_C * rt * jax.nn.softplus(p["lam"].astype(jnp.float32))
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6))
    bterm = beta * (it * u.astype(jnp.float32))
    return a, bterm


def rglru_apply(cfg, p, x):
    h = rmsnorm(x, p["ln"])
    u = h @ p["wx"]
    u, _ = _causal_conv4(u, p["conv_w"], p["conv_b"])
    u = constrain(u, ("act_batch", "seq", "rnn"))
    a, bterm = _rglru_gates(p, u)

    if getattr(cfg, "use_pallas", False):
        # fused kernel path — traced as a single kernel:rg_lru IR op
        hseq = kernel_ops.rg_lru(a, bterm)
    else:
        def combine(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, a2 * b1 + b2

        _, hseq = jax.lax.associative_scan(combine, (a, bterm), axis=1)
    y = jax.nn.gelu(h @ p["wy"]) * hseq.astype(x.dtype)
    return x + (y @ p["wo"])


def rglru_init_cache(cfg, batch, dtype=None):
    r = _rnn_width(cfg)
    dtype = dtype or cfg.dtype
    return {"h": jnp.zeros((batch, r), jnp.float32),
            "conv": jnp.zeros((batch, _CONV_K - 1, r), dtype)}


def rglru_decode(cfg, p, x, cache, pos):
    h = rmsnorm(x, p["ln"])
    u = h @ p["wx"]                                         # (B,1,r)
    u, conv_state = _causal_conv4(u, p["conv_w"], p["conv_b"], cache["conv"])
    a, bterm = _rglru_gates(p, u)
    hnew = a[:, 0] * cache["h"] + bterm[:, 0]               # (B,r)
    y = jax.nn.gelu(h @ p["wy"]) * hnew[:, None].astype(x.dtype)
    return x + (y @ p["wo"]), {"h": hnew, "conv": conv_state}


# ---------------------------------------------------------------------------
# xLSTM blocks
# ---------------------------------------------------------------------------


def init_mlstm(cfg, key):
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 7)
    return {
        "ln": _norm_init(ks[0], (d,), cfg.dtype),
        "wq": _dense_init(ks[1], (d, h * hd), cfg.dtype),
        "wk": _dense_init(ks[2], (d, h * hd), cfg.dtype),
        "wv": _dense_init(ks[3], (d, h * hd), cfg.dtype),
        "wi": _dense_init(ks[4], (d, h), cfg.dtype),
        "wf": _dense_init(ks[5], (d, h), cfg.dtype),
        "wo": _dense_init(ks[6], (h * hd, d), cfg.dtype),
    }


def mlstm_apply(cfg, p, x):
    """Parallel (stabilised quadratic) mLSTM forward."""
    B, S, d = x.shape
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    xn = rmsnorm(x, p["ln"])
    q = (xn @ p["wq"]).reshape(B, S, h, hd)
    k = (xn @ p["wk"]).reshape(B, S, h, hd) / math.sqrt(hd)
    v = (xn @ p["wv"]).reshape(B, S, h, hd)
    ig = (xn @ p["wi"]).astype(jnp.float32)                 # (B,S,h)
    fg = (xn @ p["wf"]).astype(jnp.float32)
    logf = -jax.nn.softplus(-fg)                            # log σ(f)
    F = jnp.cumsum(logf, axis=1)                            # (B,S,h)
    # logD[b,h,i,j] = F_i - F_j + ig_j   (j <= i)
    logD = (F.transpose(0, 2, 1)[:, :, :, None] -
            F.transpose(0, 2, 1)[:, :, None, :] +
            ig.transpose(0, 2, 1)[:, :, None, :])
    mask = jnp.tril(jnp.ones((S, S), bool))
    logD = jnp.where(mask[None, None], logD, -jnp.inf)
    m = jnp.max(logD, axis=-1, keepdims=True)               # (B,h,S,1)
    D = jnp.exp(logD - jnp.maximum(m, 0.0))
    Sqk = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * D
    Sqk = constrain(Sqk, ("act_batch", "heads", "seq", None))
    n = jnp.maximum(jnp.abs(jnp.sum(Sqk, axis=-1, keepdims=True)),
                    jnp.exp(-jnp.maximum(m, 0.0)))
    out = jnp.einsum("bhst,bthd->bshd", (Sqk / n).astype(v.dtype), v)
    return x + out.reshape(B, S, h * hd) @ p["wo"]


def mlstm_init_cache(cfg, batch, dtype=None):
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    return {"C": jnp.zeros((batch, h, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, h, hd), jnp.float32),
            "m": jnp.zeros((batch, h), jnp.float32)}


def mlstm_decode(cfg, p, x, cache, pos):
    B = x.shape[0]
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    xn = rmsnorm(x, p["ln"])
    q = (xn @ p["wq"]).reshape(B, h, hd)
    k = (xn @ p["wk"]).reshape(B, h, hd) / math.sqrt(hd)
    v = (xn @ p["wv"]).reshape(B, h, hd)
    ig = (xn @ p["wi"]).astype(jnp.float32).reshape(B, h)
    fg = (xn @ p["wf"]).astype(jnp.float32).reshape(B, h)
    logf = -jax.nn.softplus(-fg)
    m_new = jnp.maximum(logf + cache["m"], ig)
    fsc = jnp.exp(logf + cache["m"] - m_new)[..., None]
    isc = jnp.exp(ig - m_new)[..., None]
    C = fsc[..., None] * cache["C"] + \
        isc[..., None] * (v[..., :, None] * k[..., None, :])
    nvec = fsc * cache["n"] + isc * k
    hn = jnp.einsum("bhij,bhj->bhi", C, q.astype(jnp.float32))
    denom = jnp.maximum(jnp.abs(jnp.sum(nvec * q, axis=-1, keepdims=True)),
                        jnp.exp(-m_new)[..., None])
    out = (hn / denom).astype(x.dtype).reshape(B, 1, h * hd)
    return x + out @ p["wo"], {"C": C, "n": nvec, "m": m_new}


def init_slstm(cfg, key):
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 3)
    return {
        "ln": _norm_init(ks[0], (d,), cfg.dtype),
        "W": _dense_init(ks[1], (d, 4 * h * hd), cfg.dtype),
        "R": _dense_init(ks[2], (h, hd, 4 * hd), cfg.dtype),
        "b": jnp.zeros((4 * h * hd,), cfg.dtype),
        "wo": _dense_init(jax.random.fold_in(key, 9), (h * hd, d), cfg.dtype),
    }


def _slstm_step(cfg, p, carry, pre_x):
    """One sLSTM step. carry: (c, n, hst, m) each (B,h,hd)."""
    h_, hd = cfg.num_heads, cfg.resolved_head_dim
    c, n, hst, m = carry
    rec = jnp.einsum("bij,ijk->bik", hst.astype(p["R"].dtype), p["R"])
    pre = pre_x.reshape(*pre_x.shape[:-1], h_, 4 * hd) + rec
    zi, ii, fi, oi = jnp.split(pre.astype(jnp.float32), 4, axis=-1)
    z = jnp.tanh(zi)
    o = jax.nn.sigmoid(oi)
    logf = -jax.nn.softplus(-fi)
    m_new = jnp.maximum(logf + m, ii)
    isc = jnp.exp(ii - m_new)
    fsc = jnp.exp(logf + m - m_new)
    c_new = fsc * c + isc * z
    n_new = jnp.maximum(fsc * n + isc, 1.0)
    h_new = o * c_new / n_new
    return (c_new, n_new, h_new, m_new), h_new


def slstm_apply(cfg, p, x):
    B, S, d = x.shape
    h_, hd = cfg.num_heads, cfg.resolved_head_dim
    xn = rmsnorm(x, p["ln"])
    pre = xn @ p["W"] + p["b"]                              # (B,S,h*4hd)
    z = jnp.zeros((B, h_, hd), jnp.float32)
    carry = (z, z, z, jnp.zeros((B, h_, hd), jnp.float32))

    def body(carry, pre_t):
        return _slstm_step(cfg, p, carry, pre_t)

    _, hs = jax.lax.scan(body, carry, pre.transpose(1, 0, 2))
    out = hs.transpose(1, 0, 2, 3).reshape(B, S, h_ * hd).astype(x.dtype)
    return x + out @ p["wo"]


def slstm_init_cache(cfg, batch, dtype=None):
    h_, hd = cfg.num_heads, cfg.resolved_head_dim
    z = jnp.zeros((batch, h_, hd), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": z}


def slstm_decode(cfg, p, x, cache, pos):
    B = x.shape[0]
    h_, hd = cfg.num_heads, cfg.resolved_head_dim
    xn = rmsnorm(x, p["ln"])
    pre = (xn @ p["W"] + p["b"])[:, 0]
    carry = (cache["c"], cache["n"], cache["h"], cache["m"])
    carry, h_new = _slstm_step(cfg, p, carry, pre)
    out = h_new.reshape(B, 1, h_ * hd).astype(x.dtype)
    cache = {"c": carry[0], "n": carry[1], "h": carry[2], "m": carry[3]}
    return x + out @ p["wo"], cache
