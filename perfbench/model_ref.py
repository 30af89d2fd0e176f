"""Plain reference of a dense decoder's training step.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision.  It imports nothing of the program and takes nothing that the
program made: it draws its own weights from the seed, by the same
recipe the program uses, reads its own tokens from ``traffic``, and
follows the same equations:

- token embedding scaled by sqrt(hidden_size); pre-norm residual blocks
  of RMSNorm (eps 1e-6 as run), attention with rotate-half RoPE, causal
  mask and grouped KV heads (optional q/k/v bias), and a SwiGLU MLP;
  a final RMSNorm and an untied output head;
- mean cross-entropy plus a z-loss of ``z_loss * lse^2`` per token;
- AdamW with global-norm clipping, linear warm-up then cosine decay,
  float32 moments, and parameters stored in the configuration's type.

Memory: the batch is taken a block of rows at a time inside one jitted
step, every layer is rematerialized, and attention is taken a block of
queries at a time, so that the reference of a cell fits beside nothing
else on the chips the cell holds.

``precision="fp8"`` is the control: every matmul's operands are rounded
to float8 (e4m3, one scale per tensor) first, the step below bfloat16
that a later change could be tempted to take.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def fake_fp8(x):
    """``x`` rounded to float8 e4m3 under one per-tensor scale."""
    x = x.astype(jnp.float32)
    scale = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(FP8).astype(jnp.float32) / scale


def einsum(spec, a, b, precision):
    """One matmul of the reference, in float32 or the control's fp8."""
    if precision == "fp8":
        a, b = fake_fp8(a), fake_fp8(b)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


# ---------------------------------------------------------------------------
# weights: the program's recipe, drawn here again from the same key
# ---------------------------------------------------------------------------


def _dense(key, shape, dtype, scale=None):
    scale = scale or 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _layer(m, key, dtype):
    d, f = m["d"], m["ff"]
    qd, kvd = m["h"] * m["hd"], m["kv"] * m["hd"]
    k_attn, k_mlp, _ = jax.random.split(key, 3)
    ka = jax.random.split(k_attn, 6)
    mix = {"ln": jnp.ones((d,), dtype),
           "wq": _dense(ka[1], (d, qd), dtype),
           "wk": _dense(ka[2], (d, kvd), dtype),
           "wv": _dense(ka[3], (d, kvd), dtype),
           "wo": _dense(ka[4], (qd, d), dtype)}
    if m["qkv_bias"]:
        mix.update(bq=jnp.zeros((qd,), dtype), bk=jnp.zeros((kvd,), dtype),
                   bv=jnp.zeros((kvd,), dtype))
    km = jax.random.split(k_mlp, 4)
    ffn = {"ln": jnp.ones((d,), dtype),
           "wi": _dense(km[1], (d, f), dtype),
           "wo": _dense(km[2], (f, d), dtype),
           "wg": _dense(km[3], (d, f), dtype)}
    return {"mix": mix, "ffn": ffn}


def init_params(m: dict, key):
    """Weights of the model ``m`` (see :func:`model_dims`) from ``key``."""
    dtype = jnp.dtype(m["param_dtype"])
    ks = jax.random.split(key, 7)
    layers = jax.vmap(lambda k: _layer(m, k, dtype))(
        jax.random.split(ks[1], m["layers"]))
    return {"embed": _dense(ks[0], (m["vocab"], m["d"]), dtype, scale=1.0),
            "layers": (layers,), "tail": (),
            "final_ln": jnp.ones((m["d"],), dtype),
            "unembed": _dense(ks[2], (m["d"], m["vocab"]), dtype)}


def model_dims(config: dict) -> dict:
    """The sizes and choices the reference needs, from a configuration
    file (Hugging Face keys plus its ``run`` block)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    run = config["run"]
    return {"d": d, "h": h, "kv": config["num_key_value_heads"],
            "hd": config.get("head_dim") or d // h,
            "ff": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "layers": config["num_hidden_layers"],
            "rope_theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "qkv_bias": bool(run["qkv_bias"]),
            "z_loss": float(run["z_loss"]),
            "param_dtype": run["param_dtype"], "causal": True}


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        scale.astype(jnp.float32)


def rope(x, theta):
    """Rotate-half RoPE over positions 0..S-1; x: (B, S, H, hd)."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) *
                    (math.log(theta) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(m, q, k, v, q_block, precision):
    """Causal attention with grouped KV heads, a block of queries at a
    time; q: (B, S, H, hd), k, v: (B, S, KV, hd)."""
    B, S, H, hd = q.shape
    g = H // m["kv"]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    nb = S // q_block

    @jax.checkpoint
    def block(i, qb):
        s = einsum("bqhd,bkhd->bhqk", qb, k, precision) / math.sqrt(hd)
        qpos = i * q_block + jnp.arange(q_block)[:, None]
        if m["causal"]:
            s = jnp.where(qpos >= jnp.arange(S)[None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return einsum("bhqk,bkhd->bqhd", p, v, precision)

    qs = q.reshape(B, nb, q_block, H, hd).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda a: block(*a), (jnp.arange(nb), qs))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H * hd)


def layer(m, p, x, q_block, precision):
    """One pre-norm decoder layer in float32."""
    B, S, _ = x.shape
    a = p["mix"]
    h = rmsnorm(x, a["ln"], m["eps"])
    q = einsum("bsd,de->bse", h, a["wq"], precision)
    k = einsum("bsd,de->bse", h, a["wk"], precision)
    v = einsum("bsd,de->bse", h, a["wv"], precision)
    if m["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(B, S, m["h"], m["hd"]), m["rope_theta"])
    k = rope(k.reshape(B, S, m["kv"], m["hd"]), m["rope_theta"])
    v = v.reshape(B, S, m["kv"], m["hd"])
    o = attention(m, q, k, v, q_block, precision)
    x = x + einsum("bse,ed->bsd", o, a["wo"], precision)
    f = p["ffn"]
    h = rmsnorm(x, f["ln"], m["eps"])
    u = jax.nn.silu(einsum("bsd,df->bsf", h, f["wg"], precision)) * \
        einsum("bsd,df->bsf", h, f["wi"], precision)
    return x + einsum("bsf,fd->bsd", u, f["wo"], precision)


def loss_sums(m, params, tokens, targets, q_block, precision, row_spec):
    """Sums over the block's tokens of (ce + z-loss) and of ce."""
    x = jnp.take(params["embed"], tokens, axis=0) * math.sqrt(m["d"])
    if row_spec is not None:
        x = jax.lax.with_sharding_constraint(x, row_spec)

    def body(x, lp):
        y = layer(m, lp, x, q_block, precision)
        if row_spec is not None:
            y = jax.lax.with_sharding_constraint(y, row_spec)
        return y, None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"][0])
    h = rmsnorm(x, params["final_ln"], m["eps"])
    logits = einsum("bsd,dv->bsv", h, params["unembed"], precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    ce = lse - gold
    return jnp.sum(ce + m["z_loss"] * lse * lse), jnp.sum(ce)


# ---------------------------------------------------------------------------
# the training step
# ---------------------------------------------------------------------------


def schedule(opt: dict, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    prog = jnp.clip((step - opt["warmup_steps"]) /
                    max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    return opt["lr"] * warm * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * prog)))


def make_step(m: dict, opt: dict, *, rows_per_block: int, q_block: int,
              precision: str = "f32", row_spec=None):
    """``step(params, mu, nu, count, tokens, targets)`` -> ``(params, mu,
    nu, count, loss, clipped gradient leaf norms)``.

    ``tokens`` is the whole batch; the loss is its mean over all tokens,
    taken ``rows_per_block`` rows at a time.
    """

    def grads_of(params, tokens, targets):
        p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                     params)
        B, S = tokens.shape
        nb = B // rows_per_block

        def total(p):
            def one(carry, blk):
                t, y = blk
                lz, _ = jax.checkpoint(
                    lambda p_, t_, y_: loss_sums(m, p_, t_, y_, q_block,
                                                 precision, row_spec))(p, t, y)
                return carry + lz, None
            blocks = (tokens.reshape(nb, rows_per_block, S),
                      targets.reshape(nb, rows_per_block, S))
            s, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), blocks)
            return s / (B * S)

        return jax.value_and_grad(total)(p32)

    def step(params, mu, nu, count, tokens, targets):
        loss, g = grads_of(params, tokens, targets)
        leaves = jax.tree_util.tree_leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
        g = jax.tree_util.tree_map(lambda x: x * scale, g)
        count = count + 1
        lr = schedule(opt, count)
        c = count.astype(jnp.float32)
        bc1, bc2 = 1 - opt["b1"] ** c, 1 - opt["b2"] ** c

        def upd(p, gi, mi, vi):
            mi = opt["b1"] * mi + (1 - opt["b1"]) * gi
            vi = opt["b2"] * vi + (1 - opt["b2"]) * gi * gi
            p32 = p.astype(jnp.float32)
            delta = lr * ((mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"]) +
                          opt["weight_decay"] * p32)
            return (p32 - delta).astype(p.dtype), mi, vi

        out = jax.tree_util.tree_map(upd, params, g, mu, nu)
        pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
            lambda _, o: o[i], params, out)
        norms = jax.tree_util.tree_map(lambda x: jnp.sqrt(jnp.sum(x * x)), g)
        return pick(0), pick(1), pick(2), count, loss, norms

    return step


def leaf_names(tree) -> list[str]:
    """``/``-joined key paths of a tree's leaves, in leaf order."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = []
        for k in path:
            parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
        out.append("/".join(parts))
    return out


def reference_readings(config: dict, batches, key, *, mesh=None,
                       precision: str = "f32", rows_per_block: int = 1,
                       q_block: int = 1024, causal: bool = True) -> dict:
    """Run the reference's first ``len(batches)`` steps from the weights
    of ``key`` on ``batches`` (host ``tokens``/``targets`` pairs).

    Returns the per-step losses, the per-leaf norms of the first step's
    clipped gradient, and the per-leaf norms of the parameters' change
    over all the steps, each keyed by leaf path.  With a ``mesh`` (one
    axis, ``x``) the weights and optimizer state are split over its
    devices and each block of rows is spread over them.
    ``causal=False`` drops the attention mask (a planted fault).
    """
    m = dict(model_dims(config), causal=causal)
    opt = config["run"]["optimizer"]
    S = batches[0][0].shape[1]
    q_block = min(q_block, S)
    row_spec = None
    init = functools.partial(init_params, m)
    if mesh is not None:
        n = mesh.devices.size
        row_spec = NamedSharding(mesh, P("x"))
        shapes = jax.eval_shape(init, key)
        shard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, _split_spec(s.shape, n)), shapes)
        init_j = jax.jit(init, out_shardings=shard)
        zeros_j = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), p),
            out_shardings=shard)
    else:
        init_j = jax.jit(init)
        zeros_j = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), p))
    step = jax.jit(make_step(m, opt, rows_per_block=rows_per_block,
                             q_block=q_block, precision=precision,
                             row_spec=row_spec),
                   donate_argnums=(0, 1, 2))
    params = init_j(key)
    names = leaf_names(params)
    p0_norm_fn = jax.jit(lambda a, b: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) -
                                    y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])
    mu, nu = zeros_j(params), zeros_j(params)
    count = jnp.zeros((), jnp.int32)
    losses, grad_norms = [], None
    for tokens, targets in batches:
        params, mu, nu, count, loss, norms = step(
            params, mu, nu, count, jnp.asarray(tokens), jnp.asarray(targets))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = [float(x) for x in jax.tree_util.tree_leaves(norms)]
    del mu, nu
    change = [float(x) for x in p0_norm_fn(params, init_j(key))]
    return {"loss": losses, "grad": dict(zip(names, grad_norms)),
            "change": dict(zip(names, change))}


def _split_spec(shape, n: int):
    """Split the largest dimension that ``n`` divides over axis ``x``."""
    order = np.argsort([-s for s in shape], kind="stable")
    for i in order:
        if shape[i] % n == 0 and shape[i] >= n:
            spec = [None] * len(shape)
            spec[i] = "x"
            return P(*spec)
    return P()
