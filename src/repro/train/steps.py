"""Train / serve step factories.

``make_train_step(cfg)`` returns ``(train_step, TrainState helpers)``
computing softmax cross-entropy (fp32), grads, AdamW update, grad-norm and
loss metrics.  ``make_prefill_step`` / ``make_decode_step`` build the
serving entry points.  All steps are pure functions suitable for
``jax.jit`` + AOT ``.lower().compile()``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.optim import adam


class TrainState(NamedTuple):
    params: Any
    opt: adam.AdamState


def init_train_state(cfg, key, opt_cfg: adam.AdamConfig | None = None):
    params = T.init_params(cfg, key)
    return TrainState(params, adam.init(opt_cfg or adam.AdamConfig(), params))


def train_state_specs(cfg, opt_cfg: adam.AdamConfig | None = None):
    return jax.eval_shape(
        lambda: init_train_state(cfg, jax.random.PRNGKey(0), opt_cfg))


def cross_entropy(logits, targets, *, z_loss=1e-4):
    """fp32 CE with z-loss regularisation (production stability trick)."""
    with jax.named_scope("head_loss"):
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1).squeeze(-1)
        ce = lse - gold
        zl = z_loss * jnp.square(lse)
        return jnp.mean(ce + zl), jnp.mean(ce)


def make_loss_fn(cfg):
    def loss_fn(params, batch):
        kwargs = {}
        if "patch_embeds" in batch:
            kwargs["patch_embeds"] = batch["patch_embeds"]
        if "frames" in batch:
            kwargs["frames"] = batch["frames"]
        logits = T.forward(cfg, params, batch["tokens"], **kwargs)
        if "patch_embeds" in batch:               # image positions have no
            logits = logits[:, batch["patch_embeds"].shape[1]:]  # LM target
        loss, ce = cross_entropy(logits, batch["targets"])
        return loss, ce
    return loss_fn


def make_train_step(cfg, opt_cfg: adam.AdamConfig | None = None,
                    accum_steps: int = 1):
    """Returns train_step(state, batch) -> (state, metrics).

    With accum_steps > 1 the batch's leading dim is split into microbatches
    accumulated with a ``lax.scan`` (grad accumulation for large global
    batches)."""
    opt_cfg = opt_cfg or adam.AdamConfig()
    loss_fn = make_loss_fn(cfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def single(params, batch):
        (loss, ce), grads = grad_fn(params, batch)
        return loss, ce, grads

    def train_step(state: TrainState, batch):
        if accum_steps == 1:
            loss, ce, grads = single(state.params, batch)
        else:
            def micro(carry, mb):
                loss_a, ce_a, g_a = carry
                l, c, g = single(state.params, mb)
                g_sum = jax.tree_util.tree_map(jnp.add, g_a, g)
                return (loss_a + l, ce_a + c, g_sum), None

            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                                    *x.shape[1:]), batch)
            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (loss, ce, grads), _ = jax.lax.scan(
                micro, (jnp.zeros(()), jnp.zeros(()), zero_g), mbs)
            loss, ce = loss / accum_steps, ce / accum_steps
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)
        new_params, new_opt, gnorm = adam.apply_updates(
            opt_cfg, state.opt, state.params, grads)
        metrics = {"loss": loss, "ce": ce, "grad_norm": gnorm,
                   "step": new_opt.step}
        return TrainState(new_params, new_opt), metrics

    return train_step


def make_prefill_step(cfg):
    def prefill(params, batch):
        kwargs = {}
        if "patch_embeds" in batch:
            kwargs["patch_embeds"] = batch["patch_embeds"]
        if "frames" in batch:
            kwargs["frames"] = batch["frames"]
        logits = T.forward(cfg, params, batch["tokens"], **kwargs)
        return logits[:, -1]
    return prefill


def make_decode_step(cfg):
    def decode(params, cache, token, pos, enc_out=None):
        return T.decode_step(cfg, params, cache, token, pos,
                             enc_out=enc_out)
    return decode
