"""Batched serving launcher: prefill + autoregressive decode.

Demonstrates the inference path end-to-end on real devices (reduced
configs on CPU): a batch of prompts is prefilled, then decoded token by
token from the KV/recurrent cache, with TOAST or manual sharding rules
applied the same way as training.

``--plan toast`` derives the decode-step sharding through the staged
``Session``/``Request`` API with a ``Replicate`` constraint on the
decode cache (the classic serving layout: weights sharded, KV cache
replicated per data-parallel replica group).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_05b \
        --reduced --batch 4 --prompt-len 16 --gen 16 --plan toast
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.models.sharding import MANUAL_RULES, logical_rules
from repro.train.steps import make_decode_step


def toast_decode_rules(cfg, batch: int, max_seq: int):
    """Search a decode-step sharding with the cache pinned replicated.

    Args:
        cfg: model config (reduced or full).
        batch: decode batch size.
        max_seq: cache depth (prompt + generated tokens).

    Returns:
        ``(rules, mesh)`` — ``{logical dim name -> mesh axes}`` rules for
        the ``with_sharding_constraint`` hooks plus the concrete
        ``jax.sharding.Mesh`` they apply on (``({}, None)`` on one
        device).
    """
    from repro.api import Replicate, Request, Session
    from repro.configs.base import ShapeConfig
    from repro.core.cost_model import MeshSpec
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import step_and_inputs
    n_dev = len(jax.devices())
    if n_dev < 2:
        return {}, None
    sizes = (max(1, n_dev // 2), min(2, n_dev))
    mesh_spec = MeshSpec(("data", "model"), sizes)
    fn, fargs, names = step_and_inputs(
        cfg, ShapeConfig("serve", max_seq, batch, "decode"))
    sess = Session(fn, fargs)
    has_kv = "attn" in cfg.pattern and not cfg.is_encoder_decoder
    plan = sess.partition(Request(
        mesh=mesh_spec, backend="greedy", min_dims=4,
        logical_axes=names,
        constraints=(Replicate("['k']"), Replicate("['v']"))
        if has_kv else ()))
    print(f"[toast] cost={plan.cost:.4f} rules={plan.logical_rules} "
          f"search={plan.search_seconds:.1f}s")
    return dict(plan.logical_rules), make_mesh(sizes, mesh_spec.axes)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_05b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--plan", choices=["manual", "toast"],
                    default="manual")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)
    B, P, G = args.batch, args.prompt_len, args.gen
    max_seq = P + G
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size)

    enc_out = None
    if cfg.is_encoder_decoder:
        frames = jax.random.normal(key, (B, 16, cfg.d_model), jnp.float32)
        enc_out = T.encode(cfg, params, frames)

    dec = jax.jit(make_decode_step(cfg))
    cache = T.init_cache(cfg, B, max_seq)

    rules, mesh = (toast_decode_rules(cfg, B, max_seq)
                   if args.plan == "toast" else ({}, None))
    from contextlib import nullcontext
    # the with_sharding_constraint hooks need an ambient mesh, else the
    # searched rules silently no-op
    with jax.set_mesh(mesh) if mesh is not None else nullcontext(), \
            logical_rules(rules or None):
        # prefill via the decode path (token-by-token here; the production
        # prefill lowers the full-sequence forward — see launch/dryrun.py)
        t0 = time.perf_counter()
        logits = None
        for t in range(P):
            logits, cache = dec(params, cache, prompts[:, t:t + 1],
                                jnp.int32(t), enc_out)
        t_prefill = time.perf_counter() - t0

        tokens = [jnp.argmax(logits[:, 0], axis=-1, keepdims=True)]
        t0 = time.perf_counter()
        for g in range(G - 1):
            logits, cache = dec(params, cache, tokens[-1],
                                jnp.int32(P + g), enc_out)
            tokens.append(jnp.argmax(logits[:, 0], axis=-1, keepdims=True))
        t_decode = time.perf_counter() - t0

    out = np.asarray(jnp.concatenate(tokens, axis=1))
    print(f"prefill: {t_prefill*1e3:.1f}ms  decode: "
          f"{t_decode/max(G-1,1)*1e3:.2f}ms/token")
    for b in range(B):
        print(f"request {b}: prompt={np.asarray(prompts[b])[:8]}... "
              f"generated={out[b][:12]}...")


if __name__ == "__main__":
    main()
