"""End-to-end training launcher with fault tolerance.

Runs a (reduced or full) config on whatever devices exist, with:

- TOAST auto-partitioning (or manual rules) applied via logical rules +
  input shardings,
- deterministic data pipeline with prefetch,
- periodic async checkpointing, resume-from-latest on start,
- a supervisor mode (``--max-failures``) that restarts the training loop
  on simulated/real failures — the restart path is identical to a node
  replacement at scale: rebuild the mesh, restore the latest checkpoint
  (onto the new mesh if its shape changed), and continue.

Example (CPU, reduced config)::

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_05b \
        --reduced --steps 30 --batch 8 --seq 64 --plan toast
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.api import Request, Session
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.cost_model import MeshSpec
from repro.core.mcts import MCTSConfig
from repro.ckpt.checkpoint import CheckpointManager
from repro.data.pipeline import DataConfig, Pipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.specs import (specs_from_rules, state_logical_axes,
                                step_and_inputs)
from repro.models.sharding import MANUAL_RULES, logical_rules
from repro.train.steps import init_train_state, make_train_step
from repro.optim import compression as gc_mod


def build_mesh(spec: MeshSpec):
    from repro.launch.mesh import make_mesh
    n = len(jax.devices())
    sizes = []
    remaining = n
    for s in spec.sizes:
        s = min(s, remaining)
        sizes.append(s)
        remaining //= s
    return make_mesh(sizes, spec.axes)


def toast_rules(cfg, shape, mesh_spec: MeshSpec, budget_rounds=6,
                backend: str = "mcts"):
    fn, args, names = step_and_inputs(cfg, shape)
    sess = Session(fn, args)
    cfg_search = MCTSConfig(rounds=budget_rounds) \
        if backend == "mcts" else None
    return sess.partition(Request(mesh=mesh_spec, backend=backend,
                                  search_config=cfg_search, min_dims=4,
                                  logical_axes=names))


def run_once(args, attempt: int) -> bool:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    n_dev = len(jax.devices())
    mesh_spec = MeshSpec(("data", "model"),
                         (max(1, n_dev // 2), min(2, n_dev)))
    mesh = build_mesh(mesh_spec)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    if args.plan == "toast":
        plan = toast_rules(cfg, shape, mesh_spec)
        rules = plan.logical_rules or dict(MANUAL_RULES)
        print(f"[toast] cost={plan.cost:.4f} rules={rules} "
              f"search={plan.search_seconds:.1f}s")
    else:
        rules = dict(MANUAL_RULES)

    train_step = make_train_step(cfg)
    state = init_train_state(cfg, jax.random.PRNGKey(args.seed))
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    start_step = 0
    if ckpt.latest_step() is not None:
        start_step, state = ckpt.restore(state)
        print(f"[resume] from step {start_step}")

    state_specs = specs_from_rules(
        jax.eval_shape(lambda: state),
        state_logical_axes(cfg, state), rules, axis_sizes)
    state = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, jax.sharding.NamedSharding(mesh, s)),
        state, state_specs,
        is_leaf=lambda x: isinstance(x, jax.Array))

    comp_cfg = gc_mod.CompressionConfig(scheme=args.compress)
    pipe = Pipeline(cfg, shape, DataConfig(seed=args.seed),
                    start_step=start_step)
    jit_step = jax.jit(train_step, donate_argnums=0)
    t0, wait0 = time.perf_counter(), 0.0
    try:
        with jax.set_mesh(mesh), logical_rules(rules):
            for i in range(start_step, args.steps):
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    _, batch = next(pipe)
                    if args.fail_at is not None and i == args.fail_at \
                            and attempt == 0:
                        raise RuntimeError("injected node failure")
                    state, metrics = jit_step(state, batch)
                if (i + 1) % args.ckpt_every == 0 or i + 1 == args.steps:
                    ckpt.save_async(i + 1, state)
                if (i + 1) % args.log_every == 0:
                    dt = (time.perf_counter() - t0) / args.log_every
                    wait = (pipe.wait_s - wait0) / args.log_every
                    t0, wait0 = time.perf_counter(), pipe.wait_s
                    print(f"step {i+1}: loss={float(metrics['loss']):.4f} "
                          f"gnorm={float(metrics['grad_norm']):.3f} "
                          f"{dt*1e3:.0f}ms/step "
                          f"(data wait {wait*1e3:.1f}ms/step)", flush=True)
        ckpt.wait()
        return True
    finally:
        pipe.close()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_05b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--plan", choices=["manual", "toast"], default="manual")
    ap.add_argument("--compress", default="none")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (first attempt)")
    ap.add_argument("--max-failures", type=int, default=2)
    args = ap.parse_args()
    enable_compile_cache()

    for attempt in range(args.max_failures + 1):
        try:
            if run_once(args, attempt):
                print("training complete")
                return
        except RuntimeError as e:
            print(f"[supervisor] attempt {attempt} failed: {e}; restarting")
    raise SystemExit("exceeded max failures")


if __name__ == "__main__":
    main()
