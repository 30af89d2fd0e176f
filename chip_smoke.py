"""Smoke run of TOAST's main path on a TPU: qwen2_05b at full width.

    python chip_smoke.py               # one chip (the default phase)
    python chip_smoke.py --chips 4     # the 2x2 mesh phase, and only it
    python chip_smoke.py --reduced     # the same at reduced() size: a CPU
                                       # rehearsal, which still exits 1

Default phase: the fused (``use_pallas``) train step of qwen2_05b is
traced and analysed by ``Session``, a plan is searched for a 1x1
``(data, model)`` mesh by ``Session.partition`` and installed by
``ShardingPlan.apply``; the state is initialized from ``--seed`` and the
batches come from the seeded ``Pipeline``.  One warm-up step, then
``STEPS`` more.  Checks: every loss is finite; the step-0 loss is
within ``INIT_LOSS_TOL`` of ln(vocab); the first ``CHECK_STEPS`` steps
match the decomposed (``use_pallas=False``) step from the same params on
the same batches (see ``compare``); the fused attention and RG-LRU
kernels match their reference impls within ``KERNEL_TOL`` and
``RG_LRU_TOL``; the plan records kernel sites and the compiled step
holds a Mosaic kernel (``tpu_custom_call``).

``--chips 4``: the same step searched for a 2x2 mesh and applied over
four devices.  Its first ``CHECK_STEPS`` steps are compared with the
same steps on one device (see ``compare``), the sharded parameters must
be spread over the four devices, and the sharded kernel sites must lower
through ``jax.shard_map``.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU the script exits non-zero and prints no such line.  Each
step time printed here is a smoke reading, not a benchmark result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro.api import Request, Session                      # noqa: E402
from repro.configs import get_config                        # noqa: E402
from repro.configs.base import ShapeConfig                  # noqa: E402
from repro.core.cost_model import MeshSpec                  # noqa: E402
from repro.data.pipeline import DataConfig, Pipeline        # noqa: E402
from repro.kernels import ops                               # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh                     # noqa: E402
from repro.launch.specs import step_and_inputs              # noqa: E402
from repro.models.sharding import (KernelDispatch,          # noqa: E402
                                   kernel_dispatch, logical_rules)
from repro.train.steps import (init_train_state,            # noqa: E402
                               make_train_step)

AXES = ("data", "model")
BATCH = 4           # sequences per step, each --seq tokens long
STEPS = 5           # timed steps after the warm-up step
CHECK_STEPS = 2     # leading steps compared with a reference run
# recurrentgemma_2b's RG-LRU width (``layers._rnn_width``)
RG_LRU_WIDTH = 3840
# |step-0 loss - ln(vocab)|: logits of a random init are ~N(0, 1) per
# entry (unit-RMS final norm against a 1/sqrt(d) unembedding), which
# puts the expected loss near ln(vocab) + 1/2
INIT_LOSS_TOL = 1.0
# |loss difference| between two programs on the same params and batch:
# both run bf16 matmuls with f32 accumulation and an f32 loss, and differ
# only in rounding order, which the mean over thousands of tokens damps
# (about 3e-5 at full width on a TPU v5e)
LOSS_TOL = 1e-3
# |grad-norm ratio - 1| between the same two programs: the norm of the
# bf16 gradients, each of which such rounding moves by a few bf16 steps
# (2^-8 relative) at most
GRAD_NORM_TOL = 1e-2
# ||m - m_ref|| / ||m_ref|| of Adam's first moment after the compared
# steps: a running mean of the clipped gradients, so it compares the
# backward pass element by element.  At full width on a TPU v5e, fused
# vs decomposed attention differed by 3.4e-3 from rounding alone, and
# by 2.7e-2 when the fused attention dropped its causal mask, a fault
# that moved the loss by only 7e-4; the limit sits between the two
GRAD_TOL = 1e-2
# max |pallas - reference| of one bf16 attention call with unit-normal
# inputs: outputs stay below ~4, where a bf16 step is 2^-6
KERNEL_TOL = 5e-2
# max |pallas - reference| of one f32 RG-LRU scan: the same f32 products
# in another order (sequential against associative scan) on states that
# stay below ~10
RG_LRU_TOL = 1e-4


def log(msg: str) -> None:
    """Print one progress line."""
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    """Fail the run (non-zero exit) unless ``ok``."""
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {msg}")
    log(f"[ok] {msg}")


def device_info() -> dict:
    """The device as JAX reports it."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def search(cfg, shape: ShapeConfig, mesh_shape: tuple[int, int]):
    """Trace + analyse the train step, then search a plan for the mesh."""
    fn, args, names = step_and_inputs(cfg, shape)
    sess = Session(fn, args)
    phases = {k: round(v, 3)
              for k, v in sess.artifacts.phase_seconds.items()}
    log(f"[analysis] {sum(sess.artifacts.phase_seconds.values()):.3f}s "
        f"phases={phases} ops={len(sess.artifacts.prog.ops)}")
    plan = sess.partition(Request(mesh=MeshSpec(AXES, mesh_shape),
                                  logical_axes=names))
    log(f"[plan] mesh={mesh_shape} backend={plan.backend} "
        f"cost={plan.cost:.6f} search={plan.search_seconds:.3f}s "
        f"rules={plan.logical_rules}")
    log("[plan] kernel_sites=" + str([(r["site"], r["impl"], r["sharded"])
                                      for r in plan.kernel_sites]))
    return fn, plan


def place(plan, mesh, tree):
    """Put ``(state, batch)`` on the mesh with the plan's input specs."""
    return jax.device_put(tree, plan.jax_in_shardings(
        mesh, jax.tree_util.tree_structure(tree)))


def compile_step(plan, fn, mesh, state, batch):
    """``plan.apply`` the step and AOT-compile it for these arguments."""
    applied = plan.apply(fn, mesh, donate_argnums=0)
    t0 = time.perf_counter()
    with jax.set_mesh(mesh), logical_rules(plan.logical_rules or None):
        lowered = applied.lower(state, batch)
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    log(f"[compile] step lower+compile {seconds:.3f}s")
    return lowered, compiled


def scalars(metrics) -> dict:
    """The step's loss and gradient norm as Python floats."""
    return {k: float(metrics[k]) for k in ("loss", "grad_norm")}


def run_step(compiled, plan, mesh, state, batch):
    """One step ending in ``block_until_ready``; returns state, the
    loss and gradient norm, and seconds."""
    t0 = time.perf_counter()
    state, metrics = compiled(*place(plan, mesh, (state, batch)))
    jax.block_until_ready((state, metrics))
    seconds = time.perf_counter() - t0
    return state, scalars(metrics), seconds


def reference_steps(step_fn, cfg, key, batches):
    """Per-step loss and gradient norm of ``step_fn`` from a fresh
    state, jitted on the default device, and Adam's first moment after
    the last step, on the host.  The state is donated and dropped, so
    the run under test can hold a state of its own."""
    step = jax.jit(step_fn, donate_argnums=0)
    state = init_train_state(cfg, key)
    out = []
    for batch in batches:
        state, metrics = step(state, batch)
        out.append(scalars(metrics))
    return out, jax.device_get(state.opt.m)


def rel_l2(got, want) -> float:
    """``||got - want|| / ||want||`` over all leaves of two host trees."""
    num = den = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        num += float(np.sum(np.square(g - w)))
        den += float(np.sum(np.square(w)))
    return math.sqrt(num / den)


def compare(label: str, got: list[dict], want: list[dict], got_m,
            want_m) -> None:
    """Step by step, loss within ``LOSS_TOL`` and gradient norm within
    ``GRAD_NORM_TOL`` (relative) of the reference; then Adam's first
    moment within ``GRAD_TOL`` (relative L2).  Every reading is printed
    before the checks."""
    dl = [abs(g["loss"] - w["loss"]) for g, w in zip(got, want)]
    dg = [abs(g["grad_norm"] / w["grad_norm"] - 1)
          for g, w in zip(got, want)]
    for i, (g, w) in enumerate(zip(got, want)):
        log(f"[{label} step {i}] loss {g['loss']:.6f} vs {w['loss']:.6f} "
            f"|diff| {dl[i]:.3e}; grad_norm {g['grad_norm']:.6f} vs "
            f"{w['grad_norm']:.6f} |ratio - 1| {dg[i]:.3e}")
    dm = rel_l2(got_m, want_m)
    log(f"[{label}] Adam first moment after {len(got)} steps: relative "
        f"L2 difference {dm:.3e}")
    check(max(dl) <= LOSS_TOL, f"{label}: max |loss diff| over "
          f"{len(dl)} steps {max(dl):.3e} <= {LOSS_TOL}")
    check(max(dg) <= GRAD_NORM_TOL, f"{label}: max |grad_norm ratio - 1| "
          f"over {len(dg)} steps {max(dg):.3e} <= {GRAD_NORM_TOL}")
    check(dm <= GRAD_TOL, f"{label}: Adam first moment relative L2 "
          f"difference {dm:.3e} <= {GRAD_TOL}")


def memory_line(devices) -> str:
    """Bytes in use and peak bytes per device, where reported."""
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:in_use={st.get('bytes_in_use')} "
                     f"peak={st.get('peak_bytes_in_use')}")
    return "; ".join(parts)


def check_init_loss(cfg, loss: float) -> None:
    """Finite, and near ln(vocab) for a random init."""
    check(math.isfinite(loss), f"step-0 loss {loss!r} is finite")
    ln_v = math.log(cfg.vocab_size)
    check(abs(loss - ln_v) <= INIT_LOSS_TOL,
          f"step-0 loss {loss:.6f} within {INIT_LOSS_TOL} of "
          f"ln(vocab)={ln_v:.6f}")


def kernel_diff(call, args) -> float:
    """max |pallas - ref| of one dispatched kernel call."""
    out = {}
    for impl in ("pallas", "ref"):
        with kernel_dispatch(KernelDispatch(default_impl=impl)):
            out[impl] = call(*args).astype(jnp.float32)
    return float(jnp.max(jnp.abs(out["pallas"] - out["ref"])))


def kernel_check(key, attn_shape: tuple[int, int, int, int],
                 seq_len: int) -> None:
    """Both fused kernels against their reference impls: attention on
    bf16 inputs in model layout ``(B, S, H, hd)``, and the RG-LRU scan
    on f32 inputs as ``layers.rglru_apply`` feeds it."""
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), attn_shape,
                                 jnp.bfloat16) for i in range(3))
    diff = kernel_diff(lambda *a: ops.attention(*a, causal=True), (q, k, v))
    check(diff <= KERNEL_TOL, f"flash_attention {attn_shape}: max "
          f"|pallas - ref| = {diff:.6f} <= {KERNEL_TOL}")
    lru_shape = (1, seq_len, RG_LRU_WIDTH)
    a = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 3),
                                         lru_shape))
    b = 0.1 * jax.random.normal(jax.random.fold_in(key, 4), lru_shape)
    diff = kernel_diff(ops.rg_lru, (a, b))
    check(diff <= RG_LRU_TOL, f"rg_lru_scan {lru_shape} f32: max "
          f"|pallas - ref| = {diff:.3e} <= {RG_LRU_TOL}")


def one_chip_phase(cfg, shape, args, on_tpu: bool) -> None:
    """Session -> partition -> plan.apply on a 1x1 mesh, then steps."""
    fn, plan = search(cfg, shape, (1, 1))
    check(bool(plan.kernel_sites), f"plan records "
          f"{len(plan.kernel_sites)} kernel sites")
    mesh = make_mesh((1, 1), AXES, devices=jax.devices()[:1])
    key = jax.random.PRNGKey(args.seed)
    pipe = Pipeline(cfg, shape, DataConfig(seed=args.seed))
    try:
        batches = [next(pipe)[1] for _ in range(1 + STEPS)]
    finally:
        pipe.close()
    kernel_check(jax.random.fold_in(key, 1),
                 (1, shape.seq_len, cfg.num_heads, cfg.resolved_head_dim),
                 shape.seq_len)
    ref_cfg = dataclasses.replace(cfg, use_pallas=False)
    want, want_m = reference_steps(make_train_step(ref_cfg), cfg, key,
                                   batches[:CHECK_STEPS])

    state = init_train_state(cfg, key)
    _, compiled = compile_step(plan, fn, mesh,
                               *place(plan, mesh, (state, batches[0])))
    if on_tpu:
        check("tpu_custom_call" in compiled.as_text(),
              "compiled step holds a Mosaic kernel (tpu_custom_call)")
    got, times = [], []
    for i, batch in enumerate(batches):
        state, metrics, s = run_step(compiled, plan, mesh, state, batch)
        got.append(metrics)
        times.append(s * 1e3)
        log(f"[step {i}]{' warm-up' if i == 0 else ''} "
            f"loss={metrics['loss']:.6f} {s * 1e3:.3f}ms")
        if i == CHECK_STEPS - 1:
            got_m = jax.device_get(state.opt.m)
    check_init_loss(cfg, got[0]["loss"])
    compare("fused vs decomposed", got[:CHECK_STEPS], want, got_m, want_m)
    check(all(math.isfinite(m["loss"]) for m in got),
          f"all {len(got)} losses finite")
    log(f"[smoke reading] step ms {times[1:]}")
    log(f"[memory] {memory_line(jax.devices()[:1])}")


def four_chip_phase(cfg, shape, args, on_tpu: bool) -> None:
    """The step searched for and applied over a 2x2 mesh, against the
    same steps on one device."""
    devices = jax.devices()[:4]
    fn, plan = search(cfg, shape, (2, 2))
    sharded_sites = [r["site"] for r in plan.kernel_sites if r["sharded"]]
    check(bool(sharded_sites), f"plan shards kernel sites {sharded_sites}")
    mesh = make_mesh((2, 2), AXES, devices=devices)
    key = jax.random.PRNGKey(args.seed)
    pipe = Pipeline(cfg, shape, DataConfig(seed=args.seed))
    try:
        batches = [next(pipe)[1] for _ in range(CHECK_STEPS)]
    finally:
        pipe.close()
    want, want_m = reference_steps(fn, cfg, key, batches)

    state, batch = place(plan, mesh, (init_train_state(cfg, key),
                                      batches[0]))
    lowered, compiled = compile_step(plan, fn, mesh, state, batch)
    check("manual_computation" in lowered.as_text(),
          "sharded kernel sites lower through jax.shard_map")
    if on_tpu:
        check("tpu_custom_call" in compiled.as_text(),
              "compiled step holds a Mosaic kernel (tpu_custom_call)")
    got = []
    for i, batch in enumerate(batches):
        state, metrics, s = run_step(compiled, plan, mesh, state, batch)
        got.append(metrics)
        log(f"[2x2 step {i}] loss={metrics['loss']:.6f} {s * 1e3:.3f}ms")
    check_init_loss(cfg, got[0]["loss"])
    compare("2x2 vs one device", got, want,
            jax.device_get(state.opt.m), want_m)

    params = jax.tree_util.tree_leaves(state.params)
    check(all(len(p.sharding.device_set) == 4 for p in params),
          f"all {len(params)} parameter arrays live on 4 devices")
    split = [p for p in params
             if p.addressable_shards[0].data.shape != p.shape]
    spread = {s.device for p in split for s in p.addressable_shards}
    check(bool(split) and len(spread) == 4,
          f"{len(split)} of {len(params)} parameter arrays are split "
          f"over {len(spread)} devices")
    log(f"[memory] {memory_line(devices)}")


def main(argv: list[str] | None = None) -> None:
    """Run the chosen phase; the last stdout line is the JSON verdict."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 mesh phase")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced() config: a CPU rehearsal, exits 1 "
                         "off a TPU")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seq", type=int, default=1024)
    args = ap.parse_args(argv)

    dev = device_info()
    log(f"[device] {dev}")
    on_tpu = dev["platform"] == "tpu"
    if not on_tpu and not args.reduced:
        raise SystemExit(f"chip_smoke: no TPU (JAX found "
                         f"{dev['platform']}); --reduced rehearses the "
                         f"phases on it and still exits 1")
    if dev["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX found {dev['count']}")
    log(f"[compile cache] {enable_compile_cache()}")

    cfg = get_config("qwen2_05b")
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, use_pallas=True)
    shape = ShapeConfig("chip_smoke", args.seq, BATCH, "train")
    log(f"[config] {cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} params={cfg.num_params()} "
        f"dtype={cfg.param_dtype} batch={BATCH}x{args.seq}")

    if args.chips == 4:
        four_chip_phase(cfg, shape, args, on_tpu)
    else:
        one_chip_phase(cfg, shape, args, on_tpu)

    if not on_tpu:
        raise SystemExit(f"chip_smoke: phases passed on "
                         f"{dev['platform']}, which is not a TPU")
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
