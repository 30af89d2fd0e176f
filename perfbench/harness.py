"""One run of one training cell: plan, compile, step, check.

The timed path is the program's own: the train step of
``make_train_step`` is traced and analysed by ``Session``, a plan is
searched by ``Session.partition`` (search seed from the traffic file, no
plan store), installed by ``ShardingPlan.apply`` and compiled once.  The
state is made on the device from ``--seed``, and the program's
``Pipeline`` feeds the batches.

Set-up then drives that same compiled step and state through its first
``checked_steps`` steps with the window's own call and feed, and keeps
what the comparison needs from them: each step's loss, the per-leaf norm
of the first gradient as the optimizer got it (its first moment after
one step over ``1 - b1``), and the per-leaf norm of the parameters'
change over those steps.  The window goes on from there with the same
state and pipeline.  Once it has closed and the program's state is
freed, the plain reference (``model_ref``) runs the same steps from its
own weights and tokens, and the gaps between the two decide
``correct``.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import glob
import hashlib
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from perfbench import catalog, model_ref, peaks, tracefile, traffic

AXES = ("data", "model")
STEP_SPAN = "train"
HOST_SPANS = ("next_batch", "dispatch", "block")
# leaves whose reference gradient is under this share of the median
# leaf's are nought to rounding (a key bias under softmax): Adam moves
# them by round-off alone, so their change is not compared
MOVED_SHARE = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def process_start() -> float:
    """When this process started, on the ``time.time()`` clock."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    age = uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.time() - age


def seed_key(seed: int):
    """The PRNG key of ``--seed``: all of its bits count, not only the
    low 32 that ``PRNGKey`` keeps."""
    key = jax.random.PRNGKey(seed % 2**32)
    return jax.random.fold_in(key, (seed // 2**32) % 2**32)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` already puts it.

    The program's ``launch.compile_cache`` keeps the same rule; this
    copy keeps the path the benchmark's own and also caches small
    programs (thresholds 0), so that a run after the first compiles
    nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(catalog.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs import get_config
    run = conf["run"]
    if float(conf["rms_norm_eps"]) != 1e-6 or run["z_loss"] != 1e-4:
        raise ValueError("the program runs RMSNorm eps 1e-6 and z-loss "
                         "1e-4 only")
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return dataclasses.replace(
        get_config(run["program_config"]),
        num_layers=conf["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf.get("head_dim") or d // h,
        rope_theta=float(conf["rope_theta"]), qkv_bias=run["qkv_bias"],
        mlp="swiglu", sliding_window=0, param_dtype=run["param_dtype"],
        remat=run["remat"] != "none", remat_policy=run["remat"],
        use_pallas=run["use_pallas"])


def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


def diff_norms(a, b):
    return leaf_norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


class TrainCell:
    """The planned, compiled train step of one cell and its state."""

    def __init__(self, conf: dict, mix: dict, devices, *, make_step=None):
        from repro.api import Request, Session
        from repro.configs.base import ShapeConfig
        from repro.core.cost_model import MeshSpec
        from repro.core.mcts import MCTSConfig
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import batch_specs, state_logical_axes
        from repro.models.sharding import logical_rules
        from repro.optim.adam import AdamConfig
        from repro.train.steps import make_train_step, train_state_specs

        if mix["kind"] != "train":
            raise ValueError(f"traffic {mix['name']}: this harness runs "
                             f"training cells only, not {mix['kind']!r}")
        self.mix = mix
        self.cfg = model_config(conf)
        self.opt = AdamConfig(**conf["run"]["optimizer"])
        self.shape = ShapeConfig(mix["name"], mix["seq_len"], mix["batch"],
                                 "train")
        self.tokens_per_step = mix["batch"] * mix["seq_len"]
        fn = (make_step or make_train_step)(self.cfg, self.opt)
        state_sds = train_state_specs(self.cfg, self.opt)
        bspecs, bnames = batch_specs(self.cfg, self.shape)
        names = (state_logical_axes(self.cfg, state_sds), bnames)
        search = mix["search"]
        t0 = time.perf_counter()
        sess = Session(fn, (state_sds, bspecs))
        self.plan = plan = sess.partition(Request(
            mesh=MeshSpec(AXES, tuple(mix["mesh"])), logical_axes=names,
            backend=search["backend"],
            search_config=(MCTSConfig(seed=search["seed"])
                           if search["backend"] == "mcts" else None)))
        self.plan_s = time.perf_counter() - t0
        self.phase_seconds = dict(sess.artifacts.phase_seconds)
        self.mesh = make_mesh(tuple(mix["mesh"]), AXES, devices=devices)
        treedef = jax.tree_util.tree_structure((state_sds, bspecs))
        self.state_sh, self.batch_sh = plan.jax_in_shardings(self.mesh,
                                                             treedef)
        sds = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            (state_sds, bspecs), (self.state_sh, self.batch_sh))
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh), \
                logical_rules(plan.logical_rules or None):
            lowered = plan.apply(fn, self.mesh, donate_argnums=0).lower(*sds)
        self.compiled = lowered.compile()
        self.compile_s = time.perf_counter() - t0
        # sharded kernel sites lower through jax.shard_map
        self.kernels_shard_mapped = "manual_computation" in lowered.as_text()
        from repro.models import transformer
        from repro.optim import adam
        self.init_params = jax.jit(
            functools.partial(transformer.init_params, self.cfg),
            out_shardings=self.state_sh.params)
        self.init_opt = jax.jit(functools.partial(adam.init, self.opt),
                                out_shardings=self.state_sh.opt)
        self.leaf_norms = jax.jit(leaf_norms)
        self.diff_norms = jax.jit(diff_norms)
        self.state = self.pipe = None

    # -- evidence -----------------------------------------------------------

    def plan_evidence(self) -> dict:
        p = self.plan
        text = json.dumps({"rules": {k: list(v) for k, v in
                                     sorted(p.logical_rules.items())},
                           "sites": [[r["site"], r["impl"], r["sharded"]]
                                     for r in p.kernel_sites]},
                          sort_keys=True)
        return {"backend": p.backend, "cost": p.cost,
                "evaluations": p.evaluations,
                "search_s": p.search_seconds,
                "plan_hash": hashlib.sha256(text.encode()).hexdigest()[:16],
                "rules": p.logical_rules,
                "kernel_sites": [[r["site"], r["impl"], r["sharded"]]
                                 for r in p.kernel_sites]}

    def memory_analysis(self) -> dict:
        ma = self.compiled.memory_analysis()
        out = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")}
        out["step_bytes"] = (out["argument_size_in_bytes"] +
                             out["temp_size_in_bytes"] +
                             out["output_size_in_bytes"] -
                             out["alias_size_in_bytes"])
        return out

    # -- the timed path -----------------------------------------------------

    def start(self, seed: int) -> None:
        """A fresh state from ``seed`` on the device, and the pipeline."""
        from repro.data.pipeline import DataConfig, Pipeline
        from repro.train.steps import TrainState
        params = self.init_params(seed_key(seed))
        self.state = TrainState(params, self.init_opt(params))
        self.pipe = Pipeline(self.cfg, self.shape,
                             DataConfig(seed=seed,
                                        prefetch=self.mix["prefetch"]))

    def step(self):
        """One step of the window: the next batch, the compiled step on
        it, and the wait for its end."""
        with jax.profiler.TraceAnnotation("next_batch"):
            _, batch = next(self.pipe)
            batch = jax.device_put(batch, self.batch_sh)
        with jax.profiler.TraceAnnotation("dispatch"):
            self.state, metrics = self.compiled(self.state, batch)
        with jax.profiler.TraceAnnotation("block"):
            jax.block_until_ready((self.state, metrics))
        return metrics

    def first_steps(self, seed: int) -> dict:
        """The checked steps through the window's call and feed, and
        what the comparison reads from them."""
        names = model_ref.leaf_names(self.state.params)
        losses, grad = [], None
        for i in range(self.mix["checked_steps"]):
            metrics = self.step()
            losses.append(float(metrics["loss"]))
            if i == 0:
                scale = 1.0 / (1.0 - self.opt.b1)
                grad = [float(x) * scale
                        for x in self.leaf_norms(self.state.opt.m)]
        p0 = self.init_params(seed_key(seed))
        change = [float(x) for x in self.diff_norms(self.state.params, p0)]
        del p0
        return {"loss": losses, "grad": dict(zip(names, grad)),
                "change": dict(zip(names, change))}

    def window(self, seconds: float) -> tuple[int, float]:
        steps, t0 = 0, time.perf_counter()
        while True:
            with jax.profiler.StepTraceAnnotation(STEP_SPAN, step_num=steps):
                self.step()
            steps += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                return steps, elapsed

    def stop(self) -> None:
        """Free the program's state and stop its pipeline."""
        if self.pipe is not None:
            self.pipe.close()
        self.state = self.pipe = None
        gc.collect()


def reference(conf: dict, mix: dict, seed: int, devices,
              precision: str = "f32", rows=None, causal=True) -> dict:
    """The reference's readings for ``seed``'s first checked steps.

    ``rows`` keeps only the first rows of every batch, and
    ``causal=False`` drops the attention mask: planted faults."""
    v = conf["vocab_size"]
    batches = []
    for k in range(mix["checked_steps"]):
        tok, tgt = traffic.batch(seed, k, mix["batch"], mix["seq_len"], v)
        batches.append((tok[:rows], tgt[:rows]))
    ref = mix["reference"]
    mesh = Mesh(np.array(devices), ("x",))
    return model_ref.reference_readings(
        conf, batches, seed_key(seed), mesh=mesh, precision=precision,
        rows_per_block=min(ref["rows_per_block"], rows or mix["batch"]),
        q_block=ref["q_block"], causal=causal)


def gaps(got: dict, want: dict) -> dict:
    """The numbers compared: the largest loss gap over the checked
    steps, and by the worst leaf the gap between the two sides' norms
    of the first gradient and of the parameters' change, each over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  Leaves that the reference does not move (gradient under
    ``MOVED_SHARE`` of the median leaf's) are left out of the change."""
    loss = max(abs(a - b) for a, b in zip(got["loss"], want["loss"]))
    g_med = statistics.median(want["grad"].values())
    grad = max(abs(got["grad"][k] - w) / max(w, g_med)
               for k, w in want["grad"].items())
    moved = [k for k, w in want["grad"].items() if w >= MOVED_SHARE * g_med]
    c_med = statistics.median(want["change"][k] for k in moved)
    change = max(abs(got["change"][k] - want["change"][k]) /
                 max(want["change"][k], c_med) for k in moved)
    worst = {"grad": max(want["grad"], key=lambda k: abs(
                 got["grad"][k] - want["grad"][k]) /
                 max(want["grad"][k], g_med)),
             "change": max(moved, key=lambda k: abs(
                 got["change"][k] - want["change"][k]) /
                 max(want["change"][k], c_med))}
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change,
            "worst_leaf": worst,
            "unmoved": sorted(set(want["grad"]) - set(moved))}


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def read_trace(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    devs, host = tracefile.planes_from_profile(
        ProfileData.from_file(max(files, key=os.path.getmtime)))
    return tracefile.reduce_trace(devs, host, window_span=STEP_SPAN,
                                  host_spans=HOST_SPANS)


def run(args, *, require_tpu: bool = True, overrides=None,
        make_step=None) -> dict:
    """One run of the cell ``args.workload``; returns the result line.

    ``require_tpu``, ``overrides`` (a function of the configuration and
    traffic dicts) and ``make_step`` exist for the tests, which drive
    the harness on the CPU at a small size and with planted faults.
    """
    t_start = process_start()
    bench = catalog.benchmark()
    cell = catalog.workload(bench, args.workload)
    conf = catalog.config(bench, cell["config"])
    mix = catalog.traffic(cell["traffic"])
    limits = catalog.limits(args.workload)
    if overrides is not None:
        conf, mix = overrides(conf, mix)
    devices = jax.devices()
    dev = device_info(devices[:cell["chips"]])
    log(f"[device] {dev} (JAX sees {len(devices)})")
    if require_tpu and dev["platform"] != "tpu":
        raise SystemExit(f"perfbench: no TPU (JAX found {dev['platform']})")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"perfbench: {args.workload} needs {cell['chips']} "
                         f"chips, JAX found {len(devices)}")
    devices = devices[:cell["chips"]]
    peak = peaks.peaks_for(dev["kind"]) if require_tpu else None
    log(f"[compile cache] {enable_compile_cache()}")
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if "backend_compile" in name else None)

    tc = TrainCell(conf, mix, devices, make_step=make_step)
    ev = tc.plan_evidence()
    log(f"[plan] backend={ev['backend']} cost={ev['cost']:.6f} "
        f"evaluations={ev['evaluations']} search_s={ev['search_s']:.3f} "
        f"plan_s={tc.plan_s:.3f} hash={ev['plan_hash']}")
    log(f"[plan] rules={ev['rules']} kernel_sites={ev['kernel_sites']}")
    log(f"[plan] phases={tc.phase_seconds} kernel sites through "
        f"shard_map: {tc.kernels_shard_mapped}")
    mem = tc.memory_analysis()
    limit_bytes = min(int((d.memory_stats() or {}).get("bytes_limit", 0))
                      for d in devices)
    log(f"[compile] lower+compile {tc.compile_s:.3f}s; memory_analysis "
        f"{mem}; bytes_limit {limit_bytes}")

    tc.start(args.seed)
    got = tc.first_steps(args.seed)
    log(f"[steps] checked losses {got['loss']}")
    setup_s = time.time() - t_start
    log(f"[setup] {setup_s:.3f}s")

    trace_dir = None
    n_compiles = len(compiles)
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        jax.profiler.start_trace(trace_dir)
    try:
        steps, window_s = tc.window(args.seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    in_window = len(compiles) - n_compiles
    tokens_per_s = steps * tc.tokens_per_step / window_s
    mem_peak = peak_bytes(devices)
    log(f"[window] {steps} steps in {window_s:.3f}s: "
        f"{tokens_per_s:.1f} tokens/s; compiles inside the window: "
        f"{in_window}; peak_bytes_in_use {mem_peak}")
    tc.stop()

    t0 = time.perf_counter()
    want = reference(conf, mix, args.seed, devices)
    log(f"[reference] {time.perf_counter() - t0:.3f}s; losses "
        f"{want['loss']}")
    g = gaps(got, want)
    log(f"[compare] worst leaves {g['worst_leaf']}; left out of the "
        f"change (unmoved in the reference): {g['unmoved']}")
    checks = {k: {"value": g[k], "limit": limits[k]}
              for k in ("loss_gap", "grad_gap", "change_gap")}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and \
        all(math.isfinite(x) for x in got["loss"])

    record = {"cell": args.workload, "config": conf, "traffic": mix,
              "chips": len(devices), "device_kind": dev["kind"],
              "peak": peak, "tokens_per_s": tokens_per_s, "steps": steps,
              "window_s": window_s, "plan_s": tc.plan_s,
              "phase_seconds": tc.phase_seconds,
              "search_seconds": tc.plan.search_seconds,
              "compile_s": tc.compile_s, "setup_s": setup_s,
              "memory": mem, "bytes_limit": limit_bytes,
              "kernel_sites": tc.plan.kernel_sites, "trace": None}
    device = dict(dev, memory_peak_bytes=mem_peak)
    result = {"correct": bool(correct), "attempted": steps, "failed": 0}
    if args.trace:
        record["trace"] = tr = read_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"[trace] {tr['steps']} steps in {tr['window_s']:.3f}s; busy "
            f"{tr['busy_s']}; collectives {tr['collective_s']} (exposed "
            f"{tr['collective_exposed_s']})")
        for name, evs in tr["custom_calls"].items():
            shapes = sorted({s for s, _ in evs})
            log(f"[trace] kernel {name}: {len(evs)} events, "
                f"{sum(d for _, d in evs):.6f}s, shapes {shapes}")
        if tr["busy_s"]:
            device["busy_s"] = statistics.mean(tr["busy_s"])
            device["window_s"] = tr["window_s"]
        kind = "per_layer"
    else:
        kind = "end_to_end"
    values = {"train_tokens_per_s": tokens_per_s, "plan_s": tc.plan_s,
              "setup_s": setup_s}
    metrics = {}
    for m in catalog.metrics_for(bench, args.workload, kind):
        if kind == "end_to_end":
            value = values[m["name"]]
        else:
            reader = catalog.load_module(m["name"])
            value = reader.read(record)
            if hasattr(reader, "note"):
                log(f"[metrics] {m['name']}: {reader.note(record)}")
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    if args.trace:
        result["breakdown"] = record["trace"]["breakdown"]
        log(f"[metrics] {metrics}")
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result
