import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT-lower + compile every (arch × shape × mesh) cell.

This proves the distribution config is coherent without hardware: the
16×16 single-pod mesh and the 2×16×16 multi-pod mesh must both compile for
every cell.  For each compile we record ``memory_analysis()`` (bytes per
device), ``cost_analysis()`` (FLOPs / bytes) and the collective traffic
parsed from the compiled HLO — the inputs to EXPERIMENTS.md §Dry-run and
§Roofline.

The XLA_FLAGS line above must precede every other import (JAX locks the
device count at first init) and is deliberately NOT set anywhere else —
smoke tests and benchmarks see the real single CPU device.

Usage::

    python -m repro.launch.dryrun --arch qwen2_05b --shape train_4k \
        --mesh single --plan manual
    python -m repro.launch.dryrun --all --out results/dryrun
"""

import argparse
import json
import pathlib
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, cells, get_config
from repro.core.cost_model import HardwareSpec
from repro.launch.mesh import (make_mesh, make_production_mesh,
                               production_mesh_spec)
from repro.launch.specs import specs_from_rules, step_and_inputs
from repro.models.sharding import (MANUAL_RULES, MANUAL_RULES_MULTIPOD,
                                   logical_rules)

HW = HardwareSpec()

def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             plan: str = "manual", toast_plan=None,
             backend: str = "mcts",
             overrides: dict | None = None,
             extra_rules: dict | None = None,
             smoke: bool = False) -> dict:
    """Lower + compile one cell; returns the recorded analysis.

    ``overrides`` are dataclasses.replace'd into the ModelConfig (perf
    hillclimbing knobs); ``extra_rules`` extend/override the logical
    sharding rules.  ``smoke`` runs the reduced config on a tiny
    (64-seq, batch-8) cell over a 2x4 mesh — the CI fast path that still
    exercises trace → plan → lower → compile end to end."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.reduced()
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    from repro.configs.base import ShapeConfig
    shape = ShapeConfig("mini", 64, 8, "train") if smoke \
        else SHAPES[shape_name]
    if smoke:
        mesh = make_mesh((2, 4), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    fn, args, names = step_and_inputs(cfg, shape)
    plan_meta = {}
    if plan == "toast":
        # run the staged TOAST pipeline on this cell's step
        from repro.api import Request, Session
        from repro.core.cost_model import MeshSpec
        from repro.core.mcts import MCTSConfig
        mesh_spec = MeshSpec(("data", "model"), (2, 4)) if smoke \
            else production_mesh_spec(multi_pod=multi_pod)
        search_config = None
        if backend == "mcts":
            search_config = MCTSConfig(rounds=10,
                                       trajectories_per_round=48)
        plan_obj = toast_plan or Session(fn, args).partition(Request(
            mesh=mesh_spec, backend=backend, search_config=search_config,
            logical_axes=names))
        rules = dict(plan_obj.logical_rules)
        flat_specs = [jax.sharding.NamedSharding(mesh, s)
                      for s in plan_obj.in_specs]
        treedef = jax.tree_util.tree_structure(args)
        in_shardings = jax.tree_util.tree_unflatten(treedef, flat_specs)
        plan_meta = {"toast_cost": plan_obj.cost,
                     "toast_search_s": round(plan_obj.search_seconds, 2),
                     "toast_evals": plan_obj.evaluations,
                     "toast_backend": plan_obj.backend,
                     "toast_eval_stats": plan_obj.eval_stats,
                     "toast_rules": {k: list(v) for k, v in rules.items()},
                     "toast_resolution_bits": plan_obj.num_resolution_bits}
    else:
        rules = dict(MANUAL_RULES_MULTIPOD if multi_pod else MANUAL_RULES)
        # FSDP: shard params' embed dim over data when the model is large
        if cfg.num_params() * 2 > HW.hbm_per_chip * 4:
            rules.setdefault("embed", ("data",))
        if extra_rules:
            rules.update(extra_rules)
        spec_tree = specs_from_rules(args, names, rules, axis_sizes)
        in_shardings = jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        with logical_rules(rules):
            lowered = jax.jit(fn, in_shardings=in_shardings).lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    if os.environ.get("REPRO_KEEP_HLO"):
        import gzip
        import pathlib as _pl
        hdir = _pl.Path(os.environ["REPRO_KEEP_HLO"])
        hdir.mkdir(parents=True, exist_ok=True)
        tagname = f"{arch}_{shape_name}_" \
                  f"{'multi' if multi_pod else 'single'}_{plan}" \
                  f"{os.environ.get('REPRO_HLO_TAG', '')}.hlo.gz"
        with gzip.open(hdir / tagname, "wt") as f:
            f.write(hlo)
    # loop-aware per-device totals (XLA's cost_analysis counts each while
    # body once — wrong by the layer count for scan-over-layers models)
    from repro.launch.hlo_analysis import summarize
    hs = summarize(hlo)
    coll = {k: float(v) for k, v in hs.coll_bytes.items()}

    n_dev = int(np.prod(mesh.devices.shape))
    flops = float(hs.flops)
    bytes_acc = float(hs.bytes_rw)
    coll_total = float(sum(coll.values()))
    record = {
        "arch": arch, "shape": "mini" if smoke else shape_name,
        "mesh": "2x4" if smoke else ("2x16x16" if multi_pod else "16x16"),
        "plan": plan,
        "num_devices": n_dev,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll_total,
        "collectives": coll,
        "while_trip_counts": hs.while_trips,
        "xla_flops_per_device_raw": float(ca.get("flops", 0.0)),
        "xla_bytes_per_device_raw": float(ca.get("bytes accessed", 0.0)),
        "arg_bytes_per_device": mem.argument_size_in_bytes,
        "temp_bytes_per_device": mem.temp_size_in_bytes,
        "output_bytes_per_device": mem.output_size_in_bytes,
        "peak_bytes_per_device": mem.argument_size_in_bytes +
        mem.temp_size_in_bytes + mem.output_size_in_bytes,
        # roofline terms (seconds) per the assignment's constants
        "t_compute": flops / HW.flops_per_chip,
        "t_memory": bytes_acc / HW.hbm_bw,
        "t_collective": coll_total / HW.ici_bw,
    }
    terms = {"compute": record["t_compute"], "memory": record["t_memory"],
             "collective": record["t_collective"]}
    record["bottleneck"] = max(terms, key=terms.get)
    record.update(plan_meta)
    return record


def model_flops(arch: str, shape_name: str) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); decode: per token."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.num_params()
    if cfg.num_experts:
        active_ratio = cfg.experts_per_token / cfg.num_experts
        moe_p = cfg.num_experts * 3 * cfg.d_model * cfg.d_ff * \
            len([k for k in cfg.pattern if k in ("attn", "local")])
        n = n - moe_p + moe_p * active_ratio
    tokens = shape.global_batch * (1 if shape.kind == "decode"
                                   else shape.seq_len)
    mult = 6 if shape.kind == "train" else 2
    return mult * n * tokens


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--plan", default="manual")
    ap.add_argument("--backend", default="mcts",
                    help="search backend for --plan toast "
                         "(mcts | beam | greedy)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on a 64-seq/batch-8 cell over a "
                         "2x4 mesh — the CI fast path")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="ModelConfig overrides, e.g. moe_dispatch=batch")
    ap.add_argument("--rule", action="append", default=[],
                    help="extra logical rules, e.g. vocab=model")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    if args.all:
        work = [(a, s.name) for a in ARCH_IDS for s in cells(a)]
    else:
        work = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if args.smoke:
        work = [(args.arch or "qwen2_05b", "mini")]
        meshes = [False]

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                v = {"true": True, "false": False}.get(v.lower(), v)
        overrides[k] = v
    extra_rules = {}
    for rv in args.rule:
        k, v = rv.split("=", 1)
        extra_rules[k] = tuple(v.split("+")) if v else ()

    failures = []
    for arch, shape_name in work:
        for multi in meshes:
            tag = f"{arch}_{shape_name}_{'multi' if multi else 'single'}_" \
                  f"{args.plan}"
            if args.tag:
                tag += f"_{args.tag}"
            path = outdir / f"{tag}.json"
            if path.exists() and not args.force:
                print(f"[skip] {tag}")
                continue
            print(f"[run ] {tag} ...", flush=True)
            try:
                rec = run_cell(arch, shape_name, multi_pod=multi,
                               plan=args.plan, backend=args.backend,
                               overrides=overrides or None,
                               extra_rules=extra_rules or None,
                               smoke=args.smoke)
                path.write_text(json.dumps(rec, indent=2))
                print(f"[ ok ] {tag}: peak/dev="
                      f"{rec['peak_bytes_per_device']/2**30:.2f}GiB "
                      f"bottleneck={rec['bottleneck']} "
                      f"compile={rec['compile_s']}s", flush=True)
            except Exception as e:               # noqa: BLE001
                failures.append((tag, repr(e)))
                (outdir / f"{tag}.FAIL").write_text(traceback.format_exc())
                print(f"[FAIL] {tag}: {e}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e[:200])
        raise SystemExit(1)
    print("\nall dry-run cells compiled OK")


if __name__ == "__main__":
    main()
