"""Benchmark harness — one section per paper figure/table.

Prints ``name,us_per_call,derived`` CSV rows (per the repo convention).
Hardware is the abstract TPU v5e of the roofline spec; "step time" rows
are cost-model estimates (this container has no accelerator), search-time
rows are real wall-clock.

Sections:
- fig8:   partitioned step-time estimates, TOAST vs unsharded / manual /
          AutoMap-like / unpruned-random (≈ Alpa search-space), per model.
- fig9:   auto-sharding search time (wall-clock) + cost-model evaluations.
- fig10:  T2B sequence-length and device scaling.
- nda:    static-analysis latency per model (scalability claim §5.3).
- search: cost-evaluation throughput, dense seed path vs the incremental
          engine (writes BENCH_search.json) — scalability claim §5.3.
- zoo:    zoo-wide portfolio auto-partitioning sweep over every config in
          repro/configs (writes BENCH_zoo.json) — the paper's "diverse
          model architectures" claim.
- measure: measured execution of plan variants on a simulated device
          mesh + cost-model calibration (writes BENCH_measured.json) —
          the predict→measure→calibrate loop of docs/measure.md.
- meshsearch: mesh-shape co-search over a device budget — winner vs the
          best fixed 2-D mesh per smoke model (writes
          BENCH_meshsearch.json); opt-in, searches every candidate mesh.
- fullscale: production llama3_405b / mixtral_8x22b programs on an 8x4
          mesh — per-phase analysis time, dense vs incremental
          evals/sec, real search, vectorized-analysis exactness oracle
          (writes BENCH_fullscale.json); opt-in, minutes of wall time.
- kernels: Pallas kernel microbenchmarks (interpret mode) vs jnp oracle;
          as an explicit section it also runs the kernel-aware
          partitioning benchmark — fused-op trace, joint kernel+sharding
          search, per-kernel calibration, measured fused-vs-decomposed
          execution (writes BENCH_kernels.json, see docs/kernels.md).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.core.cost_model import HardwareSpec, MeshSpec
from repro.core.mcts import MCTSConfig

MESH = MeshSpec(("data", "model"), (16, 16))
HW = HardwareSpec()
VARIANTS = ("unsharded", "manual", "automap", "random_unpruned", "toast")
MODELS = ("t2b", "t7b", "gns", "unet", "itx")


def _row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)


def fig8_and_9(models=MODELS, budget=None):
    budget = budget or MCTSConfig(rounds=8, trajectories_per_round=32)
    for model in models:
        art, names = common.artifacts_for(model)
        for variant in VARIANTS:
            r = common.run_variant(variant, art, names, MESH, HW,
                                   mcts_cfg=budget)
            _row(f"fig8.step_time.{model}.{variant}",
                 r.runtime_est * 1e6,
                 f"cost={r.cost:.4f};peak_gb={r.peak_gb:.2f};"
                 f"oom={int(r.oom)}")
            if variant in ("toast", "automap", "random_unpruned"):
                _row(f"fig9.search_time.{model}.{variant}",
                     r.search_s * 1e6, f"evaluations={r.evaluations}")


def fig10_scaling():
    for seq, mesh in ((8192, MeshSpec(("data", "seq", "model"), (2, 16, 2))),
                      (16384, MeshSpec(("data", "seq", "model"), (2, 16, 2))),
                      (32768, MeshSpec(("data", "seq", "model"),
                                       (2, 32, 2)))):
        art, names = common.artifacts_for("t2b", seq=seq, batch=8)
        for variant in ("manual", "toast"):
            r = common.run_variant(variant, art, names, mesh, HW,
                                   mcts_cfg=MCTSConfig(rounds=6))
            _row(f"fig10.t2b.seq{seq}.dev{mesh.num_devices}.{variant}",
                 r.runtime_est * 1e6,
                 f"cost={r.cost:.4f};peak_gb={r.peak_gb:.2f};"
                 f"oom={int(r.oom)};search_s={r.search_s:.2f}")


def nda_latency():
    for model in MODELS:
        t0 = time.perf_counter()
        art, _ = common.artifacts_for(model)
        t = time.perf_counter() - t0
        _row(f"nda.analysis.{model}", t * 1e6,
             f"ops={len(art.prog.ops)};colors={len(art.nda.color_summary())};"
             f"conflicts={len(art.analysis.conflicts)};"
             f"compat_sets={len(art.analysis.compat_sets)};"
             f"bits={art.analysis.num_resolution_bits}")


def zoo_sweep(out="BENCH_zoo.json", mesh="4x2", plan_store=None):
    import json
    import pathlib

    from repro.launch import zoo
    store = None
    if plan_store:
        from repro.ckpt.plan_store import PlanStore
        store = PlanStore(plan_store)
    record = zoo.run_zoo(zoo.parse_mesh(mesh), plan_store=store,
                         verbose=False)
    for r in record["results"]:
        if r["status"] != "ok":
            _row(f"zoo.{r['model']}.ERROR", 0.0, r["error"][:80])
            continue
        _row(f"zoo.{r['model']}", r["search_s"] * 1e6,
             f"cost={r['cost']:.4f};feasible={int(r['feasible'])};"
             f"speedup={r['speedup']};evals={r['evaluations']};"
             f"winner={r['winner']};cached={int(r['cached'])}")
    pathlib.Path(out).write_text(json.dumps(record, indent=2))


def measure_sweep(out="BENCH_measured.json", mesh="2x2",
                  plan_store=None, repeats=3):
    import json
    import pathlib

    from repro.launch import measure as lmeasure
    from repro.launch import zoo
    store = None
    if plan_store:
        from repro.ckpt.plan_store import PlanStore
        store = PlanStore(plan_store)
    captures = {}
    record = zoo.run_zoo(zoo.parse_mesh(mesh), archs=zoo.SMOKE_ARCHS,
                         shape=zoo.ZOO_SHAPE_SMOKE, plan_store=store,
                         verbose=False, captures=captures)
    mrec = lmeasure.measure_record(record, captures, repeats=repeats,
                                   warmup=1, plan_store=store,
                                   verbose=False)
    for c in mrec["cells"]:
        peak = c["measured_peak_bytes"]
        peak_mb = f"{peak / 2**20:.1f}" if peak is not None else "?"
        _row(f"measure.{c['model']}.{c['plan_label']}",
             c["measured_s"] * 1e6,
             f"status={c['status']};pred_us={c['predicted_s'] * 1e6:.1f};"
             f"cal_us={c['predicted_calibrated_s'] * 1e6:.1f};"
             f"peak_mb={peak_mb}")
    cal = mrec["calibration"]
    if "mean_rel_err_before" in cal:
        _row("measure.calibration", cal["mean_rel_err_after"] * 1e6,
             f"err_before={cal['mean_rel_err_before']:.3f};"
             f"err_after={cal['mean_rel_err_after']:.3f};"
             f"n={cal['n_cells']}")
    if mrec["spearman_mean"] is not None:
        _row("measure.spearman", mrec["spearman_mean"] * 1e6,
             ";".join(f"{m}={v['spearman']:.2f}"
                      for m, v in mrec["per_model"].items()
                      if v["spearman"] is not None))
    pathlib.Path(out).write_text(json.dumps(mrec, indent=2))


def meshsearch_sweep(out="BENCH_meshsearch.json", devices=16,
                     plan_store=None):
    import json
    import pathlib

    from repro.launch import zoo
    store = None
    if plan_store:
        from repro.ckpt.plan_store import PlanStore
        store = PlanStore(plan_store)
    record = zoo.run_cosearch(devices, archs=zoo.SMOKE_ARCHS,
                              shape=zoo.ZOO_SHAPE_SMOKE,
                              plan_store=store, verbose=False)
    for r in record["results"]:
        if r["status"] != "ok" or r["winner"] is None:
            _row(f"meshsearch.{r['model']}.ERROR", 0.0,
                 str(r.get("error", "no winner"))[:80])
            continue
        w = r["winner"]
        _row(f"meshsearch.{r['model']}", r["cosearch_s"] * 1e6,
             f"winner={w['mesh_str']};cost={w['cost']:.4f};"
             f"best_fixed={r['best_fixed']['mesh_str']};"
             f"fixed_cost={r['best_fixed']['cost']:.4f};"
             f"ties_or_beats={int(r['ties_or_beats_fixed'])};"
             f"candidates={len(r['candidates'])}")
    pathlib.Path(out).write_text(json.dumps(record, indent=2))
    if record["failures"]:
        raise SystemExit("; ".join(record["failures"]))


def kernel_micro():
    from repro.kernels import ops, ref
    key = jax.random.PRNGKey(0)
    B, H, S, hd = 1, 4, 512, 64
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hd))

    def timeit(f, n=3):
        jax.block_until_ready(f())
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(f())
        return (time.perf_counter() - t0) / n

    t_flash = timeit(lambda: ops.gqa_flash_attention(q, k, v))
    _row("kernel.flash_attention.interpret", t_flash * 1e6,
         f"B{B}H{H}S{S}hd{hd}")
    a = jax.nn.sigmoid(jax.random.normal(key, (2, 1024, 256)))
    b = jax.random.normal(jax.random.fold_in(key, 3), (2, 1024, 256))
    t_lru = timeit(lambda: ops.rg_lru(a, b))
    _row("kernel.rg_lru.interpret", t_lru * 1e6, "B2S1024R256")
    t_ref = timeit(lambda: ref.reference_rg_lru(a, b))
    _row("kernel.rg_lru.jnp_oracle", t_ref * 1e6, "B2S1024R256")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default="all",
                    choices=["all", "fig8", "fig10", "nda", "search",
                             "zoo", "measure", "meshsearch", "fullscale",
                             "kernels"])
    ap.add_argument("--models", default=",".join(MODELS))
    ap.add_argument("--search-out", default="BENCH_search.json")
    ap.add_argument("--zoo-out", default="BENCH_zoo.json")
    ap.add_argument("--zoo-mesh", default="4x2")
    ap.add_argument("--zoo-plan-store", default="",
                    help="optional plan-store dir for the zoo section")
    ap.add_argument("--measure-out", default="BENCH_measured.json")
    ap.add_argument("--measure-mesh", default="2x2",
                    help="simulated mesh for the measure section")
    ap.add_argument("--meshsearch-out", default="BENCH_meshsearch.json")
    ap.add_argument("--meshsearch-devices", type=int, default=16,
                    help="device budget for the meshsearch section")
    ap.add_argument("--fullscale-out", default="BENCH_fullscale.json")
    ap.add_argument("--fullscale-mesh", default="8x4",
                    help="mesh for the fullscale section")
    ap.add_argument("--fullscale-smoke", action="store_true",
                    help="fullscale CI mode: analyze one config, no "
                         "search, enforce oracle + baseline gates")
    ap.add_argument("--kernels-out", default="BENCH_kernels.json")
    ap.add_argument("--kernels-no-measure", action="store_true",
                    help="kernels section: skip the measured-execution "
                         "subprocesses (static record only)")
    args = ap.parse_args()
    models = tuple(args.models.split(","))
    print("name,us_per_call,derived")
    if args.section in ("all", "fig8"):
        fig8_and_9(models)
    if args.section in ("all", "fig10"):
        fig10_scaling()
    if args.section in ("all", "nda"):
        nda_latency()
    if args.section in ("all", "search"):
        from benchmarks import search_throughput
        search_throughput.run(out=args.search_out)
    if args.section in ("all", "zoo"):
        zoo_sweep(out=args.zoo_out, mesh=args.zoo_mesh,
                  plan_store=args.zoo_plan_store or None)
    if args.section == "measure":       # opt-in: executes real programs
        measure_sweep(out=args.measure_out, mesh=args.measure_mesh,
                      plan_store=args.zoo_plan_store or None)
    if args.section == "meshsearch":    # opt-in: searches many meshes
        meshsearch_sweep(out=args.meshsearch_out,
                         devices=args.meshsearch_devices,
                         plan_store=args.zoo_plan_store or None)
    if args.section == "fullscale":     # opt-in: production-size configs
        from benchmarks import fullscale
        fullscale.run(out=args.fullscale_out, mesh=args.fullscale_mesh,
                      smoke=args.fullscale_smoke)
    if args.section in ("all", "kernels"):
        kernel_micro()
    if args.section == "kernels":       # opt-in: executes real programs
        from benchmarks import kernels
        kernels.run(out=args.kernels_out,
                    measure=not args.kernels_no_measure)


if __name__ == "__main__":
    main()
