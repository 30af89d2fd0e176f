"""Measured-execution launcher: run ShardingPlans on simulated meshes.

Every number the zoo reports without this module is a *predicted* cost.
Here a plan is actually executed: the worker half of this module runs in
a subprocess whose ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
gives JAX ``N`` simulated CPU devices (subprocess isolation is mandatory
— JAX locks the device count at first backend init, and different cells
need different counts), materializes the plan via ``plan.apply(fn)``,
AOT-compiles it, records compiled peak memory from
``memory_analysis()``, and times warmup + median-of-k executions.

The parent half drives a zoo sweep's plans through the worker
(:func:`measure_record`), computes Spearman rank correlation between the
predicted and measured orderings per model, fits the
``HardwareSpec`` roofline coefficients to the measurements
(``repro.core.measure.fit_hardware``), re-costs every cell under the
calibrated hardware *without re-analysis* (``CostModel.with_hardware``),
and persists the calibrated spec through the plan store
(``PlanStore.save_hardware``) so later searches can price with it.

Simulated-mesh caveat: all "devices" share the host's cores, so absolute
times are not accelerator times — rank correlation and calibrated-model
error are the meaningful outputs (see ``docs/measure.md``).  For the same
reason the parent half refuses to run in a process whose backend is a
TPU.

Usage::

    python -m repro.launch.zoo --mesh 2x2 --measure --smoke
    python -m repro.launch.measure --worker < job.json   # internal
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import statistics
import subprocess
import sys
import time

MARKER = "MEASURE_RESULT_JSON:"
_FORCE_FLAG = re.compile(r"--xla_force_host_platform_device_count=\d+")


# -- worker half (runs inside the subprocess) --------------------------------

def _classify(exc: BaseException) -> str:
    msg = f"{type(exc).__name__}: {exc}"
    if "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg \
            or "out of memory" in msg:
        return "oom"
    return "error"


def run_worker_job(job: dict) -> dict:
    """Execute one measurement job (already inside the forced-device env).

    Args:
        job: ``{"arch", "shape": {...}, "reduced", "plan":
            ShardingPlan.as_dict(), "repeats", "warmup"}``; optional
            ``"mode": "hlo"`` stops after lower+compile and returns the
            compiled module's collective traffic
            (``repro.launch.hlo_analysis``) instead of timing runs;
            optional ``"use_pallas": true`` routes the model through the
            fused kernel entry points, so the plan's ``kernel_sites``
            decisions govern execution (docs/kernels.md).

    Returns:
        A JSON-friendly result dict; ``result["status"]`` is "ok",
        "oom", "compile_error", or "error".
    """
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.partitioner import ShardingPlan
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import step_and_inputs

    plan = ShardingPlan.from_dict(job["plan"])
    need = plan.mesh.num_devices
    have = len(jax.devices())
    result: dict = {"devices": have, "status": "ok", "error": ""}
    if have < need:
        result.update(status="error",
                      error=f"plan needs {need} devices, worker has {have} "
                            f"(XLA_FLAGS not applied before jax init?)")
        return result

    cfg = get_config(job["arch"])
    if job.get("reduced", True):
        cfg = cfg.reduced()
    if job.get("use_pallas"):
        import dataclasses
        cfg = dataclasses.replace(cfg, use_pallas=True)
    s = job["shape"]
    shape = ShapeConfig(s.get("name", "measure"), s["seq_len"],
                        s["global_batch"], s["kind"])
    fn, args, _ = step_and_inputs(cfg, shape)
    mesh = make_mesh(plan.mesh.sizes, plan.mesh.axes)
    applied = plan.apply(fn, mesh)

    t0 = time.perf_counter()
    try:
        # trace under the ambient mesh + the plan's logical rules so the
        # models' ``constrain`` hooks pin *intermediate* shardings to the
        # plan's internal assignment — without them GSPMD propagates the
        # body from the in/out shardings alone and can diverge from the
        # plan (and from the predicted collective multiset)
        from repro.models.sharding import logical_rules
        with jax.set_mesh(mesh), \
                logical_rules(plan.logical_rules or None):
            lowered = applied.lower(*args)
        compiled = lowered.compile()
    except Exception as e:                          # noqa: BLE001
        status = _classify(e)
        result.update(status="compile_error" if status == "error"
                      else status, error=repr(e)[:500])
        return result
    result["compile_s"] = round(time.perf_counter() - t0, 3)

    try:
        mem = compiled.memory_analysis()
        result["arg_bytes"] = mem.argument_size_in_bytes
        result["temp_bytes"] = mem.temp_size_in_bytes
        result["out_bytes"] = mem.output_size_in_bytes
        result["peak_bytes"] = (mem.argument_size_in_bytes +
                                mem.temp_size_in_bytes +
                                mem.output_size_in_bytes)
    except Exception:                               # noqa: BLE001
        result["peak_bytes"] = None                 # analysis unavailable

    if job.get("mode") == "hlo":
        # conformance harvest: parse the compiled module's collective
        # traffic (loop-aware) and return — no timed execution
        from repro.launch.hlo_analysis import summarize, top_collectives
        text = compiled.as_text()
        s = summarize(text)
        result["coll_bytes"] = s.coll_bytes
        result["unknown_dtypes"] = list(s.unknown_dtypes)
        result["while_trips"] = s.while_trips
        result["hlo_flops"] = s.flops
        result["hlo_bytes_rw"] = s.bytes_rw
        result["top_collectives"] = [list(t) for t in
                                     top_collectives(text)]
        return result

    # concrete inputs: zeros everywhere (runtime arguments, so XLA cannot
    # constant-fold them; tokens index row 0 of the embedding table)
    concrete = jax.tree_util.tree_map(
        lambda sd: np.zeros(sd.shape, sd.dtype), args,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    try:
        for _ in range(max(1, int(job.get("warmup", 1)))):
            jax.block_until_ready(applied(*concrete))
        runs = []
        for _ in range(max(1, int(job.get("repeats", 5)))):
            t0 = time.perf_counter()
            jax.block_until_ready(applied(*concrete))
            runs.append(time.perf_counter() - t0)
    except Exception as e:                          # noqa: BLE001
        result.update(status=_classify(e), error=repr(e)[:500])
        return result
    result["runs_s"] = runs
    result["measured_s"] = statistics.median(runs)
    return result


def _worker_main() -> None:
    job = json.load(sys.stdin)
    try:
        result = run_worker_job(job)
    except Exception as e:                          # noqa: BLE001
        import traceback
        result = {"status": "error", "error": repr(e)[:500],
                  "traceback": traceback.format_exc(limit=8)}
    sys.stdout.write("\n" + MARKER + json.dumps(result) + "\n")
    sys.stdout.flush()


# -- parent half -------------------------------------------------------------

def _refuse_on_chip() -> None:
    """Refuse to start a CPU worker from a process that holds a TPU.

    The worker is the simulated-mesh CPU rehearsal.  Started from a
    process on the chip it would report CPU timings as if they were the
    chip's, and a child cannot share the chip its parent holds.
    """
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "repro.launch.measure executes plans on simulated CPU devices "
            "in a child process; this process holds a TPU, so its numbers "
            "would be CPU numbers.  Measure in-process on the chip instead.")


def _worker_env(num_devices: int) -> dict:
    env = dict(os.environ)
    flags = _FORCE_FLAG.sub("", env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (f"{flags} --xla_force_host_platform_device_count="
                        f"{num_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    import repro
    # repro is a namespace package: locate its parent via __path__
    src = str(pathlib.Path(next(iter(repro.__path__))).resolve().parent)
    pp = env.get("PYTHONPATH", "")
    if src not in pp.split(os.pathsep):
        env["PYTHONPATH"] = f"{src}{os.pathsep}{pp}" if pp else src
    return env


def measure_plan(arch: str, shape, plan, *, reduced: bool = True,
                 repeats: int = 5, warmup: int = 1,
                 timeout: float = 900.0,
                 use_pallas: bool = False) -> dict:
    """Measure one plan in a fresh simulated-mesh subprocess.

    Args:
        arch: zoo config id (the worker rebuilds the step function from
            it, so the plan's input specs line up by construction).
        shape: ``ShapeConfig`` (or a dict with ``seq_len`` /
            ``global_batch`` / ``kind``) of the traced cell.
        plan: the ``ShardingPlan`` to execute; its mesh's device count
            sets ``--xla_force_host_platform_device_count``.
        reduced: run the ``reduced()`` (CPU-smoke) config.
        repeats: timed executions (the median is reported).
        warmup: untimed executions before the timed ones.
        timeout: subprocess wall-clock budget, seconds.
        use_pallas: route the worker's model through the fused kernel
            entry points (the plan's ``kernel_sites`` then govern
            per-site impls and ``shard_map`` lowering).

    Returns:
        The worker's result dict ("status", "measured_s", "runs_s",
        "compile_s", "peak_bytes", "devices", "error").

    Raises:
        RuntimeError: when the calling process's backend is a TPU.
    """
    _refuse_on_chip()
    if not isinstance(shape, dict):
        shape = {"name": shape.name, "seq_len": shape.seq_len,
                 "global_batch": shape.global_batch, "kind": shape.kind}
    job = {"arch": arch, "shape": shape, "reduced": reduced,
           "plan": plan.as_dict(), "repeats": repeats, "warmup": warmup,
           "use_pallas": use_pallas}
    return _run_worker_subprocess(job, plan.mesh.num_devices, timeout)


def hlo_for_plan(arch: str, shape, plan, *, reduced: bool = True,
                 timeout: float = 900.0,
                 use_pallas: bool = False) -> dict:
    """Harvest a plan's compiled-HLO collective traffic in a subprocess.

    The conformance half of the static verifier needs the collectives
    XLA actually emits, which requires lowering under the plan's full
    device count — hence the same forced-device-count subprocess
    isolation as :func:`measure_plan`, but stopping after compile (no
    timed execution).

    Args:
        arch: zoo config id (the worker rebuilds the step function).
        shape: ``ShapeConfig`` (or dict) of the traced cell.
        plan: the ``ShardingPlan`` to lower.
        reduced: run the ``reduced()`` (CPU-smoke) config.
        timeout: subprocess wall-clock budget, seconds.
        use_pallas: route the worker's model through the fused kernel
            entry points (see :func:`measure_plan`).

    Returns:
        The worker result: "status", "coll_bytes" (``{kind: bytes}``,
        loop-aware), "unknown_dtypes", "top_collectives",
        "while_trips", "compile_s", "peak_bytes", "error".

    Raises:
        RuntimeError: when the calling process's backend is a TPU.
    """
    _refuse_on_chip()
    if not isinstance(shape, dict):
        shape = {"name": shape.name, "seq_len": shape.seq_len,
                 "global_batch": shape.global_batch, "kind": shape.kind}
    job = {"arch": arch, "shape": shape, "reduced": reduced,
           "plan": plan.as_dict(), "mode": "hlo",
           "use_pallas": use_pallas}
    return _run_worker_subprocess(job, plan.mesh.num_devices, timeout)


def _run_worker_subprocess(job: dict, num_devices: int,
                           timeout: float) -> dict:
    """Run one worker job in a forced-device-count subprocess."""
    cmd = [sys.executable, "-m", "repro.launch.measure", "--worker"]
    try:
        proc = subprocess.run(
            cmd, input=json.dumps(job).encode(), capture_output=True,
            env=_worker_env(num_devices), timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"status": "timeout",
                "error": f"worker exceeded {timeout}s"}
    for line in reversed(proc.stdout.decode(errors="replace").splitlines()):
        if line.startswith(MARKER):
            return json.loads(line[len(MARKER):])
    tail = proc.stderr.decode(errors="replace")[-1000:]
    return {"status": "error",
            "error": f"worker exited {proc.returncode} without a result; "
                     f"stderr tail: {tail}"}


def _bottleneck(bd) -> str:
    """Dominant roofline term of a breakdown (op-class for error report)."""
    if bd.collective_time >= bd.compute_time:
        return "collective"
    if bd.memory_time >= 0.999 * bd.compute_time:
        return "memory"
    return "compute"


def measure_record(record: dict, captures: dict, *, repeats: int = 5,
                   warmup: int = 1, plans_per_model: int = 4,
                   timeout: float = 900.0, plan_store=None,
                   verbose: bool = True) -> dict:
    """Measure a zoo sweep's plans and calibrate the cost model.

    For every model the sweep partitioned, a handful of plan variants
    (``repro.core.measure.candidate_states``) are executed on the
    simulated mesh; predicted-vs-measured Spearman rank correlation is
    computed per model, the ``HardwareSpec`` roofline is least-squares
    fitted to the measured cells, every cell is re-costed under the
    calibrated hardware (no re-analysis — ``CostModel.with_hardware``),
    and the calibrated spec is saved through the plan store.

    Args:
        record: the ``run_zoo`` sweep record (supplies mesh/shape).
        captures: ``{arch: (session, request, plan)}`` from the sweep.
        repeats: timed executions per cell (median reported).
        warmup: untimed warmup executions per cell.
        plans_per_model: plan variants measured per model (>= 3).
        timeout: per-cell subprocess budget, seconds.
        plan_store: optional ``PlanStore``; the calibrated hardware is
            persisted via ``save_hardware`` when given.
        verbose: print one progress line per measured cell.

    Returns:
        The measured record written to ``BENCH_measured.json``: cells,
        per-model Spearman, and the calibration report (hardware before
        and after, mean relative error before and after, per-op-class
        errors).
    """
    from repro.core.measure import (MeasuredCell, candidate_states,
                                    fit_hardware, mean_relative_error,
                                    spearman, verify_gate)

    mesh_str = "x".join(str(s) for s in record["mesh"]["sizes"])
    shape = dict(record["shape"])
    reduced = not record.get("full_configs", False)
    cells: list[MeasuredCell] = []
    by_model: dict[str, list[MeasuredCell]] = {}
    states: dict[tuple[str, str], object] = {}

    for arch, (sess, request, plan) in captures.items():
        cm = sess._cost_model(request.mesh, request.hw)
        actions = sess._actions(request.mesh, request.min_dims)
        cands = candidate_states(plan.state, actions=actions,
                                 cost_fn=cm.paper_cost,
                                 k=max(3, plans_per_model))
        for label, state in cands:
            vplan = sess.plan_for_state(request, state, label=label)
            feats = cm.state_features(state)
            cell = MeasuredCell(
                model=arch, plan_label=label, mesh=mesh_str,
                cost=round(vplan.cost, 6),
                predicted_s=feats["runtime"],
                predicted_peak_bytes=feats["peak_bytes"],
                features=feats)
            # soundness gate: never burn a subprocess on a plan the
            # static verifier can prove is structurally wrong
            blocking = verify_gate(cm, state, plan=vplan)
            if blocking:
                res = {"status": "verify_failed",
                       "error": "; ".join(
                           f"[{f.rule}] {f.message}"
                           for f in blocking[:4])[:500]}
            else:
                res = measure_plan(arch, shape, vplan, reduced=reduced,
                                   repeats=repeats, warmup=warmup,
                                   timeout=timeout)
            cell.status = res.get("status", "error")
            cell.error = res.get("error", "")
            cell.devices = res.get("devices", 0)
            cell.compile_s = res.get("compile_s", 0.0)
            cell.measured_peak_bytes = res.get("peak_bytes")
            cell.measured_s = res.get("measured_s", 0.0)
            cell.runs_s = [round(r, 6) for r in res.get("runs_s", [])]
            # feasibility needs evidence: None when memory analysis was
            # unavailable (never "feasible" on a 0-byte default)
            if cell.status != "ok":
                cell.feasible = False
            elif cell.measured_peak_bytes is None:
                cell.feasible = None
            else:
                cell.feasible = (cell.measured_peak_bytes <=
                                 request.hw.hbm_per_chip)
            cells.append(cell)
            by_model.setdefault(arch, []).append(cell)
            states[(arch, label)] = (sess, request, state)
            if verbose:
                ms = cell.measured_s * 1e3
                print(f"[measure {arch:>14}/{label:<9}] {cell.status:<13} "
                      f"measured={ms:8.2f}ms "
                      f"compile={cell.compile_s:5.1f}s", flush=True)

    ok = [c for c in cells if c.status == "ok" and c.measured_s > 0.0]
    calibration: dict = {"n_cells": len(ok)}
    hw0 = next(iter(captures.values()))[1].hw if captures else None
    if ok and hw0 is not None:
        axes = tuple(record["mesh"]["axes"])
        hw_cal = fit_hardware(
            [{"features": c.features, "measured_s": c.measured_s}
             for c in ok], hw0, axes)
        # re-cost every cell under the calibrated hardware: same analysis,
        # same static tables, new roofline constants
        cal_models: dict[str, object] = {}
        classes: dict[str, list[MeasuredCell]] = {}
        for c in cells:
            sess, request, state = states[(c.model, c.plan_label)]
            cm_cal = cal_models.get(c.model)
            if cm_cal is None:
                cm_cal = sess._cost_model(request.mesh, request.hw) \
                    .with_hardware(hw_cal)
                cal_models[c.model] = cm_cal
            bd = cm_cal.evaluate(state)
            c.predicted_calibrated_s = bd.runtime
            if c.status == "ok":
                classes.setdefault(_bottleneck(bd), []).append(c)
        calibration.update(
            hw_before=hw0.as_dict(), hw_after=hw_cal.as_dict(),
            mean_rel_err_before=mean_relative_error(
                [c.predicted_s for c in ok], [c.measured_s for c in ok]),
            mean_rel_err_after=mean_relative_error(
                [c.predicted_calibrated_s for c in ok],
                [c.measured_s for c in ok]),
            per_class={
                k: {"n": len(v),
                    "mean_rel_err": mean_relative_error(
                        [c.predicted_calibrated_s for c in v],
                        [c.measured_s for c in v])}
                for k, v in sorted(classes.items())})
        if plan_store is not None:
            plan_store.save_hardware(hw_cal)

    per_model = {}
    for arch, group in by_model.items():
        g = [c for c in group if c.status == "ok" and c.measured_s > 0.0]
        per_model[arch] = {
            "n_plans": len(group),
            "n_measured": len(g),
            "spearman": spearman([c.predicted_calibrated_s for c in g],
                                 [c.measured_s for c in g])
            if len(g) >= 2 else None,
            "spearman_uncalibrated": spearman(
                [c.predicted_s for c in g], [c.measured_s for c in g])
            if len(g) >= 2 else None,
        }
    rhos = [m["spearman"] for m in per_model.values()
            if m["spearman"] is not None]
    return {
        "mesh": record["mesh"],
        "shape": shape,
        "repeats": repeats,
        "warmup": warmup,
        "cells": [c.as_dict() for c in cells],
        "per_model": per_model,
        "spearman_mean": (float(sum(rhos) / len(rhos)) if rhos else None),
        "calibration": calibration,
    }


_MEASURE_COLUMNS = ("model", "plan", "status", "cost", "predicted_ms",
                    "calibrated_ms", "measured_ms", "peak_mb")


def format_measure_table(mrec: dict) -> str:
    """Render a measured record as an aligned predicted-vs-measured table.

    Args:
        mrec: the :func:`measure_record` result.

    Returns:
        A printable multi-line table string.
    """
    rows = [list(_MEASURE_COLUMNS)]
    for c in mrec["cells"]:
        rows.append([
            c["model"], c["plan_label"], c["status"],
            f"{c['cost']:.4f}",
            f"{c['predicted_s'] * 1e3:.3f}",
            f"{c['predicted_calibrated_s'] * 1e3:.3f}",
            f"{c['measured_s'] * 1e3:.3f}" if c["measured_s"] else "-",
            (f"{c['measured_peak_bytes'] / 2**20:.1f}"
             if c["measured_peak_bytes"] is not None else "-"),
        ])
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    lines = []
    for j, r in enumerate(rows):
        lines.append("  ".join(x.rjust(w) for x, w in zip(r, widths)))
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> None:
    """CLI entry point — only the internal ``--worker`` mode.

    Args:
        argv: argument list (defaults to ``sys.argv[1:]``).
    """
    ap = argparse.ArgumentParser(
        description="Measured-execution worker (driven by "
                    "`python -m repro.launch.zoo --measure`).")
    ap.add_argument("--worker", action="store_true",
                    help="read one job JSON from stdin, print the result")
    args = ap.parse_args(argv)
    if not args.worker:
        ap.error("this module is a worker; run "
                 "`python -m repro.launch.zoo --measure` instead")
    _worker_main()


if __name__ == "__main__":
    main()
