"""Zoo-wide auto-partitioning driver.

Runs the full TOAST pipeline — trace, NDA, conflict analysis, portfolio
search — over **every** model in ``repro/configs`` on one mesh, and emits
a per-model feasibility/cost/search-time table.  This is the paper's
"diverse model architectures" claim exercised end-to-end: dense
transformers, GQA, MoE (mixtral, arctic), hybrid attention/RG-LRU
(recurrentgemma), xLSTM, encoder-decoder audio (whisper) and a VLM
(phi3_vision) all go through the same driver.

Plans are memoized in a ``repro.ckpt.plan_store.PlanStore`` keyed by
(program fingerprint, mesh, hardware): a second run over an unchanged zoo
skips every search and reports cache hits instead.

Usage::

    python -m repro.launch.zoo --mesh 4x2
    python -m repro.launch.zoo --mesh 4x2            # second run: all cached
    python -m repro.launch.zoo --mesh 8x4 --backend mcts --no-plan-store
    python -m repro.launch.zoo --mesh 2x2 --measure --smoke   # run for real
    python -m benchmarks.run --section zoo           # BENCH_zoo.json only

``--measure`` executes plan variants on a simulated device mesh, adds a
measured column + predicted-vs-measured rank correlation, calibrates the
cost model against the measurements, and writes ``BENCH_measured.json``
(see ``docs/measure.md``).

By default models run in their ``reduced()`` (CPU-smoke) size with a
small train shape so the whole zoo finishes in well under a minute;
``--full`` traces the production configs (minutes, trace-only — nothing
is executed).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import time
import traceback

from repro.api import Request, Session
from repro.ckpt.plan_store import PlanStore
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ShapeConfig
from repro.core.cost_model import HardwareSpec, MeshSpec, ShardingState
from repro.core.portfolio import PortfolioConfig, PortfolioMember
from repro.core.search import BeamConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.specs import step_and_inputs

# axis names by mesh rank, matching the repo's conventions elsewhere
_AXIS_NAMES = {
    1: ("model",),
    2: ("data", "model"),
    3: ("data", "seq", "model"),
    4: ("pod", "data", "seq", "model"),
}

# small train cell used for the sweep (divisible by every supported mesh)
ZOO_SHAPE = ShapeConfig("zoo_small", seq_len=512, global_batch=8,
                        kind="train")
ZOO_SHAPE_FULL = ShapeConfig("zoo_full", seq_len=4096, global_batch=256,
                             kind="train")
# small cell + model subset for `--smoke`: small enough that every plan
# variant *executes* in seconds on a simulated CPU mesh, but big enough
# that measured runtimes differ by more than host noise (at seq 64 every
# variant is ~90ms of dispatch overhead and rank correlation is a coin
# flip; at seq 256 sharding visibly pays); two model families so the
# calibration fit is overdetermined (not an interpolation)
ZOO_SHAPE_SMOKE = ShapeConfig("zoo_smoke", seq_len=256, global_batch=8,
                              kind="train")
SMOKE_ARCHS = ("qwen2_05b", "mixtral_8x22b")


def zoo_portfolio(seeds: int = 2, workers: int | None = 2
                  ) -> PortfolioConfig:
    """The zoo's default search portfolio: cheap members, early stop.

    Cheap deterministic members (greedy, narrow beam) are listed first so
    their results arrive early; MCTS seeds follow and are cancelled when
    the feasible cost has already plateaued.  The search is GIL-bound, so
    a small worker count costs no wall-clock and leaves members queued
    (cancellable).

    Args:
        seeds: number of MCTS members.
        workers: thread-pool size (``None`` = one per member).

    Returns:
        A :class:`PortfolioConfig` for ``auto_partition``.
    """
    from repro.core.mcts import MCTSConfig
    members = [
        PortfolioMember("greedy", config=BeamConfig(patience=1)),
        PortfolioMember("beam", config=BeamConfig(width=4, patience=1)),
    ]
    members += [
        PortfolioMember("mcts", seed=s,
                        config=MCTSConfig(seed=s, rounds=4,
                                          trajectories_per_round=16))
        for s in range(seeds)
    ]
    return PortfolioConfig(members=tuple(members), max_workers=workers,
                           patience=2)


def parse_mesh(spec: str) -> MeshSpec:
    """Parse a ``"4x2"``-style mesh string into a :class:`MeshSpec`.

    Args:
        spec: ``x``-separated axis sizes, e.g. ``"4x2"`` or ``"2x4x2"``;
            1–4 axes are named per the repo convention
            (``data``/``model``, then ``seq``, then ``pod``).

    Returns:
        The corresponding ``MeshSpec`` (``pod`` marked as a DCN axis).

    Raises:
        ValueError: on malformed specs — empty strings, missing sizes
            (``"4x"``), non-integers, zero/negative sizes, or more than
            4 axes — with a message naming the expected form (the CLI
            turns it into a usage error instead of a traceback).
    """
    parts = (spec or "").strip().lower().split("x")
    try:
        sizes = tuple(int(p) for p in parts)
    except ValueError:
        raise ValueError(
            f"bad mesh spec {spec!r}: expected 'x'-separated positive "
            f"integer sizes, e.g. '4x2' or '2x4x2'") from None
    if any(s < 1 for s in sizes):
        raise ValueError(f"bad mesh spec {spec!r}: axis sizes must be "
                         f">= 1, got {sizes}")
    names = _AXIS_NAMES.get(len(sizes))
    if names is None:
        raise ValueError(f"mesh spec {spec!r} has {len(sizes)} axes; "
                         f"supported: 1-4")
    dcn = ("pod",) if "pod" in names else ()
    return MeshSpec(names, sizes, dcn)


def run_model(arch: str, mesh: MeshSpec, *,
              shape: ShapeConfig = ZOO_SHAPE,
              hw: HardwareSpec = HardwareSpec(),
              backend: str = "portfolio",
              search_config=None,
              plan_store: PlanStore | None = None,
              full: bool = False,
              min_dims: int = 10,
              capture: dict | None = None,
              profile: bool = False) -> dict:
    """Auto-partition one zoo model and summarize the outcome.

    Args:
        arch: config module name from ``repro.configs.ARCH_IDS``.
        mesh: mesh to shard over.
        shape: train cell (seq len / global batch) to trace.
        hw: hardware roofline constants.
        backend: search backend name ("portfolio" by default).
        search_config: backend-specific config (portfolio/MCTS/beam).
        plan_store: optional persistent plan cache.
        full: trace the production config instead of ``reduced()``.
        min_dims: action-space pruning threshold.
        capture: optional dict; on success ``capture[arch]`` receives
            ``(session, request, plan)`` so the measured-execution pass
            can re-cost and execute plan variants without re-analysis.
        profile: trace allocations with ``tracemalloc`` and attach a
            ``row["profile"]`` wall/alloc breakdown per pipeline stage
            (roughly 2x slower — a diagnosis mode, not a benchmark).

    Returns:
        A flat JSON-friendly result row; ``row["status"]`` is ``"ok"`` or
        ``"error"`` (with ``row["error"]`` set).
    """
    cfg_full = get_config(arch)
    cfg = cfg_full if full else cfg_full.reduced()
    row = {"model": arch, "family": cfg.family,
           # params of the config actually traced ...
           "params_m": round(cfg.num_params() / 1e6, 2),
           # ... and of the production config, so reduced-sweep rows are
           # not misread as the model's real size
           "params_m_full": round(cfg_full.num_params() / 1e6, 2),
           "status": "ok", "mesh": "x".join(map(str, mesh.sizes))}
    if profile:
        import tracemalloc
        was_tracing = tracemalloc.is_tracing()
        if not was_tracing:
            tracemalloc.start()
    try:
        fn, args, names = step_and_inputs(cfg, shape)
        if profile:
            tracemalloc.reset_peak()
        t0 = time.perf_counter()
        sess = Session(fn, args, plan_store=plan_store)
        if profile:
            analysis_wall = time.perf_counter() - t0
            _, analysis_peak = tracemalloc.get_traced_memory()
            tracemalloc.reset_peak()
        t0 = time.perf_counter()
        request = Request(
            mesh=mesh, hw=hw, backend=backend,
            search_config=search_config, min_dims=min_dims,
            logical_axes=names)
        plan = sess.partition(request)
        if profile:
            search_wall = time.perf_counter() - t0
            _, search_peak = tracemalloc.get_traced_memory()
        if capture is not None:
            capture[arch] = (sess, request, plan)
    except Exception as e:                      # noqa: BLE001
        row.update(status="error", error=repr(e),
                   traceback=traceback.format_exc(limit=5))
        return row
    finally:
        if profile and not was_tracing:
            tracemalloc.stop()
    if profile:
        row["profile"] = {
            "phases": {k: round(v, 4) for k, v in
                       sess.artifacts.phase_seconds.items()},
            "analysis_wall_s": round(analysis_wall, 4),
            "analysis_peak_mb": round(analysis_peak / 2**20, 2),
            "search_wall_s": round(search_wall, 4),
            "search_peak_mb": round(search_peak / 2**20, 2),
        }
    base, bd = plan.baseline_breakdown, plan.breakdown
    pf = plan.eval_stats.get("portfolio", {})
    row.update(
        ops=len(sess.artifacts.prog.ops),
        colors=plan.num_colors,
        conflicts=plan.num_conflicts,
        compat_sets=plan.num_compat_sets,
        resolution_bits=plan.num_resolution_bits,
        analysis_s=round(sum(sess.artifacts.phase_seconds.values()), 3),
        search_s=round(plan.search_seconds, 3),
        evaluations=plan.evaluations,
        cost=round(plan.cost, 6),
        speedup=round(base["runtime"] / max(bd["runtime"], 1e-12), 2),
        peak_gb=round(bd["peak_bytes"] / 2**30, 4),
        feasible=bool(bd["peak_bytes"] <= hw.hbm_per_chip),
        backend=plan.backend,
        winner=pf.get("winner", plan.backend),
        cached=plan.cached,
        # plans loaded from old stores can carry an empty fingerprint —
        # fall back to the session's so rows stay attributable to a
        # plan-store key
        fingerprint=(plan.fingerprint or sess.fingerprint)[:12],
        analysis_phases={k: round(v, 4) for k, v in
                         sess.artifacts.phase_seconds.items()},
        rules={k: list(v) for k, v in plan.logical_rules.items()},
    )
    return row


def run_zoo(mesh: MeshSpec, *, archs: tuple[str, ...] | None = None,
            shape: ShapeConfig | None = None,
            hw: HardwareSpec = HardwareSpec(),
            backend: str = "portfolio",
            search_config=None,
            plan_store: PlanStore | None = None,
            full: bool = False,
            min_dims: int = 10,
            verbose: bool = True,
            captures: dict | None = None,
            profile: bool = False) -> dict:
    """Sweep the whole config zoo on one mesh.

    Args:
        mesh: mesh to shard every model over.
        archs: subset of ``ARCH_IDS`` (default: all).
        shape: train cell; defaults to the small zoo cell (or the 4k cell
            with ``full=True``).
        hw: hardware roofline constants.
        backend: search backend for every model.
        search_config: backend-specific config shared by all models.
        plan_store: persistent plan cache (hits skip the search).
        full: use production configs instead of ``reduced()``.
        min_dims: action-space pruning threshold.
        verbose: print progress lines as models finish.
        captures: optional dict collecting per-arch ``(session, request,
            plan)`` for the ``--measure`` pass (see ``run_model``).
        profile: per-model wall/alloc breakdown (see ``run_model``).

    Returns:
        The sweep record: ``{"mesh", "shape", "backend", "results": [...],
        "cache", "total_seconds"}`` — the same dict written to
        ``BENCH_zoo.json``.
    """
    archs = tuple(archs or ARCH_IDS)
    shape = shape or (ZOO_SHAPE_FULL if full else ZOO_SHAPE)
    if backend == "portfolio" and search_config is None:
        search_config = zoo_portfolio()
    t0 = time.perf_counter()
    rows = []
    for arch in archs:
        t = time.perf_counter()
        row = run_model(arch, mesh, shape=shape, hw=hw, backend=backend,
                        search_config=search_config, plan_store=plan_store,
                        full=full, min_dims=min_dims, capture=captures,
                        profile=profile)
        rows.append(row)
        if verbose:
            if row["status"] == "ok":
                src = "cache" if row["cached"] else row["winner"]
                print(f"[{arch:>16}] cost={row['cost']:.4f} "
                      f"speedup={row['speedup']:5.2f}x "
                      f"feasible={'Y' if row['feasible'] else 'N'} "
                      f"{src:<10} {time.perf_counter() - t:5.2f}s",
                      flush=True)
            else:
                print(f"[{arch:>16}] ERROR {row['error']}", flush=True)
    record = {
        "mesh": mesh.as_dict(),
        "shape": {"seq_len": shape.seq_len,
                  "global_batch": shape.global_batch, "kind": shape.kind},
        "backend": backend,
        "full_configs": full,
        "results": rows,
        "cache": plan_store.stats.as_dict() if plan_store is not None
        else None,
        "total_seconds": round(time.perf_counter() - t0, 2),
    }
    return record


# -- static verification ------------------------------------------------------

def verify_record(record: dict, captures: dict, *,
                  timeout: float = 900.0, conformance: bool = True,
                  verbose: bool = True) -> dict:
    """Statically verify every captured plan + conform against real HLO.

    For each model the sweep partitioned, the full
    ``repro.core.verify`` rule set runs over the searched plan, and —
    unless ``conformance`` is off — the plan is lowered and compiled in
    a forced-device-count subprocess
    (``repro.launch.measure.hlo_for_plan``) so the predicted collective
    multiset can be matched against the collectives XLA actually
    emitted.

    Args:
        record: the ``run_zoo`` sweep record (supplies shape/mesh).
        captures: ``{arch: (session, request, plan)}`` from the sweep.
        timeout: per-model HLO-harvest subprocess budget, seconds.
        conformance: harvest compiled HLO and run the conformance
            check (pure static rules only when off).
        verbose: print one line per verified model.

    Returns:
        The verify record written to ``BENCH_verify.json``: per-model
        findings + conformance, and a summary with the failure list
        (models with error findings or a conformance mismatch).
    """
    from repro.api import Finding
    from repro.launch.measure import hlo_for_plan

    shape = dict(record["shape"])
    reduced = not record.get("full_configs", False)
    rows: list[dict] = []
    failures: list[str] = []
    for arch, (sess, request, plan) in captures.items():
        hlo = None
        harvest: dict = {}
        if conformance:
            harvest = hlo_for_plan(arch, shape, plan, reduced=reduced,
                                   timeout=timeout)
            if harvest.get("status") == "ok":
                hlo = {"coll_bytes": harvest.get("coll_bytes", {}),
                       "unknown_dtypes":
                           harvest.get("unknown_dtypes", []),
                       "top_collectives":
                           [tuple(t) for t in
                            harvest.get("top_collectives", [])]}
        report = sess.verify(
            request, plan, hlo=hlo,
            conformance="auto" if hlo is not None else False)
        if conformance and hlo is None:
            report.findings.append(Finding(
                "conformance", -1, "warning",
                f"HLO harvest failed "
                f"({harvest.get('status', 'skipped')}): "
                f"{harvest.get('error', '')[:200]}"))
            report.sort()
        row = {"model": arch,
               "mesh": "x".join(str(s) for s in plan.mesh.sizes),
               "harvest_status": harvest.get("status", "off"),
               "harvest_compile_s": harvest.get("compile_s", 0.0),
               **report.as_dict()}
        rows.append(row)
        if not report.ok:
            match = (report.conformance or {}).get("match", "-")
            failures.append(
                f"{arch}: {len(report.errors)} error finding(s), "
                f"conformance={match}")
        if verbose:
            conf = (report.conformance or {}).get("match", "-")
            print(f"[verify {arch:>16}] "
                  f"{'ok ' if report.ok else 'FAIL'} "
                  f"errors={len(report.errors)} "
                  f"warnings={len(report.warnings)} "
                  f"conformance={conf}", flush=True)
    matches: dict[str, int] = {}
    for r in rows:
        m = (r.get("conformance") or {}).get("match", "none")
        matches[m] = matches.get(m, 0) + 1
    return {
        "mesh": record["mesh"],
        "shape": shape,
        "full_configs": record.get("full_configs", False),
        "results": rows,
        "summary": {"n_models": len(rows),
                    "n_ok": sum(r["ok"] for r in rows),
                    "conformance_matches": matches},
        "failures": failures,
    }


_VERIFY_COLUMNS = ("model", "ok", "errors", "warnings", "conformance",
                   "pred_coll_mb", "emit_coll_mb", "harvest")


def format_verify_table(vrec: dict) -> str:
    """Render a verify record as an aligned per-model findings table.

    Args:
        vrec: the :func:`verify_record` result.

    Returns:
        A printable multi-line table, followed by every non-info
        finding of failing models.
    """
    table = [list(_VERIFY_COLUMNS)]
    for r in vrec["results"]:
        counts = r.get("counts", {})
        conf = r.get("conformance") or {}
        tot = conf.get("total", {})
        table.append([
            r["model"],
            "yes" if r["ok"] else "NO",
            str(counts.get("error", 0)),
            str(counts.get("warning", 0)),
            conf.get("match", "-"),
            (f"{tot['predicted'] / 2**20:.2f}"
             if "predicted" in tot else "-"),
            (f"{tot['emitted'] / 2**20:.2f}"
             if "emitted" in tot else "-"),
            r.get("harvest_status", "-"),
        ])
    widths = [max(len(row[i]) for row in table)
              for i in range(len(_VERIFY_COLUMNS))]
    lines = []
    for j, row in enumerate(table):
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    for r in vrec["results"]:
        bad = [f for f in r.get("findings", [])
               if f["severity"] in ("error", "warning")]
        if not r["ok"] and bad:
            lines.append(f"\n[{r['model']}] findings:")
            for f in bad[:12]:
                op = f["op"] if f["op"] >= 0 else "-"
                lines.append(f"  {f['severity'].upper():<7} "
                             f"{f['rule']:<22} op={op:<4} "
                             f"{f['message']}")
    return "\n".join(lines)


# -- mesh-shape co-search -----------------------------------------------------

def fixed_2d_meshes(devices: int) -> list[MeshSpec]:
    """The fixed 2-D baseline meshes for a device budget.

    Every unordered two-factor split of ``devices`` spelled the
    conventional way (``data`` × ``model``, largest axis first) — for 16
    devices: ``16x1``, ``8x2``, ``4x4``.  These are the meshes a user
    without co-search would pick by hand; ``--co-search`` reports its
    winner against the best of them.

    Args:
        devices: total device count.

    Returns:
        Deduplicated ``MeshSpec`` list, largest leading axis first.
    """
    out: list[MeshSpec] = []
    seen: set[tuple[int, int]] = set()
    for a in range(devices, 0, -1):
        if devices % a:
            continue
        b = devices // a
        key = (max(a, b), min(a, b))
        if key in seen:
            continue
        seen.add(key)
        out.append(MeshSpec(("data", "model"), (max(a, b), min(a, b))))
    return out


def _mesh_str(mesh: MeshSpec) -> str:
    return "x".join(str(s) for s in mesh.sizes)


def cosearch_model(arch: str, devices: int, *,
                   pods: tuple[int, ...] = (1, 2),
                   shape: ShapeConfig = ZOO_SHAPE,
                   hw: HardwareSpec = HardwareSpec(),
                   backend: str = "portfolio",
                   search_config=None,
                   plan_store: PlanStore | None = None,
                   min_dims: int = 10,
                   measure: bool = False,
                   repeats: int = 3,
                   timeout: float = 600.0,
                   verbose: bool = True) -> dict:
    """Co-search the mesh shape and plan for one zoo model.

    Runs :meth:`repro.api.Session.co_search` over every factorization of
    the device budget, searches the fixed 2-D baseline meshes with the
    same backend for comparison, and (optionally) validates the winner,
    the best fixed plan and the best multi-pod candidate by measured
    execution on simulated meshes — fitting a calibrated
    ``HardwareSpec`` from the measured cells and re-costing every
    candidate under it, so the record carries the ranking under both
    default and calibrated hardware.

    Args:
        arch: config module name from ``repro.configs.ARCH_IDS``.
        devices: total device budget ``N``.
        pods: pod counts the enumerator may place across DCN.
        shape: train cell to trace.
        hw: default hardware roofline constants.
        backend: per-mesh search backend.
        search_config: backend-specific config.
        plan_store: optional persistent plan cache (per-mesh keys).
        min_dims: action-space pruning threshold.
        measure: execute winner / best-fixed / best-multi-pod plans in
            simulated-mesh subprocesses and calibrate from them.
        repeats: timed executions per measured cell.
        timeout: per-cell subprocess budget, seconds.
        verbose: print per-candidate and per-cell progress lines.

    Returns:
        A JSON-friendly record: candidate rows, fixed-mesh rows, the
        winner, ``ties_or_beats_fixed``, the best multi-pod candidate,
        and (with ``measure``) measured cells plus the calibration
        comparison.  ``row["status"]`` is "ok" or "error".
    """
    cfg = get_config(arch).reduced()
    row: dict = {"model": arch, "family": cfg.family, "status": "ok",
                 "devices": devices, "pods": list(pods)}
    try:
        fn, args, names = step_and_inputs(cfg, shape)
        sess = Session(fn, args, plan_store=plan_store)
        template = Request(
            mesh=MeshSpec(("data", "model"), (1, 1)), hw=hw,
            backend=backend, search_config=search_config,
            min_dims=min_dims, logical_axes=names)
        res = sess.co_search(template, devices, pods=pods,
                             verbose=verbose)

        fixed_rows: list[dict] = []
        best_fixed: tuple | None = None
        for mesh in fixed_2d_meshes(devices):
            plan = sess.partition(dataclasses.replace(template,
                                                      mesh=mesh))
            feasible = bool(plan.breakdown["peak_bytes"]
                            <= hw.hbm_per_chip)
            frow = {"mesh_str": _mesh_str(mesh),
                    "cost": round(plan.cost, 6), "feasible": feasible,
                    "search_s": round(plan.search_seconds, 3),
                    "cached": plan.cached}
            fixed_rows.append(frow)
            key = (not feasible, plan.cost)
            if best_fixed is None or key < best_fixed[0]:
                best_fixed = (key, mesh, plan)
    except Exception as e:                          # noqa: BLE001
        row.update(status="error", error=repr(e),
                   traceback=traceback.format_exc(limit=5))
        return row

    winner_row = None
    if res.best_mesh is not None:
        want = res.best_mesh.as_dict()
        winner_row = next(r for r in res.rows if r["mesh"] == want)
    row.update(
        candidates=res.rows,
        analysis_s=round(sum(sess.artifacts.phase_seconds.values()), 3),
        cosearch_s=round(res.seconds, 3),
        fixed=fixed_rows,
        winner=winner_row,
        best_fixed=(None if best_fixed is None else
                    {"mesh_str": _mesh_str(best_fixed[1]),
                     "cost": round(best_fixed[2].cost, 6)}),
        ties_or_beats_fixed=bool(
            winner_row is not None and best_fixed is not None
            and res.best_plan.cost <= best_fixed[2].cost + 1e-9),
    )
    mp = res.best_multi_pod()
    row["multi_pod_best"] = None if mp is None else {
        "mesh_str": _mesh_str(mp[0]), "cost": round(mp[1].cost, 6)}

    if measure and res.best_mesh is not None:
        row["measured"] = _measure_cosearch(
            sess, template, res, best_fixed, arch, shape, hw,
            repeats=repeats, timeout=timeout, verbose=verbose)
    return row


def _measure_cosearch(sess, template, res, best_fixed, arch, shape, hw,
                      *, repeats: int, timeout: float,
                      verbose: bool) -> dict:
    """Measured validation of co-search winners + calibrated re-ranking."""
    from repro.core.measure import fit_hardware
    from repro.launch.measure import measure_plan

    to_run: list[tuple[str, MeshSpec, object]] = [
        ("winner", res.best_mesh, res.best_plan),
        ("unsharded", res.best_mesh,
         sess.plan_for_state(
             dataclasses.replace(template, mesh=res.best_mesh),
             ShardingState(), label="unsharded")),
    ]
    if best_fixed is not None and best_fixed[1] != res.best_mesh:
        to_run.append(("best_fixed", best_fixed[1], best_fixed[2]))
    mp = res.best_multi_pod()
    if mp is not None and mp[0] != res.best_mesh:
        to_run.append(("multi_pod_best", mp[0], mp[1]))

    cells: list[dict] = []
    for label, mesh, plan in to_run:
        cm = sess._cost_model(mesh, hw)
        feats = cm.state_features(plan.state)
        r = measure_plan(arch, shape, plan, reduced=True,
                         repeats=repeats, warmup=1, timeout=timeout)
        cell = {"label": label, "mesh_str": _mesh_str(mesh),
                "multi_pod": bool(mesh.dcn_axes),
                "status": r.get("status", "error"),
                "devices": r.get("devices", 0),
                "predicted_s": feats["runtime"],
                "measured_s": r.get("measured_s", 0.0),
                "compile_s": r.get("compile_s", 0.0),
                "runs_s": [round(x, 6) for x in r.get("runs_s", [])],
                "error": r.get("error", ""),
                "features": feats}
        cells.append(cell)
        if verbose:
            print(f"[co-measure {arch:>14}/{label:<14}] "
                  f"{cell['status']:<13} "
                  f"measured={cell['measured_s'] * 1e3:8.2f}ms "
                  f"({cell['mesh_str']})", flush=True)

    out: dict = {"cells": cells}
    ok = [c for c in cells if c["status"] == "ok"
          and c["measured_s"] > 0.0]
    if len(ok) >= 2:
        axes: list[str] = []
        for _, mesh, _ in to_run:
            for a in mesh.axes:
                if a not in axes:
                    axes.append(a)
        hw_cal = fit_hardware(
            [{"features": c["features"], "measured_s": c["measured_s"]}
             for c in ok], hw, tuple(axes))
        out["hw_calibrated"] = hw_cal.as_dict()
        # re-cost every searched candidate under the calibrated roofline
        # (shared analysis, shared static tables — only base rows move)
        best_cal: tuple | None = None
        for r in res.rows:
            if r.get("status") != "ok":
                continue
            mesh = MeshSpec(tuple(r["mesh"]["axes"]),
                            tuple(r["mesh"]["sizes"]),
                            tuple(r["mesh"]["dcn_axes"]))
            cm_cal = sess._cost_model(mesh, hw).with_hardware(hw_cal)
            cost_cal = cm_cal.paper_cost(res.plans[mesh].state)
            r["cost_calibrated"] = round(cost_cal, 6)
            key = (not r["feasible"], cost_cal)
            if best_cal is None or key < best_cal[0]:
                best_cal = (key, r["mesh_str"])
        if best_cal is not None:
            out["winner_calibrated"] = best_cal[1]
            out["calibrated_agrees"] = bool(
                res.best_mesh is not None
                and best_cal[1] == _mesh_str(res.best_mesh))
    # drop the bulky per-cell features from the persisted record
    for c in cells:
        c.pop("features", None)
    return out


def run_cosearch(devices: int, *, archs: tuple[str, ...],
                 pods: tuple[int, ...] = (1, 2),
                 shape: ShapeConfig | None = None,
                 hw: HardwareSpec = HardwareSpec(),
                 backend: str = "portfolio",
                 search_config=None,
                 plan_store: PlanStore | None = None,
                 min_dims: int = 10,
                 measure: bool = False,
                 repeats: int = 3,
                 timeout: float = 600.0,
                 verbose: bool = True) -> dict:
    """Mesh-shape co-search over several zoo models.

    Args:
        devices: total device budget ``N``.
        archs: zoo configs to co-search.
        pods: pod counts the enumerator may place across DCN.
        shape: train cell (defaults to the small zoo cell).
        hw: default hardware roofline constants.
        backend: per-mesh search backend.
        search_config: backend-specific config shared by all models.
        plan_store: persistent plan cache.
        min_dims: action-space pruning threshold.
        measure: validate winners by measured execution + calibrate.
        repeats: timed executions per measured cell.
        timeout: per-cell subprocess budget, seconds.
        verbose: print progress lines.

    Returns:
        The co-search record written to ``BENCH_meshsearch.json``;
        ``record["failures"]`` lists models whose winner was infeasible
        or lost to the best fixed 2-D mesh (the CI gate).
    """
    shape = shape or ZOO_SHAPE
    if backend == "portfolio" and search_config is None:
        search_config = zoo_portfolio()
    t0 = time.perf_counter()
    rows = []
    failures = []
    for arch in archs:
        if verbose:
            print(f"-- co-search {arch} over {devices} devices "
                  f"(pods {','.join(map(str, pods))}) --", flush=True)
        row = cosearch_model(
            arch, devices, pods=pods, shape=shape, hw=hw,
            backend=backend, search_config=search_config,
            plan_store=plan_store, min_dims=min_dims, measure=measure,
            repeats=repeats, timeout=timeout, verbose=verbose)
        rows.append(row)
        if row["status"] != "ok":
            failures.append(f"{arch}: {row['error']}")
        elif row["winner"] is None:
            failures.append(f"{arch}: no candidate searched successfully")
        elif not row["winner"]["feasible"]:
            failures.append(f"{arch}: co-search winner is infeasible")
        elif not row["ties_or_beats_fixed"]:
            failures.append(
                f"{arch}: winner cost {row['winner']['cost']} loses to "
                f"fixed {row['best_fixed']['mesh_str']} "
                f"({row['best_fixed']['cost']})")
    return {
        "devices": devices,
        "pods": list(pods),
        "shape": {"seq_len": shape.seq_len,
                  "global_batch": shape.global_batch,
                  "kind": shape.kind},
        "backend": backend,
        "results": rows,
        "failures": failures,
        "total_seconds": round(time.perf_counter() - t0, 2),
    }


_COSEARCH_COLUMNS = ("mesh", "dcn", "status", "cost", "cost_cal",
                     "feasible", "peak_gb", "bound_gb", "search_s",
                     "cached")


def format_cosearch_table(row: dict) -> str:
    """Render one model's co-search candidate rows as an aligned table.

    Args:
        row: a per-model record from :func:`cosearch_model`.

    Returns:
        A printable multi-line table string (candidates then the fixed
        2-D baselines and winner summary).
    """
    def cell(r, col):
        if col == "mesh":
            return r.get("mesh_str", "-")
        if col == "dcn":
            return "dcn" if r.get("multi_pod") else "-"
        if col == "cost_cal":
            v = r.get("cost_calibrated")
            return "-" if v is None else f"{v:.4f}"
        if col == "peak_gb":
            v = r.get("peak_gb")
            return "-" if v is None else f"{v:.4f}"
        if col == "bound_gb":
            v = r.get("peak_lower_bound_gb")
            return "-" if v is None else f"{v:.4f}"
        v = r.get(col, "-")
        if isinstance(v, bool):
            return "yes" if v else "NO"
        if isinstance(v, float):
            return f"{v:.4f}" if col == "cost" else f"{v:.2f}"
        return str(v)

    table = [list(_COSEARCH_COLUMNS)]
    table += [[cell(r, c) for c in _COSEARCH_COLUMNS]
              for r in row.get("candidates", [])]
    widths = [max(len(r[i]) for r in table)
              for i in range(len(_COSEARCH_COLUMNS))]
    lines = [f"[{row['model']}] co-search over {row['devices']} devices"]
    for j, r in enumerate(table):
        lines.append("  ".join(c.rjust(w) for c, w in zip(r, widths)))
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    fixed = ", ".join(f"{f['mesh_str']}={f['cost']:.4f}"
                      for f in row.get("fixed", []))
    lines.append(f"fixed 2-D: {fixed}")
    if row.get("winner") is not None:
        verdict = ("ties/beats" if row["ties_or_beats_fixed"]
                   else "LOSES TO")
        lines.append(
            f"winner: {row['winner']['mesh_str']} "
            f"cost={row['winner']['cost']:.4f} {verdict} best fixed "
            f"{row['best_fixed']['mesh_str']}="
            f"{row['best_fixed']['cost']:.4f}")
    return "\n".join(lines)


_COLUMNS = ("model", "family", "ops", "colors", "conflicts",
            "resolution_bits", "feasible", "cost", "speedup", "peak_gb",
            "search_s", "evaluations", "winner", "cached")


def format_table(rows: list[dict]) -> str:
    """Render sweep rows as an aligned feasibility/cost/time table.

    Args:
        rows: result rows from :func:`run_zoo` / :func:`run_model`.

    Returns:
        A printable multi-line table string.
    """
    def cell(row, col):
        if row["status"] != "ok":
            return "ERROR" if col == "cost" else (
                row["model"] if col == "model" else "-")
        v = row.get(col, "-")
        if isinstance(v, bool):
            return "yes" if v else "NO"
        if isinstance(v, float):
            return f"{v:.4f}" if col == "cost" else f"{v:.2f}"
        return str(v)

    table = [[c for c in _COLUMNS]]
    table += [[cell(r, c) for c in _COLUMNS] for r in rows]
    widths = [max(len(row[i]) for row in table)
              for i in range(len(_COLUMNS))]
    lines = []
    for j, row in enumerate(table):
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if j == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def format_profile(rows: list[dict]) -> str:
    """Render the per-model ``--profile`` wall/alloc breakdown.

    Args:
        rows: result rows from :func:`run_zoo` with ``profile`` attached.

    Returns:
        A printable multi-line breakdown (one line per profiled model).
    """
    lines = ["\n--profile: per-model phase breakdown "
             "(wall seconds / tracemalloc peak MB)"]
    for r in rows:
        p = r.get("profile")
        if not p:
            continue
        phases = "  ".join(f"{k}={v:.3f}s"
                           for k, v in p["phases"].items())
        lines.append(
            f"[{r['model']:>16}] {phases}  | analysis "
            f"{p['analysis_wall_s']:.3f}s/{p['analysis_peak_mb']:.1f}MB"
            f"  search {p['search_wall_s']:.3f}s/"
            f"{p['search_peak_mb']:.1f}MB")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> dict:
    """CLI entry point; returns the sweep record it wrote.

    Args:
        argv: argument list (defaults to ``sys.argv[1:]``).

    Returns:
        The :func:`run_zoo` record (also written to ``--out``).
    """
    ap = argparse.ArgumentParser(
        description="Auto-partition every zoo config on one mesh.")
    ap.add_argument("--mesh", default="4x2",
                    help="mesh sizes, e.g. 4x2 or 2x4x2")
    ap.add_argument("--archs", default=None,
                    help="comma-separated subset of the zoo (default: "
                         "all models; with --smoke: the smoke subset)")
    ap.add_argument("--backend", default="portfolio",
                    help="search backend (portfolio | mcts | beam | "
                         "greedy)")
    ap.add_argument("--seeds", type=int, default=2,
                    help="MCTS seeds in the default portfolio")
    ap.add_argument("--workers", type=int, default=None,
                    help="portfolio thread-pool size")
    ap.add_argument("--full", action="store_true",
                    help="production configs instead of reduced()")
    ap.add_argument("--min-dims", type=int, default=10)
    ap.add_argument("--plan-store", default="results/plan_store",
                    help="plan cache directory")
    ap.add_argument("--no-plan-store", action="store_true",
                    help="disable the plan cache")
    ap.add_argument("--out", default="BENCH_zoo.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny cell + model subset so --measure finishes "
                         "in minutes (the CI fast path)")
    ap.add_argument("--profile", action="store_true",
                    help="run the sweep under cProfile + tracemalloc and "
                         "print per-model phase wall/alloc breakdowns "
                         "plus the hottest functions (slower; for "
                         "diagnosis, not benchmarking)")
    ap.add_argument("--verify", action="store_true",
                    help="statically verify every searched plan "
                         "(soundness rules) and match the predicted "
                         "collective multiset against compiled-HLO "
                         "collectives; write --verify-out")
    ap.add_argument("--verify-out", default="BENCH_verify.json")
    ap.add_argument("--no-conformance", action="store_true",
                    help="with --verify: skip the compiled-HLO "
                         "conformance harvest (pure static rules only)")
    ap.add_argument("--measure", action="store_true",
                    help="execute plan variants on a simulated device "
                         "mesh, calibrate the cost model, write "
                         "--measure-out")
    ap.add_argument("--measure-out", default="BENCH_measured.json")
    ap.add_argument("--measure-repeats", type=int, default=5,
                    help="timed executions per cell (median reported)")
    ap.add_argument("--measure-warmup", type=int, default=1)
    ap.add_argument("--measure-plans", type=int, default=4,
                    help="plan variants measured per model (>= 3)")
    ap.add_argument("--measure-timeout", type=float, default=900.0,
                    help="per-cell worker budget, seconds")
    ap.add_argument("--use-calibrated-hw", action="store_true",
                    help="price plans with the calibrated HardwareSpec "
                         "saved in the plan store by a previous "
                         "--measure run")
    ap.add_argument("--co-search", type=int, default=None, metavar="N",
                    help="mesh-shape co-search: enumerate every mesh "
                         "factorization of N devices (instead of "
                         "--mesh), search each, and compare the winner "
                         "against the best fixed 2-D mesh")
    ap.add_argument("--pods", default="1,2",
                    help="comma-separated pod counts for --co-search; "
                         "counts > 1 add a DCN-crossing 'pod' axis")
    ap.add_argument("--co-measure", action="store_true",
                    help="with --co-search: validate the winner, the "
                         "best fixed plan and the best multi-pod "
                         "candidate by measured execution, then "
                         "calibrate and re-rank")
    ap.add_argument("--cosearch-out", default="BENCH_meshsearch.json")
    args = ap.parse_args(argv)

    try:
        mesh = parse_mesh(args.mesh)
    except ValueError as e:
        ap.error(str(e))                        # usage + exit 2
    enable_compile_cache()
    store = None if args.no_plan_store else PlanStore(args.plan_store)
    hw = HardwareSpec()
    if args.use_calibrated_hw:
        cal = store.load_hardware() if store is not None else None
        if cal is None:
            ap.error("--use-calibrated-hw: no calibrated hardware in the "
                     "plan store; run with --measure first")
        hw = cal
        print(f"using calibrated hardware from {args.plan_store}")
    search_config = None
    if args.backend == "portfolio":
        search_config = zoo_portfolio(seeds=args.seeds,
                                      workers=args.workers or 2)

    if args.archs is not None:                  # explicit wins, always
        archs = tuple(args.archs.split(","))
    else:
        archs = SMOKE_ARCHS if args.smoke else tuple(ARCH_IDS)
    shape = None
    if args.smoke:
        shape = ZOO_SHAPE_SMOKE

    if args.co_search is not None:
        try:
            pods = tuple(int(p) for p in args.pods.split(","))
        except ValueError:
            ap.error(f"bad --pods {args.pods!r}: expected "
                     f"comma-separated integers, e.g. '1,2'")
        record = run_cosearch(
            args.co_search, archs=archs, pods=pods, shape=shape, hw=hw,
            backend=args.backend, search_config=search_config,
            plan_store=store, min_dims=args.min_dims,
            measure=args.co_measure, repeats=args.measure_repeats,
            timeout=args.measure_timeout)
        print()
        for row in record["results"]:
            if row["status"] == "ok":
                print(format_cosearch_table(row))
                m = row.get("measured")
                if m and "winner_calibrated" in m:
                    agree = ("agrees" if m["calibrated_agrees"]
                             else "DISAGREES")
                    print(f"calibrated winner: "
                          f"{m['winner_calibrated']} ({agree} with the "
                          f"default-hardware winner)")
                print()
            else:
                print(f"[{row['model']}] ERROR {row['error']}\n")
        out = pathlib.Path(args.cosearch_out)
        out.write_text(json.dumps(record, indent=2))
        print(f"wrote {out} ({record['total_seconds']}s)")
        if record["failures"]:
            for f in record["failures"]:
                print(f"CO-SEARCH FAILED {f}")
            raise SystemExit(1)
        return record
    captures: dict | None = \
        {} if (args.measure or args.verify) else None
    profiler = None
    if args.profile:
        import cProfile
        profiler = cProfile.Profile()
        profiler.enable()
    record = run_zoo(mesh, archs=archs, shape=shape, hw=hw,
                     backend=args.backend, search_config=search_config,
                     plan_store=store, full=args.full,
                     min_dims=args.min_dims, captures=captures,
                     profile=args.profile)
    if profiler is not None:
        profiler.disable()
        print(format_profile(record["results"]))
        import io
        import pstats
        buf = io.StringIO()
        pstats.Stats(profiler, stream=buf).sort_stats(
            "cumulative").print_stats(25)
        print("\n--profile: hottest functions (cProfile, cumulative)")
        print(buf.getvalue())

    print()
    print(format_table(record["results"]))
    ok = [r for r in record["results"] if r["status"] == "ok"]
    feasible = sum(r["feasible"] for r in ok)
    line = (f"\n{len(ok)}/{len(record['results'])} models partitioned, "
            f"{feasible} feasible, "
            f"total {record['total_seconds']}s")
    if store is not None:
        s = store.stats
        line += (f" | plan store: {s.hits} hits / {s.misses} misses "
                 f"({args.plan_store})")
    print(line)

    out = pathlib.Path(args.out)
    out.write_text(json.dumps(record, indent=2))
    print(f"wrote {out}")

    verify_failed = False
    if args.verify:
        print("\nverifying searched plans (static soundness + "
              "compiled-HLO conformance) ...", flush=True)
        vrec = verify_record(
            record, captures or {},
            timeout=args.measure_timeout,
            conformance=not args.no_conformance)
        print()
        print(format_verify_table(vrec))
        vout = pathlib.Path(args.verify_out)
        vout.write_text(json.dumps(vrec, indent=2))
        print(f"wrote {vout}")
        record["verified"] = vrec
        if vrec["failures"]:
            for f in vrec["failures"]:
                print(f"VERIFY FAILED {f}")
            verify_failed = True

    measure_failed = False
    if args.measure:
        from repro.launch.measure import format_measure_table, \
            measure_record
        print("\nmeasuring plan variants on the simulated "
              f"{args.mesh} mesh ({mesh.num_devices} devices) ...",
              flush=True)
        mrec = measure_record(
            record, captures or {}, repeats=args.measure_repeats,
            warmup=args.measure_warmup,
            plans_per_model=args.measure_plans,
            timeout=args.measure_timeout, plan_store=store)
        print()
        print(format_measure_table(mrec))
        cal = mrec["calibration"]
        if "mean_rel_err_before" in cal:
            print(f"\ncalibration over {cal['n_cells']} cells: mean "
                  f"relative runtime error "
                  f"{cal['mean_rel_err_before']:.2f} -> "
                  f"{cal['mean_rel_err_after']:.2f}")
        rho = mrec["spearman_mean"]
        if rho is not None:
            per = ", ".join(f"{m}={v['spearman']:.2f}"
                            for m, v in mrec["per_model"].items()
                            if v["spearman"] is not None)
            print(f"predicted-vs-measured Spearman rank correlation: "
                  f"{rho:.2f} ({per})")
        mout = pathlib.Path(args.measure_out)
        mout.write_text(json.dumps(mrec, indent=2))
        print(f"wrote {mout}")
        record["measured"] = mrec
        # driver failures fail the run; "oom"/"compile_error" are
        # legitimate feasibility outcomes and do not
        broken = [c for c in mrec["cells"]
                  if c["status"] in ("error", "timeout")]
        no_ok = mrec["cells"] and not any(
            c["status"] == "ok" for c in mrec["cells"])
        if broken or no_ok:
            for c in broken:
                print(f"MEASURE FAILED {c['model']}/{c['plan_label']}: "
                      f"{c['error'][:200]}")
            measure_failed = True

    if measure_failed or verify_failed or \
            any(r["status"] != "ok" for r in record["results"]):
        raise SystemExit(1)
    return record


if __name__ == "__main__":
    main()
