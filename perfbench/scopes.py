"""Device time by the program's named scopes, and device idle time under
its host spans.

    PYTHONPATH=src python3 -m perfbench.scopes --workload <cell> \
        --seed <n> --seconds <s> [--keep DIR]

Not part of a benchmark run.  On the chips the cell holds it plans and
compiles the cell's step as the harness does, runs the checked steps as
a warm-up, traces a window of ``--seconds`` and prints one JSON line
with the two reductions below as shares of the window.  ``--keep``
copies the trace and the compiled step's HLO text there.  Without the
cell's TPU chips it exits non-zero.

The program names its device work with ``jax.named_scope`` (``attn_bwd``,
``mlp``, ``head_loss``, ``optimizer``); JAX writes the scope into the
``op_name`` of each HLO instruction, also for the backward pass and the
recomputed forward (``transpose(jvp(mlp))``, ``rematted_computation/
mlp``).  The ``XLA Ops`` events of a TPU trace carry no op_name in their
stats, so :func:`reduce_scopes` takes it from the compiled module's
text by instruction name (:func:`op_names`) and counts each event toward
the first scope of ``SCOPES`` that is a part of that path.  The
flash-attention forward kernel is counted apart, as ``attn_fwd_share``
counts it; the rest is unscoped, with the instructions the compiler
made without an op_name.  The program's host spans are
``jax.profiler.TraceAnnotation`` named ``toast.<name>``
(``repro.spans``); :func:`idle_under_spans` gives the device idle time
that falls inside each of them.  Devices are ``tracefile.planes_from_
profile``'s dict; host spans are ``(start_ns, end_ns, name)``.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import os
import re
import shutil
import tempfile

from perfbench import tracefile

SCOPES = ("attn_bwd", "mlp", "head_loss", "optimizer")
KERNEL = "toast_kernel__flash_attention__causal"
SPAN_PREFIX = "toast."
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"',
                    re.M)
_SCOPE = {s: re.compile(r"(?:^|[/(;])" + s + r"(?:$|[/);])")
          for s in SCOPES}


def op_names(hlo_text: str) -> dict[str, str]:
    """``{instruction name: op_name}`` of a compiled module's text."""
    return dict(_INSTR.findall(hlo_text))


def scope_of(op_name: str) -> str | None:
    """The first scope of ``SCOPES`` that is a part of ``op_name``."""
    for s in SCOPES:
        if _SCOPE[s].search(op_name):
            return s
    return None


def _window(host, window_span):
    steps = [(s, e) for s, e, n in host if n == window_span]
    if not steps:
        raise ValueError(f"no {window_span!r} spans in the trace")
    return min(s for s, _ in steps), max(e for _, e in steps)


def _work(dev, lo, hi):
    """The ``XLA Ops`` events of one device that are work inside
    ``[lo, hi]``, clipped to it, as ``(start, end, text, name,
    opcode)``: what ``tracefile.reduce_trace`` counts as busy there."""
    for s, e, text in dev["ops"]:
        if e <= lo or s >= hi:
            continue
        name, opcode, _ = tracefile.instruction(text)
        if opcode in tracefile.CONTAINERS or opcode.endswith("-start") \
                or opcode.endswith("-done"):
            continue
        yield max(s, lo), min(e, hi), text, name, opcode


def reduce_scopes(devices: dict, host: list, op_name_of: dict, *,
                  window_span: str) -> dict:
    """Device seconds in the window under each scope, averaged over the
    devices.

    ``op_name_of`` is :func:`op_names` of the step that ran.  Returns
    ``window_s``, ``devices``, ``scopes`` (seconds by scope),
    ``kernel_s`` (the flash-attention forward kernel), ``unscoped_s``,
    ``busy_s`` (the union of the events counted), and ``unnamed_s``, the
    part of ``unscoped_s`` whose instructions have no op_name.
    """
    lo, hi = _window(host, window_span)
    total = collections.Counter()
    busy = 0.0
    for dev in devices.values():
        spans = []
        for s, e, text, name, opcode in _work(dev, lo, hi):
            spans.append((s, e))
            path = op_name_of.get(text.partition(" = ")[0].lstrip("%"))
            if opcode == "custom-call" and name.startswith(KERNEL):
                key = "kernel"
            elif path is None:
                key = "unnamed"
            else:
                key = scope_of(path) or "unscoped"
            total[key] += (e - s) * 1e-9
        busy += tracefile.length(tracefile.union(spans)) * 1e-9
    n = max(len(devices), 1)
    return {"window_s": (hi - lo) * 1e-9, "devices": len(devices),
            "scopes": {s: total[s] / n for s in SCOPES},
            "kernel_s": total["kernel"] / n,
            "unscoped_s": (total["unscoped"] + total["unnamed"]) / n,
            "unnamed_s": total["unnamed"] / n, "busy_s": busy / n}


def idle_under_spans(devices: dict, host: list, *, window_span: str,
                     prefix: str = SPAN_PREFIX) -> dict[str, float]:
    """Device idle seconds in the window that fall inside each host span
    whose name starts with ``prefix``, by span name, averaged over the
    devices.  Idle is the window less the union of the device's work
    (as :func:`reduce_scopes` counts it)."""
    lo, hi = _window(host, window_span)
    marks = collections.defaultdict(list)
    for s, e, n in host:
        if n.startswith(prefix):
            marks[n].append((s, e))
    marks = {n: tracefile.clip(tracefile.union(v), lo, hi)
             for n, v in marks.items()}
    out = collections.Counter()
    for dev in devices.values():
        busy = tracefile.union((s, e) for s, e, *_ in _work(dev, lo, hi))
        idle = tracefile.subtract([(lo, hi)], busy)
        for n, spans in marks.items():
            inside = tracefile.length(idle) - tracefile.length(
                tracefile.subtract(idle, spans))
            out[n] += inside * 1e-9
    n_dev = max(len(devices), 1)
    return {n: out[n] / n_dev for n in sorted(marks)}


def clock_check(devices: dict, host: list, *, start_span: str,
                end_span: str) -> dict:
    """How the host and device clocks line up: the least time from a
    ``start_span`` opening to the next device event's start
    (``lead_s``), and from a device event's end to the next
    ``end_span`` closing (``lag_s``).  Both are real latencies and so at
    least 0 on one clock; an offset of the device's clock moves one down
    by as much as it moves the other up, so it lies between ``-lag_s``
    and ``lead_s``."""
    evs = sorted((s, e) for dev in devices.values()
                 for s, e, _ in dev["ops"])
    starts = [s for s, _ in evs]
    ends = sorted(e for _, e in evs)
    lead = lag = None
    for s, e, n in host:
        if n == start_span:
            i = bisect.bisect_left(starts, s)
            if i < len(starts):
                d = starts[i] - s
                lead = d if lead is None else min(lead, d)
        elif n == end_span:
            i = bisect.bisect_right(ends, e) - 1
            if i >= 0:
                d = e - ends[i]
                lag = d if lag is None else min(lag, d)
    return {"lead_s": None if lead is None else lead * 1e-9,
            "lag_s": None if lag is None else lag * 1e-9}


def shares(scoped: dict, idle: dict) -> dict:
    """The readings of :func:`reduce_scopes` and
    :func:`idle_under_spans` as shares of the window, in %, and their
    sum (``accounted``), which is 100 where no two events of a device
    overlap."""
    w = scoped["window_s"]
    out = {f"{s}_share": 100.0 * v / w for s, v in scoped["scopes"].items()}
    out["attn_fwd_share"] = 100.0 * scoped["kernel_s"] / w
    out["unscoped_share"] = 100.0 * scoped["unscoped_s"] / w
    out["idle_share"] = 100.0 * (1.0 - scoped["busy_s"] / w)
    out["input_wait_share"] = 100.0 * idle.get(
        SPAN_PREFIX + "data.wait", 0.0) / w
    out["accounted"] = sum(out[k] for k in out if k != "input_wait_share")
    return out


def main(argv: list[str] | None = None) -> None:
    import jax
    from jax.profiler import ProfileData

    from perfbench import catalog, harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    bench = catalog.benchmark()
    cell = catalog.workload(bench, args.workload)
    conf = catalog.config(bench, cell["config"])
    mix = catalog.traffic(cell["traffic"])
    devices = jax.devices()[:cell["chips"]]
    dev = harness.device_info(devices)
    harness.log(f"[device] {dev}")
    if dev["platform"] != "tpu" or dev["count"] < cell["chips"]:
        raise SystemExit(f"perfbench.scopes: {args.workload} needs "
                         f"{cell['chips']} TPU chips")
    harness.enable_compile_cache()
    tc = harness.TrainCell(conf, mix, devices)
    harness.log(f"[plan] cost={tc.plan.cost:.6f} "
                f"hash={tc.plan_evidence()['plan_hash']} "
                f"phases={tc.phase_seconds} "
                f"partition={tc.plan.eval_stats.get('phase_seconds')}")
    tc.start(args.seed)
    tc.first_steps(args.seed)
    wait0, empty0 = tc.pipe.wait_s, tc.pipe.waits_empty
    trace_dir = tempfile.mkdtemp(prefix="perfbench_scopes_")
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            steps, window_s = tc.window(args.seconds)
        finally:
            jax.profiler.stop_trace()
        wait_s = tc.pipe.wait_s - wait0
        waits_empty = tc.pipe.waits_empty - empty0
        hlo = tc.compiled.as_text()
        tc.stop()
        path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(path, os.path.join(args.keep, "trace.xplane.pb"))
            with open(os.path.join(args.keep, "step.hlo.txt"), "w") as f:
                f.write(hlo)
        devs, host = tracefile.planes_from_profile(
            ProfileData.from_file(path))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    scoped = reduce_scopes(devs, host, op_names(hlo),
                           window_span=harness.STEP_SPAN)
    idle = idle_under_spans(devs, host, window_span=harness.STEP_SPAN)
    out = {"steps": steps, "window_s": window_s,
           "tokens_per_s": steps * tc.tokens_per_step / window_s,
           "pipeline": {"wait_s": wait_s, "waits_empty": waits_empty},
           "shares": shares(scoped, idle),
           "scoped": scoped, "idle_under_spans": idle,
           "clock": clock_check(devs, host, start_span="dispatch",
                                end_span="block"),
           "program_spans": sorted({n for _, _, n in host
                                    if n.startswith(SPAN_PREFIX)})}
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
