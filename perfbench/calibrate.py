"""Readings that set a cell's limits: the program, the control, faults.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--out FILE]

Not part of a benchmark run.  In one process, on the chips the cell
holds, it plans and compiles the cell's step once, then for every seed
runs the checked steps through the timed path and the float32 reference,
and prints the gaps between them (the readings of sound runs, whose
largest sets the lower end of each limit).  For the control seeds it
also runs the reference in float8 (``model_ref``'s control) in the
program's place, and for the fault seeds the reference with half of
every batch left out (the mean over the rest) and with the attention
mask dropped, and prints their gaps to the float32 reference: the upper
ends.  A state left unchanged reads 1
on the gradient and change gaps and needs no run.  One JSON line per
reading goes to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax

    from perfbench import catalog, harness
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    fault = {int(s) for s in args.fault_seeds.split(",") if s}
    bench = catalog.benchmark()
    cell = catalog.workload(bench, args.workload)
    conf = catalog.config(bench, cell["config"])
    mix = catalog.traffic(cell["traffic"])
    devices = jax.devices()[:cell["chips"]]
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        raise SystemExit(f"calibrate: needs {cell['chips']} TPU chips")
    harness.enable_compile_cache()
    tc = harness.TrainCell(conf, mix, devices)
    print(f"[plan] {tc.plan_evidence()}", flush=True)
    out = open(args.out, "a") if args.out else None

    def emit(kind, seed, g, seconds):
        line = {"workload": args.workload, "kind": kind, "seed": seed,
                "loss_gap": g["loss_gap"], "grad_gap": g["grad_gap"],
                "change_gap": g["change_gap"], "worst": g["worst_leaf"],
                "unmoved": g["unmoved"], "seconds": seconds}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    for seed in seeds:
        tc.start(seed)
        got = tc.first_steps(seed)
        tc.stop()
        t0 = time.perf_counter()
        want = harness.reference(conf, mix, seed, devices)
        emit("program", seed, harness.gaps(got, want),
             time.perf_counter() - t0)
        if seed in control:
            t0 = time.perf_counter()
            ctl = harness.reference(conf, mix, seed, devices,
                                    precision="fp8")
            emit("control_fp8", seed, harness.gaps(ctl, want),
                 time.perf_counter() - t0)
        if seed in fault:
            t0 = time.perf_counter()
            half = harness.reference(conf, mix, seed, devices,
                                     rows=mix["batch"] // 2)
            emit("fault_half_batch", seed, harness.gaps(half, want),
                 time.perf_counter() - t0)
            t0 = time.perf_counter()
            full = harness.reference(conf, mix, seed, devices, causal=False)
            emit("fault_noncausal", seed, harness.gaps(full, want),
                 time.perf_counter() - t0)
    if out:
        out.close()


if __name__ == "__main__":
    main()
