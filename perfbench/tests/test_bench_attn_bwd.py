"""``attn_bwd_roofline`` on a synthesised trace, the forward kernel's
metrics beside the backward's events, and the backward's sites in the
2x2 cell's plan at a small size on four CPU devices."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import catalog, peaks, tracefile
from perfbench.metrics import (attn_bwd_roofline, attn_fwd_roofline,
                               attn_fwd_share)
from perfbench.tests import test_bench_2x2

MS = 1_000_000
BWD = "toast_kernel__flash_attention_bwd__causal_1"
SHAPE = "bf16[4,32,2048,96]"
TEXTS = {
    "fwd": ("%toast_kernel__flash_attention__causal_1.2 = "
            f"{SHAPE}{{3,2,1,0}} custom-call({SHAPE} %b), "
            'custom_call_target="tpu_custom_call"'),
    "stats": (f"%{BWD}_stats.1 = f32[4,32,4,8,512]{{4,3,2,1,0}} "
              f"custom-call({SHAPE} %q), "
              'custom_call_target="tpu_custom_call"'),
    "dkv": (f"%{BWD}_dkv.1 = ({SHAPE}{{3,2,1,0}}, {SHAPE}{{3,2,1,0}}) "
            f"custom-call({SHAPE} %q), "
            'custom_call_target="tpu_custom_call"'),
    "dq": (f"%{BWD}_dq.1 = {SHAPE}{{3,2,1,0}} custom-call({SHAPE} %q), "
           'custom_call_target="tpu_custom_call"'),
}
PHI3 = catalog.load_json(catalog.HERE / "configs" / "phi3_mini.json")
V5E = peaks.peaks_for("TPU v5 lite")


def record(kernels, ms_each=2):
    """A traced record of two devices; each runs ``kernels`` one after
    the other, ``ms_each`` milliseconds apiece, in a 100 ms window."""
    ops = [(i * ms_each * MS, (i + 1) * ms_each * MS, TEXTS[k])
           for i, k in enumerate(kernels)]
    devices = {f"/device:TPU:{d}": {"ops": ops, "async": []}
               for d in range(2)}
    tr = tracefile.reduce_trace(devices, [(0, 100 * MS, "train")],
                                window_span="train", host_spans=())
    return {"trace": tr, "peak": V5E, "config": PHI3}


def least_ms():
    """The least time of one backward call at the 2x2 cell's shape."""
    return 1e3 * max(
        2.5 * 2 * 4 * 32 * 2048 ** 2 * 96 / V5E["bf16_flops_per_s"],
        2 * 4 * 2048 * 96 * 7 * 32 / V5E["hbm_bytes_per_s"])


def test_counts_least_time_once_per_dkv_event():
    r = record(["stats", "dkv", "dq"], ms_each=2)
    # two devices, one dkv event each; 12 ms spent over all six events
    want = 100.0 * 2 * least_ms() / 12
    assert attn_bwd_roofline.read(r) == pytest.approx(want)
    assert "flops" in attn_bwd_roofline.note(r).split("(")[0]


@pytest.mark.parametrize("kernels", [[], ["fwd"], ["fwd", "fwd"]])
def test_reads_null_without_backward_events(kernels):
    r = record(kernels)
    assert attn_bwd_roofline.read(r) is None
    assert attn_bwd_roofline.note(r) == "no backward kernel events"
    assert attn_bwd_roofline.read(dict(r, trace=None)) is None


@pytest.mark.parametrize("kernels", [
    ["stats", "dkv", "dq"], ["dkv", "dq"], ["stats", "dkv"],
    ["stats", "dkv", "dq", "stats", "dkv", "dq"]])
def test_never_passes_100_at_the_least_time(kernels):
    """Even where every event takes just the least time of one whole
    backward, the share is at most 100%."""
    ms_each = least_ms()
    r = record(kernels, ms_each=ms_each)
    got = attn_bwd_roofline.read(r)
    assert 0 < got <= 100.0 + 1e-9
    assert got == pytest.approx(100.0 * kernels.count("dkv") /
                                len(kernels))


def test_forward_metrics_ignore_backward_events():
    alone, beside = record(["fwd"]), record(["fwd", "stats", "dkv", "dq"])
    for metric in (attn_fwd_share, attn_fwd_roofline):
        assert metric.read(beside) == pytest.approx(metric.read(alone))
    assert not BWD.startswith(attn_fwd_share.KERNEL)


SITES = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from perfbench import catalog, harness
from perfbench.tests import test_bench_2x2 as t
bench = catalog.benchmark()
cell = catalog.workload(bench, t.CELL)
conf, mix = t.tiny(catalog.config(bench, cell["config"]),
                   catalog.traffic(cell["traffic"]))
tc = harness.TrainCell(conf, mix, jax.devices()[:4])
print("RESULT " + json.dumps({
    "devices": len(jax.devices()),
    "sites": [[s["impl"], s["sharded"], s["bwd_impl"]]
              for s in tc.plan.kernel_sites],
    "shard_mapped": tc.kernels_shard_mapped}))
"""


def test_2x2_cell_runs_the_backward_where_its_forward_site_runs():
    """The harness's plan of the 2x2 cell records a Pallas backward,
    sharded through ``shard_map``, on each site its forward is Pallas."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    root = str(test_bench_2x2.ROOT)
    p = subprocess.run([sys.executable, "-c", SITES, root], cwd=root,
                       env=env, capture_output=True, text=True, timeout=120)
    lines = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-3000:]
    out = json.loads(lines[-1][len("RESULT "):])
    assert out["devices"] == 4
    assert out["sites"] == [["pallas", True, "pallas"]] * 2, out["sites"]
    assert out["shard_mapped"]
