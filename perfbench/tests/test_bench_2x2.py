"""The 2x2 cell (``phi3_mini.train.2x2``) at a small size on four CPU
devices: an honest run, the timed path broken underneath, the control.

Each case runs in a subprocess that gives the CPU backend four devices
before JAX starts (the device count is fixed at JAX's first use), so
the searched plan, its ``shard_map`` kernel sites and the reference's
row-split mesh all run as on the four chips.  The model keeps the
cell's kind (MHA, no q/k/v bias) at head size 32 and 2 layers; the
limits are the cell's own (``limits/<cell>.json``).
"""

import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from perfbench import catalog, counts, harness, model_ref

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "phi3_mini.train.2x2"
SEED = 2**31 + 11

CASE = r"""
import argparse, json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from perfbench import catalog, harness
from perfbench.tests import test_bench_2x2 as t
harness.enable_compile_cache = lambda: "off"
case = sys.argv[2]
out = {"devices": len(jax.devices())}
if case == "control":
    bench = catalog.benchmark()
    conf, mix = t.tiny(catalog.config(bench, "phi3_mini"),
                       catalog.traffic("train.2x2.s2k"))
    devices = jax.devices()[:4]
    want = harness.reference(conf, mix, t.SEED, devices)
    ctl = harness.reference(conf, mix, t.SEED, devices, precision="fp8")
    g = harness.gaps(ctl, want)
    out["gaps"] = {k: g[k] for k in ("loss_gap", "grad_gap", "change_gap")}
else:
    from perfbench.tests import test_bench_run as faults
    cells = []

    class Kept(harness.TrainCell):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            cells.append(self)

    harness.TrainCell = Kept
    args = argparse.Namespace(workload=t.CELL, seed=t.SEED, seconds=0.5,
                              trace=0)
    r = harness.run(args, require_tpu=False, overrides=t.tiny,
                    make_step=getattr(faults, case, None))
    out.update(correct=r["correct"], checks=r["checks"],
               sites=[[s["site"], s["impl"], s["sharded"]]
                      for s in cells[0].plan.kernel_sites],
               shard_mapped=cells[0].kernels_shard_mapped,
               mesh=dict(cells[0].mesh.shape))
print("RESULT " + json.dumps(out))
"""


def tiny(conf, mix):
    """The cell cut to a CPU's size; MHA as published, head size 32."""
    conf = dict(conf, hidden_size=128, intermediate_size=256,
                num_attention_heads=4, num_key_value_heads=4,
                num_hidden_layers=2, vocab_size=512, head_dim=32)
    mix = dict(mix, batch=8, seq_len=64,
               reference=dict(mix["reference"], q_block=32))
    return conf, mix


def run_case(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CASE, str(ROOT), case],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    lines = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-3000:]
    out = json.loads(lines[-1][len("RESULT "):])
    assert out["devices"] == 4
    return out


def test_honest_run_is_correct_with_sharded_kernel_sites():
    r = run_case("honest")
    assert r["correct"], r["checks"]
    assert r["mesh"] == {"data": 2, "model": 2}
    assert [s[0] for s in r["sites"]] == ["flash_attention:0",
                                          "flash_attention:1"]
    assert all(impl == "pallas" and sharded for _, impl, sharded
               in r["sites"]), r["sites"]
    assert r["shard_mapped"]


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_broken_timed_path_is_not_correct(fault):
    r = run_case(fault)
    assert not r["correct"], r["checks"]


def test_control_in_float8_is_not_correct():
    g = run_case("control")["gaps"]
    limits = catalog.limits(CELL)
    assert any(g[k] > limits[k] for k in limits), g


def test_reference_draws_the_programs_phi3_weights_bit_for_bit():
    from repro.models import transformer
    bench = catalog.benchmark()
    conf, _ = tiny(catalog.config(bench, "phi3_mini"),
                   catalog.traffic("train.2x2.s2k"))
    m = model_ref.model_dims(conf)
    assert m["kv"] == m["h"] and not m["qkv_bias"]
    key = harness.seed_key(SEED)
    mine = model_ref.init_params(m, key)
    theirs = transformer.init_params(harness.model_config(conf), key)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(theirs)
    assert "bq" not in mine["layers"][0]["mix"]
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_phi3_config_file_counts_by_hand_and_matches_the_program():
    from repro.configs import get_config
    conf = catalog.load_json(catalog.HERE / "configs" / "phi3_mini.json")
    per_layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    assert counts.layer_matmul_params(conf) == per_layer
    fwd = 16 * (2 * per_layer + 2 * 2048 * 96 * 32) + 2 * 3072 * 32064
    got = counts.train_flops_per_token(conf, 2048)
    assert got == 3 * fwd
    assert got == pytest.approx(12.07e9, rel=1e-3)
    # every published width as the program's own phi3_mini runs it
    cfg = harness.model_config(conf)
    prog = get_config("phi3_mini")
    for key in ("d_model", "num_heads", "num_kv_heads", "d_ff",
                "vocab_size", "rope_theta", "qkv_bias"):
        assert getattr(cfg, key) == getattr(prog, key), key
    assert cfg.resolved_head_dim == prog.resolved_head_dim == 96
    assert (conf["published"]["num_hidden_layers"], prog.num_layers) == \
        (32, 32)
