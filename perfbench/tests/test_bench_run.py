"""A whole run of a cell at a small size on the CPU, honest and with
the timed path broken underneath; the control; the command without a
TPU.

The harness's look for a chip is skipped (``require_tpu=False``) and the
configuration is cut to a size the CPU runs in seconds.  The limits are
the cell's own (``limits/<cell>.json``).
"""

import argparse
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from perfbench import catalog, harness, model_ref

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "qwen2_05b.train.s1k"
SEED = 2**31 + 5


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """Leave JAX's persistent cache as the rest of the suite has it."""
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


def tiny(conf, mix):
    conf = dict(conf, hidden_size=128, intermediate_size=256,
                num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=2, vocab_size=512, head_dim=32)
    mix = dict(mix, batch=4, seq_len=64,
               reference=dict(mix["reference"], q_block=32))
    return conf, mix


def run_tiny(make_step=None):
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.5,
                              trace=0)
    return harness.run(args, require_tpu=False, overrides=tiny,
                       make_step=make_step)


def test_reference_draws_the_programs_weights_bit_for_bit():
    from repro.models import transformer
    bench = catalog.benchmark()
    conf, _ = tiny(catalog.config(bench, "qwen2_05b"),
                   catalog.traffic("train.s1k"))
    key = harness.seed_key(SEED)
    mine = model_ref.init_params(model_ref.model_dims(conf), key)
    theirs = transformer.init_params(harness.model_config(conf), key)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_seed_key_keeps_every_bit():
    a, b = harness.seed_key(1), harness.seed_key(1 + 2**32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_honest_run_is_correct_and_prints_no_device_metric():
    r = run_tiny()
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"train_tokens_per_s", "plan_s", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["attempted"] >= 1


def unchanged_state(cfg, opt):
    from repro.train.steps import make_train_step
    step = make_train_step(cfg, opt)

    def bad(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return bad


def half_batch(cfg, opt):
    from repro.train.steps import make_train_step
    step = make_train_step(cfg, opt)

    def bad(state, batch):
        half = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch)
        return step(state, half)
    return bad


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def test_broken_timed_path_is_not_correct(fault):
    r = run_tiny(make_step=fault)
    assert not r["correct"], r["checks"]


def test_control_in_float8_is_not_correct():
    bench = catalog.benchmark()
    conf, mix = tiny(catalog.config(bench, "qwen2_05b"),
                     catalog.traffic("train.s1k"))
    limits = catalog.limits(CELL)
    devices = jax.devices()[:1]
    want = harness.reference(conf, mix, SEED, devices)
    ctl = harness.reference(conf, mix, SEED, devices, precision="fp8")
    g = harness.gaps(ctl, want)
    assert any(g[k] > limits[k] for k in limits), g


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL,
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"metrics"' not in p.stdout
