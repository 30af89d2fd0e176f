"""Substrate tests: data pipeline, checkpointing (atomic/async/elastic),
gradient compression, optimizer."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional test extra; see pyproject.toml
from hypothesis import given, settings, strategies as st

from repro.ckpt.checkpoint import CheckpointManager, latest_step, save
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, Pipeline, batch_at
from repro.optim import adam, compression


CFG = get_config("qwen2_05b").reduced()
SHAPE = ShapeConfig("t", 32, 8, "train")


class TestDataPipeline:
    def test_deterministic_per_step(self):
        d = DataConfig(seed=7)
        b1 = batch_at(CFG, SHAPE, d, step=3)
        b2 = batch_at(CFG, SHAPE, d, step=3)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_steps_differ(self):
        d = DataConfig(seed=7)
        assert not np.array_equal(batch_at(CFG, SHAPE, d, 0)["tokens"],
                                  batch_at(CFG, SHAPE, d, 1)["tokens"])

    def test_host_sharding_disjoint(self):
        b0 = batch_at(CFG, SHAPE, DataConfig(num_hosts=2, host_id=0), 0)
        b1 = batch_at(CFG, SHAPE, DataConfig(num_hosts=2, host_id=1), 0)
        assert b0["tokens"].shape[0] == SHAPE.global_batch // 2
        assert not np.array_equal(b0["tokens"], b1["tokens"])

    def test_prefetch_iterator_matches_random_access(self):
        d = DataConfig(seed=1)
        pipe = Pipeline(CFG, SHAPE, d, start_step=5)
        try:
            step, batch = next(pipe)
            assert step == 5
            np.testing.assert_array_equal(
                batch["tokens"], batch_at(CFG, SHAPE, d, 5)["tokens"])
        finally:
            pipe.close()

    def test_restart_recovery(self):
        """A restarted host regenerates its exact shard (straggler /
        preemption recovery without coordination)."""
        d = DataConfig(seed=2, num_hosts=4, host_id=3)
        before = batch_at(CFG, SHAPE, d, 17)
        after = batch_at(CFG, SHAPE, d, 17)        # "after restart"
        np.testing.assert_array_equal(before["targets"], after["targets"])


class TestCheckpoint:
    def _tree(self, k=0):
        return {"a": jnp.arange(12.0).reshape(3, 4) + k,
                "b": {"c": jnp.ones((5,), jnp.int32) * k}}

    def test_roundtrip(self, tmp_path):
        save(tmp_path, 3, self._tree(1))
        mgr = CheckpointManager(tmp_path)
        step, restored = mgr.restore(self._tree(0))
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.asarray(self._tree(1)["a"]))

    def test_atomic_no_tmp_visible(self, tmp_path):
        save(tmp_path, 1, self._tree())
        names = [p.name for p in pathlib.Path(tmp_path).iterdir()]
        assert "step_00000001" in names
        assert not any(n.endswith(".tmp") for n in names)

    def test_latest_and_retention(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, self._tree(s))
        assert mgr.latest_step() == 4
        steps = sorted(p.name for p in pathlib.Path(tmp_path).iterdir())
        assert len(steps) == 2                     # retention enforced

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save_async(7, self._tree(7))
        mgr.wait()
        assert latest_step(tmp_path) == 7

    def test_elastic_restore_onto_sharding(self, tmp_path):
        """Restore re-places leaves with explicit shardings (any mesh)."""
        save(tmp_path, 1, self._tree(2))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("x",))
        sh = jax.sharding.NamedSharding(mesh,
                                        jax.sharding.PartitionSpec())
        shardings = jax.tree_util.tree_map(lambda _: sh, self._tree())
        mgr = CheckpointManager(tmp_path)
        _, restored = mgr.restore(self._tree(), shardings=shardings)
        assert restored["a"].sharding == sh

    def test_shape_mismatch_rejected(self, tmp_path):
        save(tmp_path, 1, self._tree())
        mgr = CheckpointManager(tmp_path)
        bad = {"a": jnp.zeros((2, 2)), "b": {"c": jnp.zeros((5,), jnp.int32)}}
        with pytest.raises(ValueError):
            mgr.restore(bad)


class TestCompression:
    def _grads(self, key):
        return {"w": jax.random.normal(key, (64, 32)),
                "b": jax.random.normal(jax.random.fold_in(key, 1), (32,))}

    @pytest.mark.parametrize("scheme", ["topk", "int8"])
    def test_error_feedback_preserves_signal(self, scheme):
        """Sum of compressed grads over steps ≈ sum of true grads (error
        feedback means nothing is permanently lost)."""
        cfg = compression.CompressionConfig(scheme=scheme, topk_ratio=0.05)
        key = jax.random.PRNGKey(0)
        g = self._grads(key)
        state = compression.init(g)
        total_sent = jax.tree_util.tree_map(jnp.zeros_like, g)
        N = 120
        for i in range(N):
            sent, state, _ = compression.compress(cfg, state, g)
            total_sent = jax.tree_util.tree_map(jnp.add, total_sent, sent)
        # after N steps: total_sent + residual == N * g, residual bounded
        for ks in ("w", "b"):
            approx = np.asarray(total_sent[ks]) / N
            np.testing.assert_allclose(approx, np.asarray(g[ks]),
                                       atol=0.35)

    def test_topk_sparsity(self):
        cfg = compression.CompressionConfig(scheme="topk", topk_ratio=0.02)
        g = self._grads(jax.random.PRNGKey(1))
        state = compression.init(g)
        sent, _, ratio = compression.compress(cfg, state, g)
        nz = np.count_nonzero(np.asarray(sent["w"]))
        assert nz <= int(64 * 32 * 0.02) + 1
        assert ratio < 0.1

    def test_none_passthrough(self):
        cfg = compression.CompressionConfig(scheme="none")
        g = self._grads(jax.random.PRNGKey(2))
        state = compression.init(g)
        sent, _, ratio = compression.compress(cfg, state, g)
        assert ratio == 1.0
        np.testing.assert_array_equal(np.asarray(sent["w"]),
                                      np.asarray(g["w"]))


class TestAdam:
    def test_descends_quadratic(self):
        cfg = adam.AdamConfig(lr=0.1, warmup_steps=0, weight_decay=0.0)
        params = {"x": jnp.array([5.0, -3.0])}
        state = adam.init(cfg, params)
        for _ in range(200):
            grads = {"x": 2 * params["x"]}        # d/dx x^2
            params, state, _ = adam.apply_updates(cfg, state, params, grads)
        assert float(jnp.abs(params["x"]).max()) < 0.5

    def test_grad_clip(self):
        g = {"x": jnp.full((4,), 100.0)}
        clipped, norm = adam.clip_by_global_norm(g, 1.0)
        assert float(norm) == pytest.approx(200.0)
        assert float(adam.global_norm(clipped)) == pytest.approx(1.0, rel=1e-3)

    @settings(max_examples=10, deadline=None)
    @given(lr=st.floats(1e-5, 1e-2), steps=st.integers(1, 5))
    def test_state_dtype_and_finiteness(self, lr, steps):
        cfg = adam.AdamConfig(lr=lr, state_dtype="bfloat16")
        params = {"w": jnp.ones((8, 8))}
        state = adam.init(cfg, params)
        assert state.m["w"].dtype == jnp.bfloat16
        for _ in range(steps):
            grads = {"w": jnp.ones((8, 8)) * 0.1}
            params, state, gn = adam.apply_updates(cfg, state, params, grads)
        assert np.isfinite(np.asarray(params["w"])).all()
