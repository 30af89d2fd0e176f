"""Fused kernel sites through the whole stack (docs/kernels.md).

Trace -> fused IR ops -> NDA color propagation -> joint kernel+sharding
search -> ``plan.kernel_sites`` records -> serialization round-trip ->
static verify -> ``plan.apply`` execution, on small direct-call programs
plus one real zoo model traced with ``use_pallas=True``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Request, Session
from repro.core.cost_model import MeshSpec
from repro.core.partitioner import ShardingPlan
from repro.core.search import BeamConfig
from repro.kernels import ops, registry

MESH = MeshSpec(("data", "model"), (2, 2))


def sh(*s):
    return jax.ShapeDtypeStruct(s, jnp.float32)


def attn_loss(d):
    o = ops.attention(d["q"], d["k"], d["v"], causal=True)
    return jnp.sum(o * o)


ATTN_ARGS = ({"q": sh(2, 128, 4, 32), "k": sh(2, 128, 4, 32),
              "v": sh(2, 128, 4, 32)},)
ATTN_NAMES = ({"q": ("batch", "seq", "heads", "head_dim"),
               "k": ("batch", "seq", "heads", "head_dim"),
               "v": ("batch", "seq", "heads", "head_dim")},)


def lru_loss(d):
    h = ops.rg_lru(jax.nn.sigmoid(d["a"]), d["b"])
    return jnp.sum(h * h)


LRU_ARGS = ({"a": sh(4, 128, 256), "b": sh(4, 128, 256)},)
LRU_NAMES = ({"a": ("batch", "seq", "channels"),
              "b": ("batch", "seq", "channels")},)


def kernel_ops(prog, name=None):
    return [(i, op) for i, op in enumerate(prog.ops)
            if op.prim.startswith(registry.KERNEL_PRIM_PREFIX)
            and (name is None or op.prim == f"kernel:{name}")]


def beam_request(names, **kw):
    kw.setdefault("mesh", MESH)
    kw.setdefault("min_dims", 1)
    kw.setdefault("backend", "beam")
    kw.setdefault("search_config", BeamConfig(width=4, patience=1))
    return Request(logical_axes=names, **kw)


class TestFusedTrace:
    def test_attention_records_one_fused_op(self):
        sess = Session(attn_loss, ATTN_ARGS)
        kops = kernel_ops(sess.artifacts.prog, "flash_attention")
        assert len(kops) == 1
        _, op = kops[0]
        spec = registry.spec_for_prim(op.prim)
        assert spec is not None
        assert len(op.operands) == len(spec.operand_roles)
        assert bool(op.params.get("causal"))

    def test_grad_traces_fused_backward(self):
        def step(d):
            return jax.grad(attn_loss)(d)["q"].sum()
        sess = Session(step, ATTN_ARGS)
        prims = {op.prim for _, op in kernel_ops(sess.artifacts.prog)}
        assert "kernel:flash_attention" in prims
        assert "kernel:flash_attention_bwd" in prims

    def test_rg_lru_records_fused_op(self):
        sess = Session(lru_loss, LRU_ARGS)
        kops = kernel_ops(sess.artifacts.prog, "rg_lru")
        assert len(kops) == 1
        _, op = kops[0]
        assert len(op.operands) == 2


class TestKernelSites:
    @pytest.fixture(scope="class")
    def attn_plan(self):
        sess = Session(attn_loss, ATTN_ARGS)
        return sess, sess.partition(beam_request(ATTN_NAMES))

    def test_site_records_impl_decision(self, attn_plan):
        _, plan = attn_plan
        sites = [r for r in plan.kernel_sites
                 if r["kernel"] == "flash_attention"]
        assert len(sites) == 1
        r = sites[0]
        assert r["site"] == "flash_attention:0"
        assert r["impl"] in registry.KERNELS["flash_attention"].impls
        assert len(r["in_specs"]) == 3 and len(r["out_specs"]) == 1

    def test_pallas_site_records_its_tiling(self, attn_plan):
        """A Pallas flash_attention record carries the forward kernel's
        tiling at the site's per-device shape."""
        _, plan = attn_plan
        r = next(r for r in plan.kernel_sites
                 if r["kernel"] == "flash_attention")
        assert r["impl"] == "pallas"
        # 128 f32 tokens of head dim 32, causal: one 128 x 128 tile
        t = registry.flash_tiling(128, 128, 32, True, 4)
        assert (r["block_q"], r["block_k"], r["tiles_total"],
                r["tiles_computed"]) == (t.block_q, t.block_k,
                                         t.tiles_total, t.tiles_computed)
        assert (t.tiles_total, t.tiles_computed) == (1, 1)

    def test_blocked_roles_never_sharded(self, attn_plan):
        _, plan = attn_plan
        for r in plan.kernel_sites:
            spec = registry.KERNELS[r["kernel"]]
            for roles, pspec in zip(spec.operand_roles, r["in_specs"]):
                for role, entry in zip(roles, pspec):
                    if role in spec.blocked:
                        assert entry is None, (r["site"], role)

    def test_backward_kernel_gets_no_site(self, attn_plan):
        sess, plan = attn_plan
        names = {r["kernel"] for r in plan.kernel_sites}
        assert "flash_attention_bwd" not in names
        assert "rg_lru_bwd" not in names

    def test_plan_serialization_roundtrip(self, attn_plan):
        _, plan = attn_plan
        plan2 = ShardingPlan.from_dict(plan.as_dict())
        assert plan2.kernel_sites == plan.kernel_sites
        assert plan2.state.kernel_impls == plan.state.kernel_impls

    def test_static_verify_passes(self, attn_plan):
        sess, plan = attn_plan
        report = sess.verify(beam_request(ATTN_NAMES), plan)
        bad = [f for f in report.findings if f.severity == "error"]
        assert not bad, [f.message for f in bad]


class TestApplyExecutes:
    """1-device mesh: fused dispatch numerics through ``plan.apply``."""

    @pytest.mark.parametrize("fn,args,names", [
        (attn_loss, ATTN_ARGS, ATTN_NAMES),
        (lru_loss, LRU_ARGS, LRU_NAMES),
    ])
    def test_apply_matches_unsharded(self, fn, args, names):
        mesh1 = MeshSpec(("data", "model"), (1, 1))
        sess = Session(fn, args)
        plan = sess.partition(beam_request(names, mesh=mesh1))
        assert plan.kernel_sites          # the site survives to the plan
        key = jax.random.PRNGKey(0)
        concrete = ({k: jax.random.normal(jax.random.fold_in(key, j),
                                          v.shape)
                     for j, (k, v) in enumerate(args[0].items())},)
        got = plan.apply(fn)(*concrete)
        want = fn(*concrete)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestZooModelFused:
    """A real zoo model traced with kernel dispatch on."""

    @pytest.fixture(scope="class")
    def qwen(self):
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.launch.specs import step_and_inputs
        cfg = dataclasses.replace(get_config("qwen2_05b").reduced(),
                                  use_pallas=True)
        shape = ShapeConfig("kp_test", seq_len=128, global_batch=4,
                            kind="train")
        fn, args, names = step_and_inputs(cfg, shape)
        sess = Session(fn, args)
        req = beam_request(names)
        plan = sess.partition(req)
        return sess, req, plan

    def test_fused_ops_in_zoo_ir(self, qwen):
        sess, _, _ = qwen
        prims = {op.prim for _, op in kernel_ops(sess.artifacts.prog)}
        assert "kernel:flash_attention" in prims

    def test_zoo_plan_records_sites(self, qwen):
        _, _, plan = qwen
        sites = [r for r in plan.kernel_sites
                 if r["kernel"] == "flash_attention"]
        assert sites
        assert all(r["impl"] in ("pallas", "ref") for r in sites)

    def test_zoo_plan_verifies(self, qwen):
        sess, req, plan = qwen
        report = sess.verify(req, plan)
        bad = [f for f in report.findings if f.severity == "error"]
        assert not bad, [f.message for f in bad]
