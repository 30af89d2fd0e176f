"""What MCTS finds on real programs, and the benchmark cells' plans.

- Every zoo architecture (reduced) on three four-device meshes: a seeded
  search through ``Session.partition`` repeats itself exactly, never
  ends above its root, reports a cost the dense interpreter agrees
  with, and passes the static verifier.
- The two benchmark cells, built as ``perfbench.harness.TrainCell``
  builds them (configuration and traffic files, MCTS seed 0, no plan
  store), are searched without compiling, and their plans are pinned by
  the hash the benchmark records.  The kernel dispatch answers as on the
  TPU: off it, the flash sites resolve to the reference and the search
  sees another program.
"""

import types

import pytest

from repro.api import Request, Session
from repro.configs import ARCH_IDS, get_config
from repro.core.cost_model import CostModel, HardwareSpec, MeshSpec
from repro.core.mcts import MCTSBackend, MCTSConfig
from repro.core.verify import verify_state
from repro.kernels import ops
from repro.launch.specs import step_and_inputs
from repro.launch.zoo import ZOO_SHAPE_SMOKE

AXES = ("data", "model")
MESHES = ((2, 2), (4, 1), (1, 4))


class _Recording(MCTSBackend):
    """MCTS that keeps its last ``SearchResult`` (the plan drops the
    search history)."""

    def search(self, *args, **kwargs):
        self.result = super().search(*args, **kwargs)
        return self.result


@pytest.fixture(scope="module", params=ARCH_IDS)
def session(request):
    fn, args, _ = step_and_inputs(get_config(request.param).reduced(),
                                  ZOO_SHAPE_SMOKE)
    return Session(fn, args)


def _search(sess, mesh):
    backend = _Recording()
    plan = sess.partition(Request(mesh=mesh, backend=backend,
                                  search_config=MCTSConfig(seed=0)))
    return plan, backend.result


@pytest.mark.parametrize("sizes", MESHES, ids=lambda s: "x".join(map(str, s)))
def test_mcts_plan_on_zoo_program(session, sizes):
    mesh = MeshSpec(AXES, sizes)
    plan, res = _search(session, mesh)
    _, again = _search(session, mesh)
    assert (again.best_state, again.best_cost, again.evaluations,
            again.history) == (res.best_state, res.best_cost,
                               res.evaluations, res.history)
    assert plan.state == res.best_state and plan.cost == res.best_cost
    # history[0] is the root's cost
    assert res.best_cost <= res.history[0]
    art = session.artifacts
    cm = CostModel(art.prog, art.nda, art.analysis, mesh, HardwareSpec())
    dense = cm.cost_from_breakdown(cm.evaluate_dense(res.best_state))
    assert res.best_cost == pytest.approx(dense, rel=1e-9)
    report = verify_state(cm, res.best_state, plan=plan)
    assert report.errors == []


def _cell_plan(name):
    """The cell's searched plan and its evidence, as ``TrainCell`` gets
    them, without the compile."""
    from perfbench import catalog, harness
    from repro.configs.base import ShapeConfig
    from repro.launch.specs import batch_specs, state_logical_axes
    from repro.optim.adam import AdamConfig
    from repro.train.steps import make_train_step, train_state_specs

    bench = catalog.benchmark()
    cell = catalog.workload(bench, name)
    conf = catalog.config(bench, cell["config"])
    mix = catalog.traffic(cell["traffic"])
    cfg = harness.model_config(conf)
    opt = AdamConfig(**conf["run"]["optimizer"])
    shape = ShapeConfig(mix["name"], mix["seq_len"], mix["batch"], "train")
    fn = make_train_step(cfg, opt)
    state_sds = train_state_specs(cfg, opt)
    bspecs, bnames = batch_specs(cfg, shape)
    names = (state_logical_axes(cfg, state_sds), bnames)
    assert mix["search"] == {"backend": "mcts", "seed": 0}
    plan = Session(fn, (state_sds, bspecs)).partition(Request(
        mesh=MeshSpec(harness.AXES, tuple(mix["mesh"])),
        logical_axes=names, backend="mcts",
        search_config=MCTSConfig(seed=0)))
    return harness.TrainCell.plan_evidence(types.SimpleNamespace(plan=plan))


def test_qwen2_05b_cell_plan_is_pinned(monkeypatch):
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    ev = _cell_plan("qwen2_05b.train.s1k")
    assert ev["plan_hash"] == "4882248af0175949"


def test_phi3_mini_2x2_cell_plan_is_pinned(monkeypatch):
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    ev = _cell_plan("phi3_mini.train.2x2")
    assert (ev["plan_hash"], ev["evaluations"]) == ("f1100235c4d06a6b", 1405)
    assert ev["cost"] == pytest.approx(0.438854, abs=1e-6)
