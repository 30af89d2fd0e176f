"""What MCTS finds on real programs, and the benchmark cells' plans.

- Every zoo architecture (reduced) on three four-device meshes: a seeded
  search through ``Session.partition`` repeats itself exactly, never
  ends above its root, reports a cost the dense interpreter agrees
  with, and passes the static verifier.  Its final descent ends where a
  plain steepest descent from the tree's best state ends, and never
  above it.
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
from repro.core.actions import valid_actions
from repro.core.constraints import Pin
from repro.core.cost_model import (CostModel, HardwareSpec, MeshSpec,
                                   ShardingState)
from repro.core.mcts import MCTS, MCTSBackend, MCTSConfig
from repro.core.verify import verify_state
from repro.kernels import ops
from repro.launch.specs import step_and_inputs
from repro.launch.zoo import ZOO_SHAPE_SMOKE

AXES = ("data", "model")
MESHES = ((2, 2), (4, 1), (1, 4))


class _Tree(MCTS):
    """MCTS that keeps the tree's best state, which its final descent
    starts from."""

    def _descend(self, state, cost):
        self.tree_best = (state, cost)
        return super()._descend(state, cost)


class _Recording(MCTSBackend):
    """MCTS that keeps its last search: the agent, its actions and the
    ``SearchResult`` (the plan drops the search history)."""

    def search(self, evaluator, actions, config=None,
               root=ShardingState()):
        self.actions = actions
        self.agent = _Tree(evaluator, actions, config)
        self.result = self.agent.search(root)
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
    return plan, backend


def _steepest_descent(ev, actions, state, cost, min_gain):
    """The final descent as specified: from ``state``, move to the
    cheapest child while it is cheaper by more than ``min_gain``.
    Returns the final state, its cost and each accepted step's gain."""
    gains = []
    while True:
        children = [ev.paper_cost_child(state, a)
                    for a in valid_actions(actions, state)]
        if not children:
            return state, cost, gains
        child, c = min(children, key=lambda sc: sc[1])
        if cost - c <= min_gain:
            return state, cost, gains
        gains.append(cost - c)
        state, cost = child, c


@pytest.mark.parametrize("sizes", MESHES, ids=lambda s: "x".join(map(str, s)))
def test_mcts_plan_on_zoo_program(session, sizes):
    mesh = MeshSpec(AXES, sizes)
    plan, backend = _search(session, mesh)
    res = backend.result
    again = _search(session, mesh)[1].result
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


@pytest.mark.parametrize("sizes", MESHES, ids=lambda s: "x".join(map(str, s)))
def test_mcts_descent_on_zoo_program(session, sizes):
    plan, backend = _search(session, MeshSpec(AXES, sizes))
    agent, res = backend.agent, backend.result
    tree_state, tree_cost = agent.tree_best
    descent = plan.eval_stats["descent"]
    assert descent["cost_before"] == tree_cost
    assert descent["cost_after"] == res.best_cost == res.history[-1]
    assert res.best_cost <= tree_cost
    penalty = agent.cfg.length_penalty
    state, cost, gains = _steepest_descent(agent.ev, backend.actions,
                                           tree_state, tree_cost, penalty)
    assert (state, cost, len(gains)) == (res.best_state, res.best_cost,
                                         descent["steps"])
    assert all(g > penalty for g in gains)
    assert 0 <= descent["evaluations"] <= res.evaluations


def _cell_session(name):
    """The cell's traced step as ``TrainCell`` traces it, its mesh and
    its logical axis names."""
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
    return (Session(fn, (state_sds, bspecs)),
            MeshSpec(harness.AXES, tuple(mix["mesh"])), names)


def _cell_search(name, constraints=()):
    """The cell's searched plan as ``TrainCell`` gets it, without the
    compile, and the backend that searched it."""
    sess, mesh, names = _cell_session(name)
    backend = _Recording()
    plan = sess.partition(Request(
        mesh=mesh, logical_axes=names, backend=backend,
        constraints=constraints, search_config=MCTSConfig(seed=0)))
    return plan, backend


def _cell_plan(name):
    """The cell's plan evidence, as the benchmark records it."""
    from perfbench import harness
    plan, _ = _cell_search(name)
    return harness.TrainCell.plan_evidence(types.SimpleNamespace(plan=plan))


def test_qwen2_05b_cell_plan_is_pinned(monkeypatch):
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    ev = _cell_plan("qwen2_05b.train.s1k")
    assert ev["plan_hash"] == "4882248af0175949"


def test_phi3_mini_2x2_cell_plan_is_pinned(monkeypatch):
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    ev = _cell_plan("phi3_mini.train.2x2")
    # seed 0's tree stops at 0.438854 after 1405 evaluations; the final
    # descent adds batch -> model in 111 more
    assert (ev["plan_hash"], ev["evaluations"]) == ("abeb3067f2467d38", 1516)
    assert ev["cost"] == pytest.approx(0.244020, abs=1e-6)
    assert ev["rules"] == {"batch": ("data", "model"),
                           "embed": ("model", "data")}


def test_qwen2_05b_cell_descent_takes_no_step(monkeypatch):
    """The 1x1 mesh offers no sharding action, only the two attention
    sites' choice of the reference kernel: one scan of those, no step."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    plan, backend = _cell_search("qwen2_05b.train.s1k")
    assert [a.kernel_impl for a in backend.actions] == ["ref", "ref"]
    assert plan.eval_stats["descent"] == {
        "steps": 0, "evaluations": 2, "cost_before": plan.cost,
        "cost_after": plan.cost}


def test_phi3_mini_2x2_cell_descent(monkeypatch):
    """The descent engages on the 2x2 cell, ends at the plan the tree
    stopped one action short of, and is a fixed point there."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    plan, backend = _cell_search("phi3_mini.train.2x2")
    descent = plan.eval_stats["descent"]
    assert descent["steps"] >= 1
    assert descent["evaluations"] <= 200
    assert descent["cost_before"] == pytest.approx(0.438854, abs=1e-6)
    assert descent["cost_after"] == plan.cost <= 0.245
    again = backend.agent._descend(plan.state, plan.cost)
    assert again[:3] == (plan.state, plan.cost, 0)


@pytest.mark.parametrize("pins", [
    (Pin("batch", "data"),),
    (Pin("heads", "model"), Pin("hidden", "model"), Pin("batch", "data")),
], ids=["batch", "megatron"])
def test_phi3_mini_2x2_cell_descent_keeps_pins(monkeypatch, pins):
    """Under pins the descent still engages and leaves every pinned
    input dim on its axes: with ``heads`` pinned to ``model``, a child
    that puts ``model`` on ``embed`` too keeps the pinned color but
    strips ``model`` from the heads dim of every attention weight."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    plan, backend = _cell_search("phi3_mini.train.2x2", pins)
    assert plan.eval_stats["descent"]["steps"] >= 1
    assert plan.check(pins)
    cs = backend.agent.ev.constraints
    assert cs.violations(plan.state) == []
    assert cs.unsharded_pins(backend.agent.cm, plan.state) == 0
    for pin in pins:
        assert plan.logical_rules[pin.target] == (pin.spec,)
