"""Portfolio search backend tests (concurrent members, early stop)."""

import jax
import jax.numpy as jnp
import pytest

from repro.core.cost_model import CostModel, MeshSpec
from repro.core.actions import build_action_space
from repro.core.evaluator import IncrementalEvaluator
from repro.core.mcts import MCTSConfig
from repro.core.partitioner import analyze, auto_partition
from repro.core.portfolio import (PortfolioBackend, PortfolioConfig,
                                  PortfolioMember, default_portfolio)
from repro.core.search import BeamConfig, get_backend


def sh(*s):
    return jax.ShapeDtypeStruct(s, jnp.float32)


def mlp(x, w1, w2):
    return jax.nn.relu(x @ w1) @ w2


MLP_ARGS = (sh(1024, 512), sh(512, 2048), sh(2048, 512))
MESH = MeshSpec(("data", "model"), (4, 4))
FAST_MCTS = MCTSConfig(rounds=3, trajectories_per_round=12)


@pytest.fixture(scope="module")
def mlp_art():
    return analyze(mlp, MLP_ARGS)


@pytest.fixture(scope="module")
def search_inputs(mlp_art):
    cm = CostModel(mlp_art.prog, mlp_art.nda, mlp_art.analysis, MESH)
    actions = build_action_space(mlp_art.nda, mlp_art.analysis, MESH,
                                 min_dims=1)
    return cm, actions


class TestPortfolioBackend:
    def test_registered(self):
        assert isinstance(get_backend("portfolio"), PortfolioBackend)

    def test_wrong_config_type_raises(self, search_inputs):
        cm, actions = search_inputs
        with pytest.raises(TypeError):
            PortfolioBackend().search(IncrementalEvaluator(cm), actions,
                                      BeamConfig())

    def test_matches_best_member(self, search_inputs):
        """The portfolio's best cost equals the min over its members run
        in isolation (sequential, no early stop -> fully deterministic)."""
        cm, actions = search_inputs
        members = (
            PortfolioMember("greedy", config=BeamConfig(patience=1)),
            PortfolioMember("mcts", seed=3,
                            config=MCTSConfig(seed=3, rounds=3,
                                              trajectories_per_round=12)),
        )
        solo = []
        for m in members:
            res = get_backend(m.backend).search(
                IncrementalEvaluator(cm), actions, m.config)
            solo.append(res.best_cost)
        cfg = PortfolioConfig(members=members, max_workers=1,
                              patience=100)
        res = PortfolioBackend().search(IncrementalEvaluator(cm), actions,
                                        cfg)
        assert res.best_cost == pytest.approx(min(solo))
        assert res.best_cost < 1.0

    def test_mcts_winner_carries_its_descent(self, search_inputs):
        """An MCTS member runs its final descent inside the portfolio,
        and the winner's descent is the portfolio's; beam runs none."""
        cm, actions = search_inputs
        mcts = PortfolioMember("mcts", seed=3, config=MCTSConfig(
            seed=3, rounds=3, trajectories_per_round=12))
        solo = get_backend("mcts").search(IncrementalEvaluator(cm), actions,
                                          mcts.config)
        res = PortfolioBackend().search(
            IncrementalEvaluator(cm), actions,
            PortfolioConfig(members=(mcts,), max_workers=1))
        assert res.descent == solo.descent
        assert res.descent["cost_after"] == res.best_cost
        beam = get_backend("beam").search(IncrementalEvaluator(cm), actions,
                                          BeamConfig(patience=1))
        assert beam.descent is None

    def test_member_outcomes_recorded(self, search_inputs):
        cm, actions = search_inputs
        cfg = PortfolioConfig(members=(
            PortfolioMember("greedy", config=BeamConfig(patience=1)),
            PortfolioMember("beam", config=BeamConfig(width=2,
                                                      patience=1)),
        ), max_workers=1, patience=100)
        res = PortfolioBackend().search(IncrementalEvaluator(cm), actions,
                                        cfg)
        assert len(res.members) == 2
        assert all(m.status == "done" for m in res.members)
        assert all(m.evaluations > 0 for m in res.members)
        assert res.winner in {m.label for m in res.members}
        assert res.rounds_run == 2
        assert res.evaluations == sum(m.evaluations for m in res.members)

    def test_early_stop_cancels_queued_members(self, search_inputs):
        """With one worker and patience=1, identical members plateau after
        two completions and the queued tail is cancelled."""
        cm, actions = search_inputs
        same = BeamConfig(width=1, max_depth=6, patience=1)
        members = tuple(PortfolioMember("greedy", seed=i, config=same,
                                        label=f"g{i}") for i in range(8))
        cfg = PortfolioConfig(members=members, max_workers=1, patience=1)
        res = PortfolioBackend().search(IncrementalEvaluator(cm), actions,
                                        cfg)
        statuses = [m.status for m in res.members]
        assert res.early_stopped
        assert statuses.count("cancelled") >= 1
        assert statuses.count("done") < len(members)
        # the best result is still a real improvement
        assert res.best_cost < 1.0

    def test_error_member_does_not_sink_portfolio(self, search_inputs):
        cm, actions = search_inputs
        cfg = PortfolioConfig(members=(
            # wrong config type for mcts -> this member errors out
            PortfolioMember("mcts", config=BeamConfig(), label="bad"),
            PortfolioMember("greedy", config=BeamConfig(patience=1),
                            label="good"),
        ), max_workers=1, patience=100)
        res = PortfolioBackend().search(IncrementalEvaluator(cm), actions,
                                        cfg)
        by_label = {m.label: m for m in res.members}
        assert by_label["bad"].status == "error"
        assert by_label["good"].status == "done"
        assert res.winner == "good"

    def test_all_members_failing_raises(self, search_inputs):
        cm, actions = search_inputs
        cfg = PortfolioConfig(members=(
            PortfolioMember("mcts", config=BeamConfig(), label="bad"),),
            max_workers=1)
        with pytest.raises(RuntimeError):
            PortfolioBackend().search(IncrementalEvaluator(cm), actions,
                                      cfg)

    def test_default_portfolio_shape(self):
        members = default_portfolio((0, 1))
        assert len(members) == 4            # 2 mcts + beam + greedy
        assert {m.backend for m in members} == {"mcts", "beam", "greedy"}

    def test_cancelled_members_never_write_partial_results(
            self, search_inputs):
        """A cancelled member must leave no trace beyond its 'cancelled'
        outcome: zero evaluations/seconds, no cost, and the winner is
        always a completed member."""
        cm, actions = search_inputs
        same = BeamConfig(width=1, max_depth=6, patience=1)
        members = tuple(PortfolioMember("greedy", seed=i, config=same,
                                        label=f"g{i}") for i in range(8))
        cfg = PortfolioConfig(members=members, max_workers=1, patience=1)
        res = PortfolioBackend().search(IncrementalEvaluator(cm), actions,
                                        cfg)
        cancelled = [m for m in res.members if m.status == "cancelled"]
        assert cancelled                      # early stop really fired
        for m in cancelled:
            assert m.evaluations == 0
            assert m.seconds == 0.0
            assert m.best_cost == float("inf")
            assert not m.feasible
            assert m.error == ""
        done = {m.label for m in res.members if m.status == "done"}
        assert res.winner in done
        # totals only count completed members
        assert res.evaluations == sum(m.evaluations for m in res.members
                                      if m.status == "done")

    def test_best_plan_deterministic_across_worker_counts(
            self, search_inputs):
        """With fixed seeds and no plateau cutoff, the returned best
        plan is identical whether members run sequentially or on four
        threads (deterministic tie-breaks by portfolio order)."""
        cm, actions = search_inputs
        members = (
            PortfolioMember("greedy", config=BeamConfig(patience=1)),
            PortfolioMember("beam", config=BeamConfig(width=2,
                                                      patience=1)),
            PortfolioMember("mcts", seed=0,
                            config=MCTSConfig(seed=0, rounds=2,
                                              trajectories_per_round=8)),
            PortfolioMember("mcts", seed=1,
                            config=MCTSConfig(seed=1, rounds=2,
                                              trajectories_per_round=8)),
        )
        outcomes = []
        for workers in (1, 4):
            cfg = PortfolioConfig(members=members, max_workers=workers,
                                  patience=100)
            res = PortfolioBackend().search(IncrementalEvaluator(cm),
                                            actions, cfg)
            outcomes.append((res.best_state, res.best_cost, res.winner))
        assert outcomes[0] == outcomes[1]


class TestAutoPartitionPortfolio:
    def test_backend_name_and_stats(self, mlp_art):
        cfg = PortfolioConfig(members=(
            PortfolioMember("greedy", config=BeamConfig(patience=1)),
            PortfolioMember("mcts", config=FAST_MCTS),
        ), max_workers=2, patience=100)
        plan = auto_partition(mlp, MLP_ARGS, MESH, min_dims=1,
                              artifacts=mlp_art, portfolio=cfg)
        assert plan.backend == "portfolio"
        assert plan.cost < 1.0
        pf = plan.eval_stats["portfolio"]
        assert pf["winner"]
        assert len(pf["members"]) == 2

    def test_portfolio_true_uses_default(self, mlp_art):
        plan = auto_partition(
            mlp, MLP_ARGS, MESH, min_dims=1, artifacts=mlp_art,
            portfolio=True,
            search_config=PortfolioConfig(
                members=(PortfolioMember("greedy",
                                         config=BeamConfig(patience=1)),),
                max_workers=1))
        assert plan.backend == "portfolio"

    def test_explicit_backend_string(self, mlp_art):
        plan = auto_partition(
            mlp, MLP_ARGS, MESH, min_dims=1, artifacts=mlp_art,
            backend="portfolio",
            search_config=PortfolioConfig(
                members=(PortfolioMember("greedy",
                                         config=BeamConfig(patience=1)),),
                max_workers=1))
        assert plan.backend == "portfolio"
        assert plan.cost < 1.0
