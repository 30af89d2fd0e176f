"""Monte-Carlo Tree Search partitioning agent (paper §4.1–4.3).

Faithful to the paper's adaptations of standard UCT:

- **State** is the canonical sharding map (``ShardingState``), not the
  action sequence — any action ordering reaching the same sharded model
  hits the same node (transposition-free by construction, §4.3).
- **Early round termination**: the search runs in rounds of trajectories;
  if a round fails to improve the best-known cost, the whole search stops
  (§4.1).
- **Short-trajectory incentive**: rewards are discounted in trajectory
  length so shorter action sequences with equal cost are preferred (§4.1).
- Trajectories end on a explicit *stop* action or at ``max_depth`` (30 in
  the paper).
- **Final descent** (not in the paper): the tree returns only states on
  its paths or at the end of a random playout, so its answer need not be
  a local minimum.  After the last round, a steepest descent from the
  best state takes the cheapest single action while it gains more than
  the short-trajectory incentive ``length_penalty``.

Evaluation runs through ``IncrementalEvaluator``: every action application
during tree walk and playout costs the child *incrementally* from its
parent's record, and repeated prefix states hit the transposition cache —
the full abstract interpretation never re-runs per state (paper §5.3).
"""

from __future__ import annotations

import dataclasses
import math
import random

from repro.core.actions import Action, STOP, valid_actions
from repro.core.cost_model import CostModel, ShardingState
from repro.core.evaluator import IncrementalEvaluator
from repro.core.search import SearchBackend, SearchResult, recover_actions

__all__ = ["MCTS", "MCTSBackend", "MCTSConfig", "SearchResult"]


@dataclasses.dataclass
class MCTSConfig:
    rounds: int = 12
    trajectories_per_round: int = 48
    max_depth: int = 30
    exploration: float = 0.7
    length_penalty: float = 0.01       # short-trajectory incentive
    seed: int = 0
    patience: int = 1                  # rounds without improvement -> stop


class _Node:
    __slots__ = ("visits", "value", "children", "untried")

    def __init__(self, untried: list[Action]) -> None:
        self.visits = 0
        self.value = 0.0
        self.children: dict[Action, ShardingState] = {}
        self.untried = untried


class MCTS:
    def __init__(self, cost_model: CostModel | IncrementalEvaluator,
                 actions: list[Action],
                 config: MCTSConfig | None = None) -> None:
        if isinstance(cost_model, IncrementalEvaluator):
            self.ev = cost_model
        else:
            self.ev = IncrementalEvaluator(cost_model)
        self.cm = self.ev.cm
        self.actions = actions
        self.cfg = config if config is not None else MCTSConfig()
        self.rng = random.Random(self.cfg.seed)
        self.nodes: dict[ShardingState, _Node] = {}
        self.evaluations = 0

    def _node(self, state: ShardingState) -> _Node:
        n = self.nodes.get(state)
        if n is None:
            n = _Node(valid_actions(self.actions, state) + [STOP])
            self.rng.shuffle(n.untried)
            self.nodes[state] = n
        return n

    def _cost(self, state: ShardingState) -> float:
        self.evaluations += 1
        return self.ev.paper_cost(state)

    def _cost_child(self, state: ShardingState,
                    action: Action) -> tuple[ShardingState, float]:
        self.evaluations += 1
        return self.ev.paper_cost_child(state, action)

    def _reward(self, cost: float, depth: int) -> float:
        return 1.0 - cost - self.cfg.length_penalty * depth

    def _uct(self, parent: _Node, child_state: ShardingState) -> float:
        child = self._node(child_state)
        if child.visits == 0:
            return float("inf")
        exploit = child.value / child.visits
        explore = self.cfg.exploration * math.sqrt(
            math.log(max(parent.visits, 1)) / child.visits)
        return exploit + explore

    def _trajectory(self, root: ShardingState):
        """One rollout; returns (path states, final state, depth)."""
        path = [root]
        state = root
        depth = 0
        while depth < self.cfg.max_depth:
            node = self._node(state)
            if node.untried:
                action = node.untried.pop()
            else:
                if not node.children:
                    break
                action = max(node.children,
                             key=lambda a: self._uct(node, node.children[a]))
            if action.is_stop:
                break
            # incremental child costing primes the transposition cache for
            # the prefix-candidate sweep in search()
            nxt, _ = self._cost_child(state, action)
            node.children[action] = nxt
            if nxt == state:
                break
            path.append(nxt)
            state = nxt
            depth += 1
            # random playout extension: after expansion, follow random
            # actions without tree bookkeeping
            node2 = self._node(state)
            if node2.visits == 0:
                s = state
                d = depth
                while d < self.cfg.max_depth:
                    av = valid_actions(self.actions, s)
                    if not av or self.rng.random() < 0.35:
                        break
                    s, _ = self._cost_child(s, self.rng.choice(av))
                    d += 1
                return path, s, d
        return path, state, depth

    def search(self, root: ShardingState = ShardingState()) -> SearchResult:
        best_state = root
        best_cost = self._cost(root)
        history = [best_cost]
        stale = 0
        rounds_run = 0
        for _ in range(self.cfg.rounds):
            rounds_run += 1
            improved = False
            for _ in range(self.cfg.trajectories_per_round):
                path, final, depth = self._trajectory(root)
                cost = self._cost(final)
                reward = self._reward(cost, depth)
                for s in path:
                    n = self._node(s)
                    n.visits += 1
                    n.value += reward
                # every prefix state of the trajectory is itself a candidate
                for s in path:
                    c = self._cost(s)
                    if c < best_cost - 1e-12:
                        best_cost, best_state, improved = c, s, True
                if cost < best_cost - 1e-12:
                    best_cost, best_state, improved = cost, final, True
            history.append(best_cost)
            if not improved:
                stale += 1
                if stale >= self.cfg.patience:
                    break           # paper: stop when a round fails to improve
            else:
                stale = 0
        descended, cost, steps, evals = self._descend(best_state, best_cost)
        history.append(cost)
        descent = {"steps": steps, "evaluations": evals,
                   "cost_before": best_cost, "cost_after": cost}
        return SearchResult(descended, cost, recover_actions(descended),
                            rounds_run, self.evaluations, history, descent)

    def _descend(self, state: ShardingState, cost: float
                 ) -> tuple[ShardingState, float, int, int]:
        """Steepest descent from ``state``: scan every valid action and
        move to the cheapest child while it is cheaper by more than
        ``length_penalty``.  Returns the final state, its cost, the
        steps taken and the cost queries spent."""
        evals0 = self.evaluations
        steps = 0
        while True:
            best = None
            for action in valid_actions(self.actions, state):
                child, c = self._cost_child(state, action)
                if best is None or c < best[1]:
                    best = (child, c)
            if best is None or cost - best[1] <= self.cfg.length_penalty:
                return state, cost, steps, self.evaluations - evals0
            state, cost = best
            steps += 1


class MCTSBackend(SearchBackend):
    """``SearchBackend`` adapter for :class:`MCTS`."""

    name = "mcts"

    def search(self, evaluator, actions: list[Action], config=None,
               root: ShardingState = ShardingState()) -> SearchResult:
        if config is not None and not isinstance(config, MCTSConfig):
            raise TypeError(f"mcts backend expects MCTSConfig, "
                            f"got {type(config).__name__}")
        return MCTS(evaluator, actions, config).search(root)
