"""Search-throughput benchmark: cost-model evaluations/sec.

Compares three evaluation paths on the paper's transformer config:

- **dense** ("seed path"): the original exhaustive abstract interpretation
  (``CostModel.evaluate_dense``) re-run from scratch for every state — what
  the search paid per fresh state before the incremental engine.
- **incremental**: ``IncrementalEvaluator.paper_cost_child`` along the same
  action walks (parent-diff re-costing + vectorized peak memory).
- **search**: a real MCTS run on the incremental engine — states costed per
  second including transposition-cache hits, plus the best cost found (the
  regression anchor: incremental evaluation is exact, so best-cost must not
  degrade).

Emits the repo's ``name,us_per_call,derived`` CSV rows and writes
``BENCH_search.json``.
"""

from __future__ import annotations

import json
import random
import time

from repro.core.actions import build_action_space, valid_actions
from repro.core.cost_model import CostModel, HardwareSpec, MeshSpec, \
    ShardingState
from repro.core.evaluator import IncrementalEvaluator
from repro.core.mcts import MCTS, MCTSConfig

MESH = MeshSpec(("data", "model"), (16, 16))
FULL_MESH = MeshSpec(("data", "model"), (8, 4))
FULL_MODELS = ("llama3_405b", "mixtral_8x22b")


def _row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)


def _random_walks(actions, *, n_walks: int, depth: int, seed: int):
    """Seeded random action walks from the root; returns a list of walks,
    each a list of (parent_state, action, child_state)."""
    rng = random.Random(seed)
    walks = []
    for _ in range(n_walks):
        s = ShardingState()
        walk = []
        for _ in range(depth):
            av = valid_actions(actions, s)
            if not av:
                break
            a = rng.choice(av)
            child = a.apply(s)
            walk.append((s, a, child))
            s = child
        walks.append(walk)
    return walks


def run(model: str = "t2b", *, n_walks: int = 24, depth: int = 10,
        dense_sample: int = 40, seed: int = 0,
        mcts_cfg: MCTSConfig | None = None,
        out: str | None = "BENCH_search.json") -> dict:
    from benchmarks import common
    art, _ = common.artifacts_for(model)
    hw = HardwareSpec()
    cm = CostModel(art.prog, art.nda, art.analysis, MESH, hw)
    actions = build_action_space(art.nda, art.analysis, MESH, min_dims=10)
    walks = _random_walks(actions, n_walks=n_walks, depth=depth, seed=seed)
    states = [c for walk in walks for _, _, c in walk]

    # -- incremental engine over the walks (fresh evaluator: no warm cache)
    ev = IncrementalEvaluator(cm)
    t0 = time.perf_counter()
    for walk in walks:
        for parent, a, _ in walk:
            ev.paper_cost_child(parent, a)
    t_inc = time.perf_counter() - t0
    inc_eps = len(states) / max(t_inc, 1e-12)

    # -- dense seed path on a sample of the same states
    sample = states[:dense_sample]
    t0 = time.perf_counter()
    for s in sample:
        cm.cost_from_breakdown(cm.evaluate_dense(s))
    t_dense = time.perf_counter() - t0
    dense_eps = len(sample) / max(t_dense, 1e-12)

    # -- end-to-end MCTS on the incremental engine
    cfg = mcts_cfg or MCTSConfig(rounds=6, trajectories_per_round=24)
    ev2 = IncrementalEvaluator(cm)
    agent = MCTS(ev2, actions, cfg)
    t0 = time.perf_counter()
    res = agent.search()
    t_search = time.perf_counter() - t0
    search_eps = res.evaluations / max(t_search, 1e-12)

    speedup = inc_eps / max(dense_eps, 1e-12)
    record = {
        "model": model,
        "mesh": list(MESH.sizes),
        "ops": len(art.prog.ops),
        "actions": len(actions),
        "walk_states": len(states),
        "dense_evals_per_s": dense_eps,
        "incremental_evals_per_s": inc_eps,
        "speedup": speedup,
        "search_states_per_s": search_eps,
        "search_best_cost": res.best_cost,
        "search_evaluations": res.evaluations,
        "search_seconds": t_search,
        "eval_stats": ev2.stats.as_dict(),
    }
    _row(f"search.dense_eval.{model}", 1e6 / max(dense_eps, 1e-12),
         f"evals_per_s={dense_eps:.1f}")
    _row(f"search.incremental_eval.{model}", 1e6 / max(inc_eps, 1e-12),
         f"evals_per_s={inc_eps:.1f};speedup={speedup:.1f}x")
    _row(f"search.mcts.{model}", t_search * 1e6,
         f"states_per_s={search_eps:.1f};best_cost={res.best_cost:.4f};"
         f"evaluations={res.evaluations}")
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=2)
    return record


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
