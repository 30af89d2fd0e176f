"""Host spans, phase timers, pipeline counters and device scopes.

``repro.spans.span`` puts ``toast.<name>`` on the profiler's host plane
and fills the phase timers of the analysis and the partitioner; the
data pipeline counts its waits; the train step names its device work
with ``jax.named_scope`` (``attn_bwd``, ``mlp``, ``head_loss``,
``optimizer``) without changing what the tracer sees.
"""

import contextlib
import dataclasses
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.api import Request, Session
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.cost_model import MeshSpec
from repro.core.mcts import MCTSConfig
from repro.core.partitioner import analyze
from repro.data import pipeline
from repro.launch.specs import step_and_inputs

SCOPES = ("attn_bwd", "mlp", "head_loss", "optimizer")


def sh(*s):
    return jax.ShapeDtypeStruct(s, jnp.float32)


def mlp(d):
    return jax.nn.relu(d["x"] @ d["w1"]) @ d["w2"]


MLP_ARGS = ({"x": sh(256, 128), "w1": sh(128, 512), "w2": sh(512, 128)},)


def in_path(scope, op_name):
    return re.search(r"(?:^|[/(;])" + scope + r"(?:$|[/);])", op_name)


# --- span -------------------------------------------------------------------

def test_span_nests_and_fills_into():
    d = {}
    with spans.span("outer", d):
        with spans.span("inner", d):
            time.sleep(0.01)
        with spans.span("inner", d):
            pass
    assert set(d) == {"outer", "inner"}
    assert d["outer"] >= d["inner"] >= 0.01


def test_span_records_when_the_block_raises_and_leaves_no_state():
    before = dict(vars(spans))
    d = {}
    with pytest.raises(KeyError):
        with spans.span("boom", d):
            raise KeyError("x")
    with spans.span("no_dict"):
        pass
    assert list(d) == ["boom"] and d["boom"] >= 0.0
    assert dict(vars(spans)) == before


def test_span_lands_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("outer"):
            with spans.span("inner"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    evs = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
           for plane in ProfileData.from_file(path).planes
           if plane.name == "/host:CPU"
           for line in plane.lines for ev in line.events}
    (os_, oe), (is_, ie) = evs["toast.outer"], evs["toast.inner"]
    assert os_ <= is_ <= ie <= oe


# --- phase timers -----------------------------------------------------------

def test_analyze_keeps_its_three_phases():
    art = analyze(mlp, MLP_ARGS)
    assert set(art.phase_seconds) == {"trace", "nda", "conflicts"}
    assert all(v >= 0.0 for v in art.phase_seconds.values())


def test_partition_phases_land_in_eval_stats():
    sess = Session(mlp, MLP_ARGS)
    plan = sess.partition(Request(
        mesh=MeshSpec(("data", "model"), (2, 2)), min_dims=1,
        search_config=MCTSConfig(rounds=2, trajectories_per_round=8)))
    phases = plan.eval_stats["phase_seconds"]
    assert list(phases) == ["cost_model", "actions", "search", "build_plan"]
    assert all(v >= 0.0 for v in phases.values())
    # search_seconds runs from the call to the end of the search
    assert plan.search_seconds >= phases["search"]
    assert "rows_recosted" not in plan.eval_stats


# --- pipeline counters ------------------------------------------------------

def small_pipe(**kw):
    cfg = get_config("qwen2_05b").reduced()
    return pipeline.Pipeline(cfg, ShapeConfig("t", 16, 2, "train"),
                             pipeline.DataConfig(**kw))


def test_pipeline_counts_no_wait_when_a_batch_is_ready():
    pipe = small_pipe(prefetch=2)
    try:
        deadline = time.monotonic() + 30
        while not pipe._q.full() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pipe._q.full()
        step, _ = next(pipe)
        assert step == 0 and pipe.waits_empty == 0
        assert 0.0 <= pipe.wait_s < 1.0
    finally:
        pipe.close()


def test_pipeline_counts_the_wait_for_a_slow_batch(monkeypatch):
    made = pipeline._batch_for

    def slow(*a):
        time.sleep(0.2)
        return made(*a)
    monkeypatch.setattr(pipeline, "_batch_for", slow)
    pipe = small_pipe(prefetch=1)
    try:
        next(pipe)
        assert pipe.waits_empty == 1
        assert pipe.wait_s >= 0.05
    finally:
        pipe.close()


# --- device scopes ----------------------------------------------------------

def fused_train_step():
    cfg = dataclasses.replace(get_config("qwen2_05b").reduced(),
                              use_pallas=True, remat=True,
                              remat_policy="full")
    return step_and_inputs(cfg, ShapeConfig("t", 64, 2, "train"))


def test_train_step_carries_the_four_scopes_backward_included():
    fn, args, _ = fused_train_step()
    lowered = jax.jit(fn).lower(*args)
    locs = set(re.findall(r'loc\("([^"]*)"',
                          lowered.as_text(debug_info=True)))
    for scope in SCOPES:
        assert any(in_path(scope, n) for n in locs), scope
    # the compiled module's op_name paths, which a device trace reads:
    # the backward pass and the recomputed forward carry the scopes too
    names = set(re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text()))
    backward = [n for n in names if "transpose(" in n]
    for scope in ("attn_bwd", "mlp", "head_loss"):
        assert any(in_path(scope, n) for n in backward), scope
    assert any("rematted_computation" in n and in_path("mlp", n)
               for n in names)
    assert any(in_path("optimizer", n) for n in names)


def test_scopes_change_neither_the_program_nor_the_plan(monkeypatch):
    def search():
        fn, args, names = fused_train_step()
        sess = Session(fn, args)
        plan = sess.partition(Request(
            mesh=MeshSpec(("data", "model"), (2, 2)), logical_axes=names,
            search_config=MCTSConfig(seed=0, rounds=2,
                                     trajectories_per_round=8)))
        return sess.fingerprint, plan.cost, plan.logical_rules

    scoped = search()
    # the kernels' jits are traced once per process: trace them anew
    # without the scopes, and again with them afterwards
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        fn, args, _ = fused_train_step()
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        assert not any(in_path(s, n) for s in SCOPES
                       for n in re.findall(r'loc\("([^"]*)"', text))
        assert search() == scoped
    finally:
        jax.clear_caches()
