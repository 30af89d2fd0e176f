"""TOAST front-end: the ``ShardingPlan`` type and the classic
``auto_partition`` entry point.

The staged public API lives in ``repro.api`` (``Session`` /
``Request`` / ``Constraint``); ``auto_partition`` remains as a thin
one-shot wrapper over it::

    plan = auto_partition(train_step, (params, batch),
                          mesh=MeshSpec(("data", "model"), (16, 16)))
    jitted = plan.apply(train_step)        # in+out shardings installed

Intermediate conflict resolutions (e.g. sequence sharding of attention
scores) surface in ``plan.constraint_specs`` and — when the caller declares
logical dimension names for inputs — as ``plan.logical_rules`` consumed by
the models' ``with_sharding_constraint`` hooks.
"""

from __future__ import annotations

import dataclasses
import json
import math
from collections import Counter, defaultdict
from typing import Callable

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.conflicts import ConflictAnalysis, analyze_conflicts
from repro.core.constraints import (Constraint, ConstraintError,
                                    check_plan_detailed, match_paths)
from repro.core.cost_model import (CostModel, HardwareSpec, MeshSpec,
                                   ShardingState)
from repro.core.ir import Program, extract_program
from repro.core.mcts import MCTSConfig
from repro.core.nda import NDAResult, run_nda
from repro.core.search import SearchBackend
from repro.spans import span

# the forward and backward tilings a Pallas flash_attention site record
# carries (beside the backward's impl, ``bwd_impl``)
_TILING_KEYS = ("block_q", "block_k", "tiles_total", "tiles_computed",
                "bwd_block_q", "bwd_block_k", "bwd_tiles_total",
                "bwd_tiles_computed")


@dataclasses.dataclass(frozen=True)
class Violation:
    """One constraint violation found by :meth:`ShardingPlan.check`.

    Attributes:
        constraint: the violated constraint object.
        message: human-readable description of the violation.
    """

    constraint: Constraint
    message: str

    def __str__(self) -> str:
        """The violation message."""
        return self.message


class CheckResult(list):
    """The violations :meth:`ShardingPlan.check` found.

    A ``list`` of :class:`Violation` with *inverted* truthiness: the
    result is truthy when the plan **satisfies** every constraint
    (preserving the historical ``assert plan.check(cs)`` idiom, where
    ``check`` returned a bare ``True``) and falsy when violations
    exist — iterate it to see which constraints failed.
    """

    def __bool__(self) -> bool:
        """True when no violation was found."""
        return len(self) == 0

    @property
    def messages(self) -> list[str]:
        """The violation messages alone."""
        return [v.message for v in self]


@dataclasses.dataclass
class ShardingPlan:
    """The output of :func:`auto_partition`: a complete sharding decision.

    Attributes:
        mesh: the logical device mesh the plan was searched for.
        in_specs: one ``PartitionSpec`` per flattened program input, in
            ``input_paths`` order.
        input_paths: pytree key paths of the flattened inputs.
        state: the canonical search state (color→axes + resolution bits)
            the specs were projected from.
        cost: the paper cost ``C(s) = RT(s) + MP(s)`` of ``state``.
        breakdown: cost-breakdown dict of the plan
            (compute/memory/collective times, peak bytes, flops, ...).
        baseline_breakdown: same breakdown for the unsharded program.
        constraint_specs: specs for conflict-resolved *intermediate*
            values, keyed by value id (apply via
            ``with_sharding_constraint``).
        logical_rules: ``{logical dim name -> mesh axes}`` projection of
            the plan, when the caller declared ``logical_axes``.
        search_seconds: wall-clock the pipeline took (0 for cache hits).
        evaluations: cost queries issued by the search backend.
        num_colors: NDA colors in the analyzed program.
        num_conflicts: sharding conflicts found (paper §3.3).
        num_compat_sets: box-compatibility sets (paper §3.5).
        num_resolution_bits: supergroup resolution bits (paper §3.6).
        backend: name of the search backend that produced the plan.
        eval_stats: evaluator work counters (cache hits / incremental /
            from-base evaluations); under ``"phase_seconds"``, the
            wall seconds of the partition phases (``cost_model``,
            ``actions``, ``search``, ``build_plan``); and under
            ``"descent"``, when the search ran MCTS, its final descent
            (``SearchResult.descent``).
        fingerprint: deterministic program fingerprint
            (:func:`repro.core.ir.program_fingerprint`) when known.
        cached: True when the plan was served from a
            ``repro.ckpt.plan_store.PlanStore`` instead of a fresh search.
        out_specs: one ``PartitionSpec`` per flattened program *output*,
            projected from the same final state (consumed by
            :meth:`apply` as ``jax.jit``'s ``out_shardings``).  Empty on
            plans deserialized from pre-output-sharding JSON.
        logical_axes: the flattened per-input logical dim names the plan
            was searched with (``None`` when the request declared none);
            lets :meth:`check` resolve logical-name constraint targets.
        kernel_sites: one record per fused kernel site in the traced
            program (``kernel:*`` ops with a dispatch entry point), in
            call order: ``{"site": "<kernel>:<ordinal>", "op": op_idx,
            "kernel": name, "impl": decided impl, "sharded": bool,
            "in_specs": [PartitionSpec, ...], "out_specs": [...]}``.
            :meth:`apply` installs these through the models' kernel
            dispatch so sharded sites lower via ``shard_map`` with the
            plan's specs (docs/kernels.md).  A Pallas
            ``flash_attention`` record adds the forward kernel's tiling
            at the site's per-device shape (``registry.flash_tiling``):
            ``block_q``, ``block_k``, ``tiles_computed``,
            ``tiles_total``; and the backward's impl and tiling
            (``registry.flash_bwd_tiling``): ``bwd_impl``,
            ``bwd_block_q``, ``bwd_block_k``, ``bwd_tiles_computed``,
            ``bwd_tiles_total``.  Empty for programs traced without
            ``use_pallas``.
    """

    mesh: MeshSpec
    in_specs: list[PartitionSpec]
    input_paths: list[str]
    state: ShardingState
    cost: float
    breakdown: dict
    baseline_breakdown: dict
    constraint_specs: dict[int, PartitionSpec]
    logical_rules: dict[str, tuple[str, ...]]
    search_seconds: float
    evaluations: int
    num_colors: int
    num_conflicts: int
    num_compat_sets: int
    num_resolution_bits: int
    backend: str = "mcts"
    eval_stats: dict = dataclasses.field(default_factory=dict)
    fingerprint: str = ""
    cached: bool = False
    out_specs: list[PartitionSpec] = dataclasses.field(default_factory=list)
    logical_axes: list[tuple[str, ...] | None] | None = None
    kernel_sites: list[dict] = dataclasses.field(default_factory=list)

    def jax_in_shardings(self, mesh: jax.sharding.Mesh, treedef=None):
        """Materialize ``in_specs`` as ``NamedSharding``s on ``mesh``.

        Args:
            mesh: a concrete ``jax.sharding.Mesh`` whose axis names match
                the plan's ``MeshSpec``.
            treedef: optional treedef to unflatten the shardings into the
                original argument structure.

        Returns:
            A flat list of ``NamedSharding`` (or the unflattened pytree
            when ``treedef`` is given), suitable for ``jax.jit``'s
            ``in_shardings``.
        """
        specs = [NamedSharding(mesh, s) for s in self.in_specs]
        if treedef is not None:
            return jax.tree_util.tree_unflatten(treedef, specs)
        return specs

    def jax_out_shardings(self, mesh: jax.sharding.Mesh, treedef=None):
        """Materialize ``out_specs`` as ``NamedSharding``s on ``mesh``.

        Args:
            mesh: a concrete ``jax.sharding.Mesh`` whose axis names match
                the plan's ``MeshSpec``.
            treedef: optional treedef to unflatten the shardings into the
                function's output structure.

        Returns:
            A flat list of ``NamedSharding`` (or the unflattened pytree
            when ``treedef`` is given), suitable for ``jax.jit``'s
            ``out_shardings``; ``None`` when the plan carries no output
            specs (pre-output-sharding JSON).
        """
        if not self.out_specs:
            return None
        specs = [NamedSharding(mesh, s) for s in self.out_specs]
        if treedef is not None:
            return jax.tree_util.tree_unflatten(treedef, specs)
        return specs

    def spec_for(self, pattern: str) -> PartitionSpec | None:
        """Return the spec of the input matching ``pattern``.

        Matching tries exact path equality first, then substring
        containment (``"['x']"``), then ``fnmatch`` globs (``*w1*``).
        When several inputs match they must all carry the same spec — a
        multi-match with *differing* specs raises instead of silently
        returning the first hit (the old behaviour).

        Args:
            pattern: exact path, glob, or substring matched against
                ``input_paths``.

        Returns:
            The matching ``PartitionSpec``, or ``None`` when nothing
            matches.

        Raises:
            ValueError: when the pattern matches several inputs whose
                specs differ (ambiguous).
        """
        idxs = match_paths(pattern, self.input_paths)
        if not idxs:
            return None
        specs = {self.in_specs[i] for i in idxs}
        if len(specs) > 1:
            hits = ", ".join(f"{self.input_paths[i]}={self.in_specs[i]}"
                             for i in idxs)
            raise ValueError(f"spec_for({pattern!r}) is ambiguous: {hits}")
        return self.in_specs[idxs[0]]

    def check(self, constraints, *,
              raise_on_violation: bool = True) -> CheckResult:
        """Check the plan against user constraints.

        Args:
            constraints: iterable of ``repro.core.constraints``
                constraints (``Pin`` / ``Replicate`` / ``Forbid``).
            raise_on_violation: raise ``ConstraintError`` when any
                constraint is violated (the historical behaviour); pass
                ``False`` to inspect the violations instead.

        Returns:
            A :class:`CheckResult` — a list of :class:`Violation`
            that is truthy when the plan satisfies every constraint
            (back-compat with the old bare-``True`` return).

        Raises:
            ConstraintError: listing every violated constraint (unless
                ``raise_on_violation=False``), or when a target resolves
                to no input.
        """
        result = CheckResult(
            Violation(c, msg)
            for c, msg in check_plan_detailed(self, tuple(constraints)))
        if result or not raise_on_violation:
            return result
        raise ConstraintError("plan violates constraints: " +
                              "; ".join(result.messages))

    def verify(self, session=None, request=None, **kwargs):
        """Statically verify the plan (see ``repro.core.verify``).

        Convenience delegator: with a ``session`` this is
        ``session.verify(request, plan, **kwargs)`` (full rule set +
        communication conformance); without one, only the rules that
        need no trace artifacts run (constraint spec checks).

        Args:
            session: the ``repro.api.Session`` that produced the plan
                (enables every rule + conformance).
            request: the ``repro.api.Request`` the plan answered
                (defaults to a bare request on the plan's mesh).
            **kwargs: forwarded to ``Session.verify`` (``hlo``,
                ``conformance``, ...).

        Returns:
            A ``repro.core.verify.VerifyReport``.

        Raises:
            ValueError: when called without a session (artifact-free
                verification needs one; load-from-JSON plans can only be
                checked via :meth:`check`).
        """
        if session is None:
            raise ValueError(
                "plan.verify needs the Session that produced the plan "
                "(the verifier re-derives collectives from its trace "
                "artifacts); for JSON-loaded plans use plan.check")
        return session.verify(request, self, **kwargs)

    def apply(self, fn: Callable, mesh: jax.sharding.Mesh | None = None,
              **jit_kwargs) -> "AppliedPlan":
        """Jit ``fn`` with the plan's input *and* output shardings.

        Args:
            fn: the function the plan was searched for (same signature).
            mesh: concrete ``jax.sharding.Mesh``; built from the plan's
                ``MeshSpec`` over the available devices when ``None``.
            **jit_kwargs: forwarded to ``jax.jit`` (``donate_argnums``,
                ``static_argnums``, ...).

        Returns:
            An :class:`AppliedPlan` — call it like the jitted function,
            or AOT-compile via its ``lower`` method.
        """
        if mesh is None:
            from repro.launch.mesh import make_mesh
            mesh = make_mesh(self.mesh.sizes, self.mesh.axes)
        return AppliedPlan(self, fn, mesh, jit_kwargs)

    def as_dict(self) -> dict:
        """JSON-serializable dict capturing the full plan (the inverse of
        :meth:`from_dict`)."""
        return {
            "mesh": self.mesh.as_dict(),
            "in_specs": [list(map(_spec_entry, s)) for s in self.in_specs],
            "input_paths": self.input_paths,
            "state": {"color_axes": [[c, list(axes)] for c, axes in
                                     self.state.color_axes],
                      "bits": [list(b) for b in self.state.bits],
                      "kernel_impls": [[i, impl] for i, impl in
                                       self.state.kernel_impls]},
            "cost": self.cost,
            "breakdown": self.breakdown,
            "baseline_breakdown": self.baseline_breakdown,
            "constraint_specs": {str(vid): list(map(_spec_entry, s))
                                 for vid, s in self.constraint_specs.items()},
            "logical_rules": {k: list(v) for k, v in
                              self.logical_rules.items()},
            "search_seconds": self.search_seconds,
            "evaluations": self.evaluations,
            "num_colors": self.num_colors,
            "num_conflicts": self.num_conflicts,
            "num_compat_sets": self.num_compat_sets,
            "num_resolution_bits": self.num_resolution_bits,
            "backend": self.backend,
            "eval_stats": self.eval_stats,
            "fingerprint": self.fingerprint,
            "out_specs": [list(map(_spec_entry, s)) for s in self.out_specs],
            "logical_axes": (None if self.logical_axes is None else
                             [list(t) if t is not None else None
                              for t in self.logical_axes]),
            "kernel_sites": [
                {"site": r["site"], "op": r["op"], "kernel": r["kernel"],
                 "impl": r["impl"], "sharded": r["sharded"],
                 "in_specs": [list(map(_spec_entry, s))
                              for s in r["in_specs"]],
                 "out_specs": [list(map(_spec_entry, s))
                               for s in r["out_specs"]],
                 **{k: r[k] for k in (*_TILING_KEYS, "bwd_impl")
                    if k in r}}
                for r in self.kernel_sites],
            "schema": 2,
        }

    def to_json(self) -> str:
        """Serialize the plan to a JSON string (see :meth:`as_dict`)."""
        return json.dumps(self.as_dict(), indent=2)

    @classmethod
    def from_dict(cls, d: dict) -> "ShardingPlan":
        """Rebuild a plan from :meth:`as_dict` output.

        Args:
            d: a dict produced by :meth:`as_dict` / parsed plan JSON.

        Returns:
            An equivalent ``ShardingPlan`` (``cached`` is reset to False;
            the plan store sets it on retrieval).
        """
        m = d["mesh"]
        state_d = d.get("state", {"color_axes": [], "bits": []})
        return cls(
            mesh=MeshSpec(tuple(m["axes"]), tuple(m["sizes"]),
                          tuple(m.get("dcn_axes", ()))),
            in_specs=[_spec_from_entries(s) for s in d["in_specs"]],
            input_paths=list(d["input_paths"]),
            state=ShardingState(
                tuple((int(c), tuple(axes))
                      for c, axes in state_d["color_axes"]),
                tuple((int(sg), int(b)) for sg, b in state_d["bits"]),
                tuple((int(i), str(impl)) for i, impl in
                      state_d.get("kernel_impls", []))),
            cost=d["cost"],
            breakdown=dict(d["breakdown"]),
            baseline_breakdown=dict(d["baseline_breakdown"]),
            constraint_specs={int(vid): _spec_from_entries(s)
                              for vid, s in
                              d.get("constraint_specs", {}).items()},
            logical_rules={k: tuple(v) for k, v in
                           d.get("logical_rules", {}).items()},
            search_seconds=d["search_seconds"],
            evaluations=d["evaluations"],
            num_colors=d["num_colors"],
            num_conflicts=d["num_conflicts"],
            num_compat_sets=d["num_compat_sets"],
            num_resolution_bits=d["num_resolution_bits"],
            backend=d.get("backend", "mcts"),
            eval_stats=dict(d.get("eval_stats", {})),
            fingerprint=d.get("fingerprint", ""),
            out_specs=[_spec_from_entries(s)
                       for s in d.get("out_specs", [])],
            logical_axes=(None if d.get("logical_axes") is None else
                          [tuple(t) if t is not None else None
                           for t in d["logical_axes"]]),
            kernel_sites=[
                {"site": r["site"], "op": int(r["op"]),
                 "kernel": r["kernel"], "impl": r["impl"],
                 "sharded": bool(r["sharded"]),
                 "in_specs": [_spec_from_entries(s)
                              for s in r["in_specs"]],
                 "out_specs": [_spec_from_entries(s)
                               for s in r["out_specs"]],
                 **{k: int(r[k]) for k in _TILING_KEYS if k in r},
                 **({"bwd_impl": r["bwd_impl"]} if "bwd_impl" in r
                    else {})}
                for r in d.get("kernel_sites", [])],
        )

    @classmethod
    def from_json(cls, s: str) -> "ShardingPlan":
        """Rebuild a plan from a :meth:`to_json` string.

        Args:
            s: JSON produced by :meth:`to_json`.

        Returns:
            The reconstructed ``ShardingPlan``.
        """
        return cls.from_dict(json.loads(s))


class AppliedPlan:
    """The result of :meth:`ShardingPlan.apply`: a sharded jitted function.

    Jitting is deferred to the first call (or ``lower``) because
    ``jax.jit``'s ``in_shardings``/``out_shardings`` must mirror the
    argument and output pytree structures, which are only known once
    arguments arrive.  The jitted function is cached per argument
    (treedef, shape/dtype struct) — treedef alone is not enough, since
    the output structure (and hence ``out_shardings``) can depend on the
    input shapes — so steady-state calls pay one dict lookup.

    Plans carrying ``kernel_sites`` additionally trace ``fn`` under a
    kernel-dispatch context: each fused site executes the plan's chosen
    implementation, and sharded sites lower through ``shard_map`` with
    the plan's per-site specs (mappable roles only — blocked roles stay
    whole per device; see docs/kernels.md).
    """

    def __init__(self, plan: "ShardingPlan", fn: Callable,
                 mesh: jax.sharding.Mesh, jit_kwargs: dict) -> None:
        """Bind a plan to a function and a concrete mesh.

        Args:
            plan: the sharding plan to install.
            fn: the function the plan was searched for.
            mesh: concrete mesh matching the plan's ``MeshSpec`` axes.
            jit_kwargs: extra keyword arguments for ``jax.jit``.
        """
        self.plan = plan
        self.fn = fn
        self.mesh = mesh
        self._jit_kwargs = dict(jit_kwargs)
        self._cache: dict = {}
        self._traced_fn = self._with_kernel_dispatch(fn)

    def _with_kernel_dispatch(self, fn: Callable) -> Callable:
        """Wrap ``fn`` so jit-tracing runs under the plan's kernel
        dispatch (site ordinals align with the trace because the model
        code runs identically here and in ``extract_program``)."""
        sites = self.plan.kernel_sites
        if not sites:
            return fn
        from repro.models.sharding import KernelDispatch, kernel_dispatch
        disp = KernelDispatch(
            impls={r["site"]: r["impl"] for r in sites},
            mesh=self.mesh,
            specs={r["site"]: (tuple(r["in_specs"]),
                               r["out_specs"][0]
                               if len(r["out_specs"]) == 1
                               else tuple(r["out_specs"]))
                   for r in sites if r["sharded"]})

        def dispatched(*a, **kw):
            with kernel_dispatch(disp):
                return fn(*a, **kw)
        return dispatched

    @staticmethod
    def _leaf_aval(x) -> tuple:
        dtype = getattr(x, "dtype", None)
        if dtype is None:
            dtype = jax.numpy.result_type(x)
        return (tuple(getattr(x, "shape", ())), str(dtype))

    def _jitted(self, args: tuple, kwargs: dict):
        if kwargs:
            raise ValueError(
                "plan.apply() functions take positional arguments only "
                "(jax.jit in_shardings do not cover keyword arguments)")
        flat, _ = jax.tree_util.tree_flatten((args, {}))
        if len(flat) != len(self.plan.in_specs):
            raise ValueError(
                f"plan has {len(self.plan.in_specs)} input specs but the "
                f"call provides {len(flat)} argument leaves")
        args_def = jax.tree_util.tree_structure(args)
        # key on the full (treedef, shape/dtype struct): out_shardings are
        # built from eval_shape of the *first* call's avals, and a
        # function's output structure may change with its input shapes —
        # reusing a treedef-keyed entry across different arg shapes served
        # a stale jitted function (regression: tests/test_api.py)
        key = (args_def, tuple(self._leaf_aval(x) for x in flat))
        hit = self._cache.get(key)
        if hit is not None:
            return hit
        in_sh = jax.tree_util.tree_unflatten(
            args_def, [NamedSharding(self.mesh, s)
                       for s in self.plan.in_specs])
        out_sh = None
        if self.plan.out_specs:
            out_shape = jax.eval_shape(self._traced_fn, *args)
            out_def = jax.tree_util.tree_structure(out_shape)
            if out_def.num_leaves != len(self.plan.out_specs):
                raise ValueError(
                    f"plan has {len(self.plan.out_specs)} output specs "
                    f"but fn returns {out_def.num_leaves} leaves")
            out_sh = jax.tree_util.tree_unflatten(
                out_def, [NamedSharding(self.mesh, s)
                          for s in self.plan.out_specs])
        jitted = jax.jit(self._traced_fn, in_shardings=in_sh,
                         out_shardings=out_sh, **self._jit_kwargs)
        self._cache[key] = jitted
        return jitted

    def __call__(self, *args, **kwargs):
        """Run the sharded jitted function.

        Args:
            *args: positional arguments (structure must match the traced
                function's).
            **kwargs: rejected — ``in_shardings`` cover positional
                arguments only.

        Returns:
            The function result, with the plan's output shardings.
        """
        return self._jitted(args, kwargs)(*args)

    def lower(self, *args, **kwargs):
        """AOT-lower the sharded function (``jax.jit(...).lower``).

        Args:
            *args: positional arguments — ``jax.ShapeDtypeStruct``
                stand-ins suffice.
            **kwargs: rejected (positional-only, as in ``__call__``).

        Returns:
            The ``jax.stages.Lowered`` object (``.compile()`` it).
        """
        return self._jitted(args, kwargs).lower(*args)


def _spec_entry(e):
    if e is None:
        return None
    if isinstance(e, tuple):
        return list(e)
    return e


def _spec_from_entries(entries) -> PartitionSpec:
    return PartitionSpec(*[tuple(e) if isinstance(e, list) else e
                           for e in entries])


@dataclasses.dataclass
class ToastArtifacts:
    """Analysis artifacts, reusable across searches (heavily cached —
    paper §5.3)."""
    prog: Program
    nda: NDAResult
    analysis: ConflictAnalysis
    actions_by_mesh: dict = dataclasses.field(default_factory=dict)
    # wall seconds per analysis phase ("trace" / "nda" / "conflicts"),
    # filled in by :func:`analyze` — the zoo's --profile and the
    # fullscale benchmark report these
    phase_seconds: dict = dataclasses.field(default_factory=dict)


def analyze(fn: Callable, args: tuple, kwargs: dict | None = None
            ) -> ToastArtifacts:
    """Trace ``fn`` and run the mesh-independent analysis once.

    Args:
        fn: function to trace (never executed).
        args: example positional arguments (abstract values suffice).
        kwargs: example keyword arguments.

    Returns:
        :class:`ToastArtifacts` reusable across meshes and searches,
        with per-phase wall times in ``phase_seconds``.
    """
    phases: dict = {}
    with span("trace", phases):
        prog = extract_program(fn, *args, **(kwargs or {}))
    with span("nda", phases):
        nda = run_nda(prog)
    with span("conflicts", phases):
        analysis = analyze_conflicts(nda)
    return ToastArtifacts(prog, nda, analysis, phase_seconds=phases)


def _state_specs(cm: CostModel, state: ShardingState,
                 vids: list[int]) -> list[PartitionSpec]:
    """Project a search state onto one ``PartitionSpec`` per value id
    (program inputs or outputs)."""
    color_axes, bits = state.as_dicts()
    _, suppressed = cm._chosen_suppressed(bits)
    specs = []
    for vid in vids:
        site = cm.nda.def_site[vid]
        axes = cm.site_axes(site, color_axes, suppressed)
        specs.append(PartitionSpec(*[
            (a[0] if len(a) == 1 else tuple(a)) if a else None
            for a in axes]))
    return specs


def kernel_site_records(cm: CostModel,
                        state: ShardingState) -> list[dict]:
    """Project a search state onto per-site fused-kernel records.

    One record per dispatch-site kernel op (backward kernels execute
    inside the forward site's ``custom_vjp`` and get none), in program
    order — which is call order, so the ``"<kernel>:<ordinal>"`` site
    keys line up with the execution-time dispatch counters.  Specs cover
    **mappable** roles only: blocked roles are never sharded inside the
    kernel, so ``shard_map`` receives them whole (GSPMD inserts the
    gather the cost model priced).

    Args:
        cm: the cost model built for the plan's mesh.
        state: the final search state.

    Returns:
        ``ShardingPlan.kernel_sites``-shaped records (see its docstring).
    """
    from repro.kernels import registry as kernel_registry
    color_axes, bits = state.as_dicts()
    _, suppressed = cm._chosen_suppressed(bits)
    impls = dict(state.kernel_impls)
    counters: Counter = Counter()
    records: list[dict] = []

    def _project(roles, vid, mappable, dims):
        axes = cm.site_axes(cm.nda.def_site[vid], color_axes, suppressed)
        entries, sharded = [], False
        for role, a, n in zip(roles, axes, cm.prog.types[vid].shape):
            if role in mappable and a:
                entries.append(a[0] if len(a) == 1 else tuple(a))
                sharded = True
                n //= math.prod(cm._axis_size[x] for x in a)
            else:
                entries.append(None)
            dims.setdefault(role, int(n))
        return PartitionSpec(*entries), sharded

    for op_idx, op in enumerate(cm.prog.ops):
        spec = kernel_registry.spec_for_prim(op.prim)
        if spec is None or not spec.dispatch_site:
            continue
        ordinal = counters[spec.name]
        counters[spec.name] += 1
        in_specs, out_specs, sharded, dims = [], [], False, {}
        for roles, vid in zip(spec.operand_roles, op.operands):
            ps, sh = _project(roles, vid, spec.mappable, dims)
            in_specs.append(ps)
            sharded = sharded or sh
        for roles, vid in zip(spec.result_roles, op.results):
            ps, sh = _project(roles, vid, spec.mappable, dims)
            out_specs.append(ps)
            sharded = sharded or sh
        impl = impls.get(op_idx, spec.default_impl)
        if not spec.feasible(impl, dims):
            # priced as the reference by the cost model; recorded so, since
            # the dispatch refuses an explicit Pallas choice it cannot tile
            impl = "ref"
        record = {
            "site": f"{spec.name}:{ordinal}", "op": op_idx,
            "kernel": spec.name,
            "impl": impl,
            "sharded": sharded,
            "in_specs": in_specs, "out_specs": out_specs}
        if spec.name == "flash_attention" and impl == "pallas":
            t0 = cm.prog.types[op.operands[0]]
            shape = (dims["q_seq"], dims["kv_seq"], dims["head_dim"],
                     bool(op.params.get("causal")),
                     t0.nbytes // max(t0.size, 1))
            record.update(dataclasses.asdict(
                kernel_registry.flash_tiling(*shape)))
            # the backward runs where its forward site runs
            record["bwd_impl"] = impl
            record.update({f"bwd_{k}": v for k, v in dataclasses.asdict(
                kernel_registry.flash_bwd_tiling(*shape)).items()})
        records.append(record)
    return records


def _constraint_specs(cm: CostModel, state: ShardingState,
                      analysis: ConflictAnalysis) -> dict[int, PartitionSpec]:
    color_axes, bits = state.as_dicts()
    _, suppressed = cm._chosen_suppressed(bits)
    out: dict[int, PartitionSpec] = {}
    for c in analysis.conflicts:
        if c.color not in color_axes:
            continue
        for w in c.witnesses:
            if w.site.kind != "def":
                continue
            axes = cm.site_axes(w.site, color_axes, suppressed)
            out[w.site.value] = PartitionSpec(*[
                (a[0] if len(a) == 1 else tuple(a)) if a else None
                for a in axes])
    return out


def _is_name_tuple(x) -> bool:
    # NB: the empty tuple is a *container* (matches empty containers in the
    # args tree), never a name leaf — else flatten order desynchronises.
    return x is None or (isinstance(x, tuple) and type(x) is tuple and
                         len(x) > 0 and
                         all(isinstance(e, (str, type(None))) for e in x))


def flatten_logical_axes(names_tree) -> list[tuple[str, ...] | None]:
    """Flatten a logical-names pytree into program-input order.

    Args:
        names_tree: pytree mirroring the function arguments with tuples
            of logical dim names (or ``None``) at leaf positions.

    Returns:
        One names-tuple (or ``None``) per flattened input leaf, in the
        order used by ``extract_program``.
    """
    return [x if isinstance(x, tuple) else None
            for x in jax.tree_util.tree_leaves(names_tree,
                                               is_leaf=_is_name_tuple)]


def _logical_rules(nda: NDAResult, prog: Program, state: ShardingState,
                   logical_axes: list[tuple[str, ...]] | None
                   ) -> dict[str, tuple[str, ...]]:
    """Project the color→axes assignment onto caller-declared logical
    dimension names (majority vote per color)."""
    if logical_axes is None:
        return {}
    color_axes, _ = state.as_dicts()
    votes: dict[int, Counter] = defaultdict(Counter)
    for vid, names in zip(prog.inputs, logical_axes):
        if names is None:
            continue
        cols = nda.colors_of_value(vid)
        for col, name in zip(cols, names):
            if name:
                votes[col][name] += 1
    rules: dict[str, tuple[str, ...]] = {}
    for col, axes in color_axes.items():
        if col in votes and axes:
            name = votes[col].most_common(1)[0][0]
            rules[name] = tuple(axes)
    return rules


def auto_partition(fn: Callable, args: tuple, mesh: MeshSpec, *,
                   kwargs: dict | None = None,
                   hw: HardwareSpec = HardwareSpec(),
                   mcts: MCTSConfig | None = None,
                   backend: str | SearchBackend = "mcts",
                   search_config=None,
                   portfolio=None,
                   plan_store=None,
                   min_dims: int | None = None,
                   logical_axes: list[tuple[str, ...]] | None = None,
                   constraints=(),
                   artifacts: ToastArtifacts | None = None) -> ShardingPlan:
    """Run the full TOAST pipeline on ``fn(*args, **kwargs)``.

    A one-shot convenience wrapper over the staged API: it builds a
    ``repro.api.Session`` (trace + NDA + conflict analysis) and a
    ``repro.api.Request`` and returns ``session.partition(request)``.
    Repeated partitioning of one function (several meshes, constraint
    sets, backends) is cheaper through an explicit ``Session``.

    Args:
        fn: the function to partition (a train/serve step).  Only traced,
            never executed.
        args: example arguments (``jax.ShapeDtypeStruct`` stand-ins work).
        mesh: logical device mesh to shard over.
        kwargs: optional keyword arguments for ``fn``.
        hw: hardware roofline constants (per-chip FLOPs, HBM, ICI, memory
            budget).
        mcts: MCTS-specific config alias (ignored by other backends).
        backend: search strategy — "mcts" (default), "beam", "greedy",
            "portfolio", or a ``SearchBackend`` instance.
        search_config: backend-specific config object (``BeamConfig``,
            ``PortfolioConfig``, ...).
        portfolio: convenience switch for the portfolio runner: pass a
            ``repro.core.portfolio.PortfolioConfig`` (or ``True`` for the
            default portfolio) instead of setting ``backend`` and
            ``search_config`` separately.
        plan_store: a ``repro.ckpt.plan_store.PlanStore`` (or a directory
            path for one).  When given, a plan cached under this
            program's fingerprint × ``mesh`` × ``hw`` × request key is
            returned without searching, and fresh plans are persisted on
            the way out.
        min_dims: action-space pruning threshold — colors occurring on
            fewer dims are not sharded directly (paper uses 10).
        logical_axes: optional per-input logical dim names (see
            ``flatten_logical_axes``); enables ``plan.logical_rules``.
        constraints: optional ``repro.core.constraints`` constraints
            (``Pin`` / ``Replicate`` / ``Forbid``) the plan must satisfy.
        artifacts: pre-computed analysis artifacts to reuse across
            meshes/searches (see :func:`analyze`).

    Returns:
        A :class:`ShardingPlan`; ``plan.cached`` is True when it came from
        the plan store.
    """
    from repro.api import Request, Session
    from repro.core.search import get_backend
    if portfolio is not None and portfolio is not False:
        backend = "portfolio"
        if search_config is None and not isinstance(portfolio, bool):
            search_config = portfolio
    if search_config is None and mcts is not None:
        engine = get_backend(backend)
        if engine.name == "mcts":
            search_config = mcts
        backend = engine        # resolved once; reused by the session
    if min_dims is None:
        from repro.core.actions import DEFAULT_MIN_DIMS
        min_dims = DEFAULT_MIN_DIMS
    request = Request(mesh=mesh, hw=hw, backend=backend,
                      search_config=search_config, min_dims=min_dims,
                      logical_axes=logical_axes,
                      constraints=tuple(constraints))
    session = Session(fn, args, kwargs=kwargs, artifacts=artifacts,
                      plan_store=plan_store)
    return session.partition(request)
