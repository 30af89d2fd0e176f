"""Light tensor IR extracted from a jaxpr.

The paper's NDA operates on straight-line tensor programs in ANF (SSA).
A jaxpr is exactly that.  We extract a flat ``Program`` of ``Op`` nodes over
integer value ids, inlining call-like sub-jaxprs (jit, custom_jvp/vjp,
remat) and instantiating ``scan``/``while`` bodies once with explicit
carry-in/carry-out connections (see nda.py for how those connections become
identities).
"""

from __future__ import annotations

import dataclasses
import hashlib
import re
from typing import Any

import jax
import numpy as np
from jax.extend import core as jcore

from repro.kernels import registry as kernel_registry
from repro.spans import span


@dataclasses.dataclass(frozen=True)
class TensorType:
    shape: tuple[int, ...]
    dtype: Any

    def __post_init__(self) -> None:
        # size/nbytes sit on the cost model's per-row hot path (millions
        # of reads per search); precompute once instead of re-running
        # np.prod + np.dtype per access
        size = 1
        for s in self.shape:
            size *= int(s)
        object.__setattr__(self, "_size", size)
        object.__setattr__(self, "_nbytes",
                           size * np.dtype(self.dtype).itemsize)

    @property
    def rank(self) -> int:
        return len(self.shape)

    @property
    def size(self) -> int:
        return self._size

    @property
    def nbytes(self) -> int:
        return self._nbytes


@dataclasses.dataclass
class Op:
    prim: str
    params: dict
    operands: list[int]          # value ids ( -1 for literals )
    results: list[int]           # value ids
    # For scan-instantiated ops, records which structural role each
    # operand/result plays; used by nda to add loop-carried identities.
    meta: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Program:
    ops: list[Op] = dataclasses.field(default_factory=list)
    types: dict[int, TensorType] = dataclasses.field(default_factory=dict)
    inputs: list[int] = dataclasses.field(default_factory=list)
    outputs: list[int] = dataclasses.field(default_factory=list)
    input_paths: list[str] = dataclasses.field(default_factory=list)
    # extra identity links between values: (vid_a, vid_b, offset_a) means
    # dims[offset_a:] of a are identified dim-wise with dims of b.  Produced
    # by scan carry connections (offset 0) and scan xs/ys slicing (offset 1).
    value_links: list[tuple[int, int, int]] = dataclasses.field(default_factory=list)
    # number of loop iterations each op executes (1 for top level,
    # `length` for ops inside a scan body) — used by the cost model.
    trip_counts: dict[int, int] = dataclasses.field(default_factory=dict)

    def new_value(self, shape, dtype) -> int:
        vid = len(self.types)
        self.types[vid] = TensorType(tuple(int(s) for s in shape), dtype)
        return vid

    def add_op(self, op: Op, trip: int = 1) -> None:
        self.trip_counts[len(self.ops)] = trip
        self.ops.append(op)


# the name the installed JAX gives ``jax.jit`` call sites in a jaxpr
JIT_PRIM = "jit"

# call-like primitives whose sub-jaxpr the extractor inlines
_CALL_PRIMS = {
    JIT_PRIM, "closed_call", "custom_jvp_call", "custom_vjp_call", "remat2",
}


def _sub_jaxpr(prim_name: str, params: dict):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in params:
            j = params[key]
            return j
    return None


# jits with this name prefix (``repro.kernels.ops``) are fused kernel
# sites: the extractor records them as single ``kernel:<name>`` ops
# instead of inlining the Pallas/reference internals.
_KERNEL_JIT_PREFIX = "toast_kernel__"


def _kernel_eqn_info(eqn):
    """``(prim, params, n_operands)`` for a fused-kernel jit eqn, else ``None``.

    The jit name encodes the kernel id plus its static configuration:
    ``toast_kernel__flash_attention__causal=1``.  The registry contract
    is checked so anything unexpected falls back to ordinary inlining
    rather than producing a malformed fused op: results must match the
    registry arity exactly, operands must be at least it — grad-time
    partial evaluation *appends* hoisted loop-invariant values to a
    jit's invars (and can emit constant-only jits reusing the name),
    so the real operands are the leading ``n_operands`` invars, which
    must also have the registry ranks.  Implementation
    choice (pallas vs ref) is deliberately *not* part of the name — the
    traced program, and hence the fingerprint, is impl-independent.
    """
    if eqn.primitive.name != JIT_PRIM:
        return None
    name = eqn.params.get("name", "")
    if not isinstance(name, str) or not name.startswith(_KERNEL_JIT_PREFIX):
        return None
    parts = name[len(_KERNEL_JIT_PREFIX):].split("__")
    spec = kernel_registry.KERNELS.get(parts[0])
    if spec is None or len(eqn.invars) < len(spec.operand_roles) or \
            len(eqn.outvars) != len(spec.result_roles):
        return None
    for var, roles in zip(eqn.invars, spec.operand_roles):
        if len(getattr(var.aval, "shape", ())) != len(roles):
            return None
    params: dict = {"kernel": spec.name}
    for kv in parts[1:]:
        if "=" not in kv:
            continue
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            pass
        params[k] = bool(v) if k == "causal" else v
    return spec.prim, params, len(spec.operand_roles)


class _Extractor:
    def __init__(self) -> None:
        self.prog = Program()

    def value_for(self, atom, env: dict) -> int:
        if isinstance(atom, jcore.Literal):
            val = atom.val
            aval = atom.aval
            vid = self.prog.new_value(getattr(aval, "shape", ()),
                                      getattr(aval, "dtype", np.float32))
            return vid
        return env[atom]

    def bind_var(self, var, env: dict) -> int:
        vid = self.prog.new_value(var.aval.shape, var.aval.dtype)
        env[var] = vid
        return vid

    def extract(self, jaxpr, arg_ids: list[int], env: dict | None = None,
                trip: int = 1) -> list[int]:
        """Walk a (open) jaxpr, returning value ids of its outputs."""
        env = {} if env is None else env
        assert len(jaxpr.invars) == len(arg_ids), (len(jaxpr.invars), len(arg_ids))
        for var, vid in zip(jaxpr.invars, arg_ids):
            env[var] = vid
        for var in jaxpr.constvars:
            env[var] = self.prog.new_value(var.aval.shape, var.aval.dtype)
        for eqn in jaxpr.eqns:
            self._handle_eqn(eqn, env, trip)
        return [self.value_for(v, env) for v in jaxpr.outvars]

    # -- handlers ---------------------------------------------------------

    def _handle_eqn(self, eqn, env, trip) -> None:
        name = eqn.primitive.name
        if name in _CALL_PRIMS or _sub_jaxpr(name, eqn.params) is not None and \
                name not in ("scan", "while", "cond"):
            kernel = _kernel_eqn_info(eqn)
            if kernel is not None:
                # fused kernel site: one op, internals never inlined
                # (trailing invars beyond the registry arity are values
                # hoisted by partial eval — not operands)
                prim, kparams, n_operands = kernel
                in_ids = [self.value_for(a, env)
                          for a in eqn.invars[:n_operands]]
                out_ids = [self.bind_var(v, env) for v in eqn.outvars]
                self.prog.add_op(Op(prim, kparams, in_ids, out_ids), trip)
                return
            sub = _sub_jaxpr(name, eqn.params)
            if sub is not None:
                closed = sub if hasattr(sub, "jaxpr") else None
                inner = closed.jaxpr if closed is not None else sub
                in_ids = [self.value_for(a, env) for a in eqn.invars]
                # custom_jvp/vjp pass extra tracing args sometimes; align tails
                n = len(inner.invars)
                out_ids = self.extract(inner, in_ids[-n:], {}, trip)
                for var, vid in zip(eqn.outvars, out_ids):
                    env[var] = vid
                return
        if name == "scan":
            self._handle_scan(eqn, env, trip)
            return
        if name == "while":
            self._handle_while(eqn, env, trip)
            return
        if name == "cond":
            self._handle_cond(eqn, env, trip)
            return
        # plain op
        in_ids = [self.value_for(a, env) for a in eqn.invars]
        out_ids = [self.bind_var(v, env) for v in eqn.outvars]
        self.prog.add_op(Op(name, dict(eqn.params), in_ids, out_ids), trip)

    def _handle_scan(self, eqn, env, trip) -> None:
        p = eqn.params
        closed = p["jaxpr"]
        inner = closed.jaxpr
        num_consts, num_carry = p["num_consts"], p["num_carry"]
        length = int(p["length"])
        invals = [self.value_for(a, env) for a in eqn.invars]
        consts = invals[:num_consts]
        carries = invals[num_consts:num_consts + num_carry]
        xss = invals[num_consts + num_carry:]
        # one symbolic iteration: body consts = consts; body carries fresh
        # values dim-linked to outer carries; body xs = one slice of xss.
        body_args: list[int] = list(consts)
        body_carry_ids = []
        for c in carries:
            t = self.prog.types[c]
            b = self.prog.new_value(t.shape, t.dtype)
            self.prog.value_links.append((c, b, 0))
            body_carry_ids.append(b)
        body_args += body_carry_ids
        body_xs_ids = []
        for xs in xss:
            t = self.prog.types[xs]
            b = self.prog.new_value(t.shape[1:], t.dtype)
            # dim i+1 of xs links to dim i of slice — recorded as sliced link
            self.prog.value_links.append((xs, b, 1))
            body_xs_ids.append(b)
        body_args += body_xs_ids
        outs = self.extract(inner, body_args, {}, trip * length)
        carry_outs = outs[:num_carry]
        y_outs = outs[num_carry:]
        # outer results
        out_ids = []
        for i, var in enumerate(eqn.outvars):
            vid = self.bind_var(var, env)
            out_ids.append(vid)
            if i < num_carry:
                # loop: body carry out ≗ outer result ≗ body carry in
                self.prog.value_links.append((carry_outs[i], vid, 0))
                self.prog.value_links.append((body_carry_ids[i], vid, 0))
            else:
                y = y_outs[i - num_carry]
                self.prog.value_links.append((vid, y, 1))

    def _handle_while(self, eqn, env, trip) -> None:
        p = eqn.params
        body = p["body_jaxpr"].jaxpr
        nb = p["body_nconsts"]
        invals = [self.value_for(a, env) for a in eqn.invars]
        # invars: cond_consts..., body_consts..., carry...
        nc = p["cond_nconsts"]
        body_consts = invals[nc:nc + nb]
        carries = invals[nc + nb:]
        body_carry_ids = []
        for c in carries:
            t = self.prog.types[c]
            b = self.prog.new_value(t.shape, t.dtype)
            self.prog.value_links.append((c, b, 0))
            body_carry_ids.append(b)
        outs = self.extract(body, body_consts + body_carry_ids, {}, trip)
        for i, var in enumerate(eqn.outvars):
            vid = self.bind_var(var, env)
            self.prog.value_links.append((outs[i], vid, 0))
            self.prog.value_links.append((body_carry_ids[i], vid, 0))

    def _handle_cond(self, eqn, env, trip) -> None:
        p = eqn.params
        branches = p["branches"]
        invals = [self.value_for(a, env) for a in eqn.invars]
        out_ids = [self.bind_var(v, env) for v in eqn.outvars]
        for br in branches:
            outs = self.extract(br.jaxpr, invals[1:], {}, trip)
            for o, r in zip(outs, out_ids):
                self.prog.value_links.append((o, r, 0))


# memory addresses in default object reprs ("<function f at 0x7f..>")
_ADDR_RE = re.compile(r"0x[0-9a-fA-F]{4,}")


def _canon(x) -> str:
    """Deterministic canonical string for an op param value.

    Used by :func:`program_fingerprint`, so the result must be identical
    across processes and interpreter runs: no ``id()``, no default object
    ``repr`` (which embeds addresses), no ``hash()`` (salted by
    PYTHONHASHSEED).  Unknown objects degrade to their type name.
    """
    if x is None or isinstance(x, (bool, int, float, str)):
        return repr(x)
    if isinstance(x, bytes):
        return f"bytes:{hashlib.sha256(x).hexdigest()}"
    if isinstance(x, np.dtype):
        return f"dtype:{x.name}"
    if isinstance(x, np.ndarray):
        return (f"ndarray:{x.shape}:{x.dtype.name}:"
                f"{hashlib.sha256(np.ascontiguousarray(x).tobytes()).hexdigest()}")
    if isinstance(x, (tuple, list)):
        return "[" + ",".join(_canon(e) for e in x) + "]"
    if isinstance(x, (set, frozenset)):
        return "{" + ",".join(sorted(_canon(e) for e in x)) + "}"
    if isinstance(x, dict):
        items = sorted((_canon(k), _canon(v)) for k, v in x.items())
        return "{" + ",".join(f"{k}:{v}" for k, v in items) + "}"
    try:
        # numpy scalars, jnp dtypes, enums (Precision.DEFAULT), ...
        if isinstance(x, np.generic):
            return f"npscalar:{x.dtype.name}:{x!r}"
        s = str(x)
    except Exception:                                      # noqa: BLE001
        s = ""
    if not s or _ADDR_RE.search(s):
        return f"<{type(x).__module__}.{type(x).__qualname__}>"
    return f"{type(x).__qualname__}:{s}"


def program_fingerprint(prog: Program) -> str:
    """Deterministic content hash of a :class:`Program`.

    The fingerprint covers everything the downstream analysis can observe:
    op primitives and canonicalized params, the operand/result value-id
    wiring, tensor types, input/output ids, scan/while value links, and
    trip counts.  It is a pure function of the traced computation — stable
    across processes, PYTHONHASHSEED values, and re-traces of the same
    function — which makes it usable as a persistent cache key (see
    ``repro.ckpt.plan_store``).

    Args:
        prog: the extracted program to hash.

    Returns:
        A 64-char hex SHA-256 digest.
    """
    h = hashlib.sha256()

    def feed(s: str) -> None:
        h.update(s.encode())
        h.update(b"\x00")

    for i, op in enumerate(prog.ops):
        feed(f"op{i}:{op.prim}")
        feed(_canon(op.params))
        feed(_canon(op.operands))
        feed(_canon(op.results))
        feed(_canon(op.meta))
        feed(f"trip:{prog.trip_counts.get(i, 1)}")
    for vid in sorted(prog.types):
        t = prog.types[vid]
        feed(f"v{vid}:{t.shape}:{np.dtype(t.dtype).name}")
    feed(_canon(prog.inputs))
    feed(_canon(prog.outputs))
    feed(_canon(sorted(prog.value_links)))
    return h.hexdigest()


def extract_program(fn, *args, **kwargs) -> Program:
    """Trace ``fn`` to a jaxpr and extract the flat Program."""
    with span("trace.jaxpr"):
        closed = jax.make_jaxpr(fn)(*args, **kwargs)
    with span("trace.ir"):
        return extract_from_jaxpr(closed, args, kwargs)


def extract_from_jaxpr(closed, args=(), kwargs=None) -> Program:
    ex = _Extractor()
    jaxpr = closed.jaxpr
    arg_ids = []
    for var in jaxpr.invars:
        arg_ids.append(ex.prog.new_value(var.aval.shape, var.aval.dtype))
    ex.prog.inputs = list(arg_ids)
    # pytree paths for plan mapping
    try:
        flat, _ = jax.tree_util.tree_flatten_with_path((args, kwargs or {}))
        ex.prog.input_paths = [jax.tree_util.keystr(p) for p, _ in flat]
    except Exception:
        ex.prog.input_paths = [f"arg{i}" for i in range(len(arg_ids))]
    if len(ex.prog.input_paths) != len(arg_ids):
        ex.prog.input_paths = [f"arg{i}" for i in range(len(arg_ids))]
    ex.prog.outputs = ex.extract(jaxpr, arg_ids)
    return ex.prog
