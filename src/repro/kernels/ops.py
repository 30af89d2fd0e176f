"""Dispatching public entry points for the fused Pallas kernels.

Model code (``use_pallas=True`` paths) calls :func:`attention` /
:func:`rg_lru` / :func:`gqa_flash_attention`.  Each call

- resolves the implementation (``pallas`` vs ``ref``) from the ambient
  kernel-dispatch state (``repro.models.sharding.kernel_dispatch``) —
  per-site plan decisions, else backend auto-detection; an explicit
  ``pallas`` choice for a shape the Pallas grid cannot tile
  (``registry.MIN_BLOCK``) raises;
- runs the computation inside a **named jit** whose name starts with
  ``toast_kernel__`` — the tracer (``core.ir``) records that boundary as
  a single fused IR op (``prim="kernel:flash_attention"`` etc.) instead
  of inlining the kernel internals;
- is differentiable: a ``jax.custom_vjp`` routes the backward pass
  through its own named jit (``toast_kernel__..._bwd``), so train steps
  trace to fused forward *and* backward ops; flash attention's backward
  runs the implementation its forward site resolved to (RG-LRU's runs
  the reference);
- optionally lowers through ``shard_map`` when the dispatch state
  carries the plan's per-site partition specs (``plan.apply`` installs
  them), so sharded kernel sites execute as per-device Pallas calls
  over the mappable roles only.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import registry
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_bwd)
from repro.kernels.registry import MIN_BLOCK
from repro.kernels.rg_lru import rg_lru_scan

__all__ = ["attention", "default_interpret", "gqa_flash_attention",
           "rg_lru"]


def default_interpret() -> bool:
    """Pallas interpret flag for the running backend: Mosaic on TPU only."""
    return jax.default_backend() != "tpu"


def _dispatch():
    """The ambient kernel-dispatch state (lazy import, may be ``None``)."""
    from repro.models.sharding import get_kernel_dispatch
    return get_kernel_dispatch()


def _resolve(kernel: str, dims: dict):
    """Resolve ``(impl, interpret, site_key)`` for one kernel call.

    Order of precedence: per-site plan decision from the dispatch state,
    then the state's default impl, then backend auto-detection (Pallas
    on TPU where the shape tiles, reference elsewhere).  An explicit
    Pallas choice whose shape cannot tile — a divisor block below
    ``MIN_BLOCK`` — raises rather than run something else.
    """
    disp = _dispatch()
    impl = interpret = site = None
    if disp is not None:
        site = disp.next_site(kernel)
        impl = disp.impl_for(site)
        interpret = disp.interpret
    on_tpu = not default_interpret()
    if interpret is None:
        interpret = not on_tpu
    feasible = registry.pallas_feasible(kernel, dims)
    if impl is None:
        impl = "pallas" if feasible and on_tpu else "ref"
    elif impl == "pallas" and not feasible:
        raise ValueError(
            f"{kernel}: pallas was chosen for shape {dims}, which has no "
            f"divisor block >= {MIN_BLOCK}")
    return impl, interpret, site


def _maybe_shard_map(kernel: str, site, fn):
    """Wrap ``fn`` in ``shard_map`` when the plan supplied site specs."""
    disp = _dispatch()
    if disp is None or site is None:
        return fn
    spec = disp.specs_for(site)
    if spec is None:
        return fn
    mesh, in_specs, out_specs = spec
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# flash attention (model layout, GQA pre-expanded: q (B,S,H,hd);
# k, v (B,T,H,hd))
# ---------------------------------------------------------------------------


def _ref_attention_model_layout(q, k, v, causal: bool):
    out = ref.reference_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal)
    return out.transpose(0, 2, 1, 3)


@lru_cache(maxsize=None)
def _fa_fwd_jit(causal: bool):
    """Named forward jit — the fused-op trace boundary."""

    def fwd(q, k, v, impl, interpret):
        if impl == "pallas":
            B, S, H, hd = q.shape
            T = k.shape[1]
            qt = q.transpose(0, 2, 1, 3)
            kt = k.transpose(0, 2, 1, 3)
            vt = v.transpose(0, 2, 1, 3)
            t = registry.flash_tiling(S, T, hd, causal, q.dtype.itemsize)
            out = flash_attention(
                qt, kt, vt, causal=causal, block_q=t.block_q,
                block_k=t.block_k, interpret=interpret)
            return out.transpose(0, 2, 1, 3)
        return _ref_attention_model_layout(q, k, v, causal)

    fwd.__name__ = f"toast_kernel__flash_attention__causal={int(causal)}"
    return jax.jit(fwd, static_argnums=(3, 4))


@lru_cache(maxsize=None)
def _fa_bwd_jit(causal: bool):
    """Named backward jit — traces as ``kernel:flash_attention_bwd``.

    Runs the Pallas backward where the forward site resolved to Pallas,
    else the vjp of the reference.
    """

    def bwd(q, k, v, g, impl, interpret):
        with jax.named_scope("attn_bwd"):
            if impl == "pallas":
                B, S, H, hd = q.shape
                t = registry.flash_bwd_tiling(S, k.shape[1], hd, causal,
                                              q.dtype.itemsize)
                grads = flash_attention_bwd(
                    *(x.transpose(0, 2, 1, 3) for x in (q, k, v, g)),
                    causal=causal, block_q=t.block_q, block_k=t.block_k,
                    interpret=interpret)
                return tuple(x.transpose(0, 2, 1, 3) for x in grads)
            _, vjp = jax.vjp(
                lambda q_, k_, v_: _ref_attention_model_layout(
                    q_, k_, v_, causal), q, k, v)
            return vjp(g)

    bwd.__name__ = \
        f"toast_kernel__flash_attention_bwd__causal={int(causal)}"
    return jax.jit(bwd, static_argnums=(4, 5))


@lru_cache(maxsize=None)
def _attention_core(causal: bool, impl: str, interpret: bool):
    fwd_jit = _fa_fwd_jit(causal)
    bwd_jit = _fa_bwd_jit(causal)

    @jax.custom_vjp
    def fa(q, k, v):
        return fwd_jit(q, k, v, impl, interpret)

    def fa_fwd(q, k, v):
        return fwd_jit(q, k, v, impl, interpret), (q, k, v)

    def fa_bwd(res, g):
        return bwd_jit(*res, g, impl, interpret)

    fa.defvjp(fa_fwd, fa_bwd)
    return fa


def attention(q, k, v, *, causal: bool = True):
    """Fused attention dispatch: q (B,S,H,hd); k, v (B,T,H,hd).

    GQA group expansion happens in the caller (the model layer), so the
    fused op's head dim is shared across q/k/v and a plan may map it
    over the mesh.  Returns (B,S,H,hd).
    """
    dims = registry.KERNELS["flash_attention"].dims_from_shapes(
        (q.shape, k.shape, v.shape))
    impl, interpret, site = _resolve("flash_attention", dims)
    fn = _maybe_shard_map("flash_attention", site,
                          _attention_core(causal, impl, interpret))
    return fn(q, k, v)


@partial(jax.jit, static_argnames=("causal", "interpret"))
def _legacy_gqa(q, k, v, causal, interpret):
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    g = H // KV
    qt = q.transpose(0, 2, 1, 3)
    kt = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)
    vt = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
    t = registry.flash_tiling(S, T, hd, causal, q.dtype.itemsize)
    out = flash_attention(qt, kt, vt, causal=causal, block_q=t.block_q,
                          block_k=t.block_k, interpret=interpret)
    return out.transpose(0, 2, 1, 3)


def gqa_flash_attention(q, k, v, *, causal: bool = True):
    """Model-layout GQA attention: q (B,S,H,hd); k, v (B,T,KV,hd).

    Groups are expanded to full heads, then the dispatch decides Pallas
    vs reference (and interpret mode) per the ambient state.
    """
    B, S, H, hd = q.shape
    T = k.shape[1]
    dims = {"batch": B, "q_seq": S, "kv_seq": T, "heads": H,
            "head_dim": hd}
    impl, interpret, _ = _resolve("flash_attention", dims)
    if impl == "pallas":
        return _legacy_gqa(q, k, v, causal, interpret)
    g = H // k.shape[2]
    kf = jnp.repeat(k, g, axis=2)
    vf = jnp.repeat(v, g, axis=2)
    return _ref_attention_model_layout(q, kf, vf, causal)


# ---------------------------------------------------------------------------
# RG-LRU gated linear recurrence: h_t = a_t h_{t-1} + b_t; a, b (B,S,R)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _lru_fwd_jit():
    """Named forward jit — traces as ``kernel:rg_lru``."""

    def fwd(a, b, impl, interpret):
        if impl == "pallas":
            B, S, R = a.shape
            return rg_lru_scan(a, b, block_r=registry.pick_block(R, 128),
                               block_s=registry.pick_block(S, 256),
                               interpret=interpret)
        return ref.reference_rg_lru(a, b)

    fwd.__name__ = "toast_kernel__rg_lru"
    return jax.jit(fwd, static_argnums=(2, 3))


@lru_cache(maxsize=None)
def _lru_bwd_jit():
    """Named backward jit — traces as ``kernel:rg_lru_bwd``."""

    def bwd(a, b, g):
        _, vjp = jax.vjp(ref.reference_rg_lru, a, b)
        return vjp(g)

    bwd.__name__ = "toast_kernel__rg_lru_bwd"
    return jax.jit(bwd)


@lru_cache(maxsize=None)
def _lru_core(impl: str, interpret: bool):
    fwd_jit = _lru_fwd_jit()
    bwd_jit = _lru_bwd_jit()

    @jax.custom_vjp
    def lru(a, b):
        return fwd_jit(a, b, impl, interpret)

    def lru_fwd(a, b):
        return fwd_jit(a, b, impl, interpret), (a, b)

    def lru_bwd(res, g):
        return bwd_jit(*res, g)

    lru.defvjp(lru_fwd, lru_bwd)
    return lru


def rg_lru(a, b):
    """Fused gated linear recurrence dispatch; a, b: (B, S, R)."""
    dims = registry.KERNELS["rg_lru"].dims_from_shapes((a.shape, b.shape))
    impl, interpret, site = _resolve("rg_lru", dims)
    fn = _maybe_shard_map("rg_lru", site, _lru_core(impl, interpret))
    return fn(a, b)
