"""Static metadata for every fused kernel the tracer can record.

This module is the single source of truth for the *fused-op IR contract*
(docs/kernels.md): which kernels exist, the dimension **roles** of their
operands/results (how NDA colors propagate through the fused op), which
roles a sharding may map over the mesh (``shard_map``-lowered) vs which
are consumed *inside* the kernel and must never be sharded, the
available implementations, and per-impl roofline formulas (FLOPs /
HBM bytes) the cost model prices kernel sites with.

Deliberately **pure python** — no jax imports — so ``core.nda``,
``core.actions`` and ``core.cost_model`` can consume it without pulling
accelerator code into the analysis layer.
"""

from __future__ import annotations

import dataclasses
import functools
import math

__all__ = [
    "FlashTiling", "KERNEL_PRIM_PREFIX", "KERNELS", "KernelSpec",
    "MIN_BLOCK", "flash_bwd_tiling", "flash_bwd_vmem_bytes",
    "flash_last_block", "flash_tiling", "flash_vmem_bytes",
    "kernel_name", "pallas_feasible", "pick_block", "spec_for_prim",
]

# IR prims for fused kernel sites are f"{KERNEL_PRIM_PREFIX}{name}"
KERNEL_PRIM_PREFIX = "kernel:"

# smallest Pallas block worth launching: the f32 sublane tile.  Shapes
# whose divisor-aligned block falls below this (primes, tiny remainders)
# are priced and executed as the reference impl instead.
MIN_BLOCK = 8


def pick_block(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is ``<= target`` (pure helper).

    Mirrors the block picking in ``kernels.ops`` so the cost model and
    the execution dispatch agree on tiling without importing jax.
    """
    b = min(target, max(n, 1))
    while n % b:
        b -= 1
    return max(b, 1)


# Flash-attention forward tiling.  The largest block a side may take,
# from a sweep of the forward kernel on a TPU v5e chip (PERF.md), and
# the VMEM a grid step may fill by ``flash_vmem_bytes``: the Mosaic
# compiler's default scoped limit on a v5e.
FLASH_MAX_BLOCK = 1024
FLASH_VMEM_BUDGET = 16 * 2**20


@dataclasses.dataclass(frozen=True)
class FlashTiling:
    """How the flash-attention forward grid tiles the score matrix.

    Attributes:
        block_q: rows of a q-block.
        block_k: rows of a k/v-block.
        tiles_total: (q-block, k-block) pairs in the grid.
        tiles_computed: pairs that do work; a causal pair whose first
            key lies after its last query is skipped.
    """

    block_q: int
    block_k: int
    tiles_total: int
    tiles_computed: int


def flash_last_block(qi, block_q: int, block_k: int):
    """The last k-block that causal q-block ``qi`` reads: the block of
    its last query.  Takes ints, or traced ints inside the kernel."""
    return ((qi + 1) * block_q - 1) // block_k


def flash_vmem_bytes(block_q: int, block_k: int, head_dim: int,
                     dtype_bytes: int) -> int:
    """VMEM one forward grid step holds, as the budget counts it.

    Double-buffered q, k, v and o tiles with the head dim padded to 128
    lanes; an f32 copy of the v tile; the f32 score tile; three f32
    (block_q, head_dim) arrays (the accumulator and its update); six
    lane-padded f32 (block_q, 1) rows (running max, sum and their
    updates).  On the shapes checked against the v5e compiler this
    counts 2-3 MiB more than the compiler allocates.
    """
    lanes = -(-head_dim // 128) * 128
    tiles = 2 * dtype_bytes * lanes * (2 * block_q + 2 * block_k)
    return tiles + 4 * (block_k * lanes + block_q * block_k +
                        3 * block_q * lanes + 6 * block_q * 128)


def _aligned_block(n: int, target: int, align: int) -> int:
    """Largest divisor of ``n`` that is ``<= target`` and a multiple of
    ``align``; 0 when there is none."""
    for b in range(min(target, n) // align * align, 0, -align):
        if n % b == 0:
            return b
    return 0


def flash_bwd_vmem_bytes(block_q: int, block_k: int, head_dim: int,
                         dtype_bytes: int) -> int:
    """VMEM one grid step of the flash-attention backward holds, as the
    budget counts it: the larger of its ``dkv`` and ``dq`` passes
    (``stats`` holds less than either).

    Both hold double-buffered q, dO, k and v tiles and their outputs
    (dK and dV, or dQ) with the head dim padded to 128 lanes, and their
    f32 accumulators; the score-sized temporaries count as one f32 tile
    and P and dS in the input dtype, and the f32 statistics blocks as
    (8, block_q) twice and their (block_q, 128) columns.  At hd 256 in
    f32 and 1024 x 1024 this counts 26.6 MiB where the v5e compiler
    allocates 23.7.
    """
    lanes = -(-head_dim // 128) * 128
    squares = block_q * block_k * (4 + 2 * dtype_bytes)
    stats = 2 * 4 * 8 * block_q + 4 * block_q * 128
    dkv = 2 * dtype_bytes * lanes * (2 * block_q + 4 * block_k) + \
        2 * 4 * block_k * lanes
    dq = 2 * dtype_bytes * lanes * (3 * block_q + 2 * block_k) + \
        4 * block_q * lanes
    return squares + stats + max(dkv, dq)


def _fit_tiling(q_seq: int, kv_seq: int, head_dim: int, causal: bool,
                dtype_bytes: int, vmem_bytes, max_q: int = FLASH_MAX_BLOCK,
                max_k: int = FLASH_MAX_BLOCK) -> FlashTiling:
    """The largest sublane-aligned blocks up to ``max_q`` / ``max_k``
    whose ``vmem_bytes`` count stays within ``FLASH_VMEM_BUDGET``,
    halving the larger target until it does; ``pick_block(n, 128)``
    where nothing aligned is larger."""
    sublane = max(8, 32 // dtype_bytes)
    base_q, base_k = pick_block(q_seq, 128), pick_block(kv_seq, 128)
    tq, tk = max_q, max_k
    while True:
        bq = max(base_q, _aligned_block(q_seq, tq, sublane))
        bk = max(base_k, _aligned_block(kv_seq, tk, sublane))
        if max(tq, tk) <= 128 or vmem_bytes(
                bq, bk, head_dim, dtype_bytes) <= FLASH_VMEM_BUDGET:
            break
        if tq >= tk:
            tq //= 2
        else:
            tk //= 2
    nq, nk = q_seq // bq, kv_seq // bk
    computed = nq * nk
    if causal:
        computed = sum(min(nk, flash_last_block(i, bq, bk) + 1)
                       for i in range(nq))
    return FlashTiling(bq, bk, nq * nk, computed)


@functools.lru_cache(maxsize=1024)
def flash_tiling(q_seq: int, kv_seq: int, head_dim: int, causal: bool,
                 dtype_bytes: int) -> FlashTiling:
    """Blocks of the flash-attention forward kernel at one call's shape.

    Each block is the largest divisor of its length that is a multiple
    of the dtype's sublane tile and at most ``FLASH_MAX_BLOCK``, with the
    pair within ``FLASH_VMEM_BUDGET`` (the larger target halves until it
    fits).  Where no such block is larger than ``pick_block(n, 128)``,
    that block is kept, so the feasibility rule (``MIN_BLOCK``) and
    every shape that tiled before are unchanged.  Pure: ``kernels.ops``
    runs with these blocks and the cost model prices the K/V re-reads
    from ``tiles_computed``.
    """
    return _fit_tiling(q_seq, kv_seq, head_dim, causal, dtype_bytes,
                       flash_vmem_bytes)


@functools.lru_cache(maxsize=1024)
def flash_bwd_tiling(q_seq: int, kv_seq: int, head_dim: int, causal: bool,
                     dtype_bytes: int) -> FlashTiling:
    """Blocks of the flash-attention backward at one call's shape.

    The forward's rule (:func:`flash_tiling`) with the backward's own
    VMEM count (:func:`flash_bwd_vmem_bytes`), and for causal calls no
    block over half its length, so that whole tiles lie past the
    diagonal and are skipped: at (8, 14, 1024, 64) 512 x 512 blocks
    took 1.83 ms a call against 1.96 at 1024 x 1024 on a v5e, and at
    (4, 32, 2048, 96) 1024 x 1024 took 6.96 ms against 7.44 at 512 x 512
    (PERF.md).  The three passes share the blocks, and
    ``tiles_computed`` counts the tiles each of them computes.  Pure;
    the cost model does not read it (it prices the backward as the
    reference).
    """
    half = 2 if causal else 1
    return _fit_tiling(q_seq, kv_seq, head_dim, causal, dtype_bytes,
                       flash_bwd_vmem_bytes,
                       max_q=min(FLASH_MAX_BLOCK, q_seq // half),
                       max_k=min(FLASH_MAX_BLOCK, kv_seq // half))


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Contract of one fused kernel as seen by the analysis stack.

    Attributes:
        name: kernel id (``flash_attention``, ``rg_lru``, ...).
        operand_roles: per-operand dim-role names; equal role names are
            unified by the NDA (they must shard identically).
        result_roles: per-result dim-role names, same role namespace.
        mappable: roles a plan may shard — the site lowers to a
            ``shard_map`` over exactly these roles' mesh axes.
        blocked: roles consumed inside the kernel (contractions, the
            scan axis, lane-aligned tiles); sharding them is excluded
            from the action space while kernel sites are present.
        impls: available implementations, preferred first.  Sites with
            a single impl contribute no search decision.
        block_roles: role -> target block size; Pallas is feasible only
            when every such role's (local) size admits a divisor block
            of at least ``MIN_BLOCK``.
        dispatch_site: True for kernels called through a ``kernels.ops``
            entry point (they allocate a per-trace dispatch site key);
            False for backward kernels, which execute inside the entry
            kernel's ``custom_vjp`` and inherit its site.
    """

    name: str
    operand_roles: tuple[tuple[str, ...], ...]
    result_roles: tuple[tuple[str, ...], ...]
    mappable: frozenset
    blocked: frozenset
    impls: tuple[str, ...]
    block_roles: tuple[tuple[str, int], ...] = ()
    dispatch_site: bool = True

    @property
    def prim(self) -> str:
        """The IR prim this kernel traces as (``kernel:<name>``)."""
        return KERNEL_PRIM_PREFIX + self.name

    @property
    def default_impl(self) -> str:
        """The impl assumed when a state records no explicit choice."""
        return self.impls[0]

    def dims_from_shapes(self, shapes) -> dict:
        """Map role -> size from per-operand shapes (first occurrence).

        Args:
            shapes: one shape tuple per operand, model layout.

        Returns:
            ``{role: size}`` for every operand role.
        """
        dims: dict = {}
        for roles, shape in zip(self.operand_roles, shapes):
            for role, size in zip(roles, shape):
                dims.setdefault(role, int(size))
        return dims

    def flops(self, dims: dict, params: dict) -> float:
        """Model FLOPs of one call given role sizes ``dims``."""
        return _FLOPS[self.name](dims, params)

    def bytes_moved(self, impl: str, dims: dict, params: dict,
                    dtype_bytes: int) -> float:
        """Modelled HBM traffic of one call for implementation ``impl``."""
        return _BYTES[self.name](impl, dims, params, dtype_bytes)

    def feasible(self, impl: str, dims: dict) -> bool:
        """Whether ``impl`` can run on role sizes ``dims``.

        The reference impl always can; Pallas needs every blocked tile
        dimension to admit a divisor block of at least ``MIN_BLOCK``.
        """
        if impl != "pallas":
            return True
        for role, target in self.block_roles:
            n = dims.get(role)
            if n is not None and pick_block(n, target) < MIN_BLOCK:
                return False
        return True


# -- per-kernel roofline formulas -------------------------------------------
#
# dims use the role names of the specs below.  Formulas are intentionally
# simple analytic models — ``fit_hardware`` calibrates an effective rate
# per (kernel, impl) against measured execution on top of them.


def _fa_flops(d, params):
    # two matmuls (QK^T and PV) over the full score matrix; causal
    # self-attention touches half the blocks
    f = 4.0 * d["batch"] * d["heads"] * d["q_seq"] * d["kv_seq"] * \
        d["head_dim"]
    if params.get("causal") and d["q_seq"] == d["kv_seq"]:
        f *= 0.5
    return f


def _fa_bytes(impl, d, params, db):
    io = d["batch"] * d["heads"] * d["head_dim"] * \
        (2.0 * d["q_seq"] + 2.0 * d["kv_seq"]) * db
    if impl == "pallas":
        # flash streaming: Q and O once; a K and a V block per tile the
        # kernel computes (a skipped causal tile copies nothing)
        t = flash_tiling(d["q_seq"], d["kv_seq"], d["head_dim"],
                         bool(params.get("causal")), db)
        return d["batch"] * d["heads"] * d["head_dim"] * db * (
            2.0 * d["q_seq"] + 2.0 * t.block_k * t.tiles_computed)
    # reference: materializes the f32 score matrix (write+read, twice —
    # scores then softmax probabilities)
    scores = 4.0 * d["batch"] * d["heads"] * d["q_seq"] * d["kv_seq"] * 4
    return io + scores


def _fa_bwd_flops(d, params):
    # 5 matmuls in the attention backward vs 2 forward
    return 2.5 * _fa_flops(d, params)


def _fa_bwd_bytes(impl, d, params, db):
    io = d["batch"] * d["heads"] * d["head_dim"] * \
        (4.0 * d["q_seq"] + 4.0 * d["kv_seq"]) * db
    scores = 8.0 * d["batch"] * d["heads"] * d["q_seq"] * d["kv_seq"] * 4
    return io + scores


def _lru_flops(d, params):
    return 2.0 * d["batch"] * d["seq"] * d["channels"]


def _lru_bytes(impl, d, params, db):
    elems = d["batch"] * d["seq"] * d["channels"]
    if impl == "pallas":
        # single pass: read a, b; write h
        return 3.0 * elems * db
    # associative scan: log2(S) combine passes, each reading and
    # writing both carry arrays
    passes = max(1.0, math.ceil(math.log2(max(d["seq"], 2))))
    return 4.0 * elems * db * passes


def _lru_bwd_flops(d, params):
    return 4.0 * d["batch"] * d["seq"] * d["channels"]


def _lru_bwd_bytes(impl, d, params, db):
    passes = max(1.0, math.ceil(math.log2(max(d["seq"], 2))))
    return 6.0 * d["batch"] * d["seq"] * d["channels"] * db * passes


_FLOPS = {
    "flash_attention": _fa_flops,
    "flash_attention_bwd": _fa_bwd_flops,
    "rg_lru": _lru_flops,
    "rg_lru_bwd": _lru_bwd_flops,
}

_BYTES = {
    "flash_attention": _fa_bytes,
    "flash_attention_bwd": _fa_bwd_bytes,
    "rg_lru": _lru_bytes,
    "rg_lru_bwd": _lru_bwd_bytes,
}


# -- the registry -----------------------------------------------------------

_ATTN_Q = ("batch", "q_seq", "heads", "head_dim")
_ATTN_KV = ("batch", "kv_seq", "heads", "head_dim")
_LRU = ("batch", "seq", "channels")

KERNELS: dict[str, KernelSpec] = {
    "flash_attention": KernelSpec(
        name="flash_attention",
        # model layout, GQA already expanded by the layer: q (B,S,H,hd);
        # k, v (B,T,H,hd) -> o (B,S,H,hd)
        operand_roles=(_ATTN_Q, _ATTN_KV, _ATTN_KV),
        result_roles=(_ATTN_Q,),
        mappable=frozenset({"batch", "heads"}),
        # kv_seq is the softmax contraction; q_seq tiles the grid with
        # causal masking against absolute positions; head_dim feeds the
        # MXU contraction — none survive sharding inside the kernel.
        blocked=frozenset({"q_seq", "kv_seq", "head_dim"}),
        impls=("pallas", "ref"),
        block_roles=(("q_seq", 128), ("kv_seq", 128)),
    ),
    "flash_attention_bwd": KernelSpec(
        name="flash_attention_bwd",
        # (q, k, v, d_out) -> (dq, dk, dv)
        operand_roles=(_ATTN_Q, _ATTN_KV, _ATTN_KV, _ATTN_Q),
        result_roles=(_ATTN_Q, _ATTN_KV, _ATTN_KV),
        mappable=frozenset({"batch", "heads"}),
        blocked=frozenset({"q_seq", "kv_seq", "head_dim"}),
        impls=("ref",),
        dispatch_site=False,
    ),
    "rg_lru": KernelSpec(
        name="rg_lru",
        # h_t = a_t * h_{t-1} + b_t over (B, S, R)
        operand_roles=(_LRU, _LRU),
        result_roles=(_LRU,),
        mappable=frozenset({"batch", "channels"}),
        blocked=frozenset({"seq"}),
        impls=("pallas", "ref"),
        block_roles=(("channels", 128),),
    ),
    "rg_lru_bwd": KernelSpec(
        name="rg_lru_bwd",
        # (a, b, d_out) -> (da, db)
        operand_roles=(_LRU, _LRU, _LRU),
        result_roles=(_LRU, _LRU),
        mappable=frozenset({"batch", "channels"}),
        blocked=frozenset({"seq"}),
        impls=("ref",),
        dispatch_site=False,
    ),
}


def kernel_name(prim: str) -> str | None:
    """The kernel id of an IR prim, or ``None`` for non-kernel prims."""
    if prim.startswith(KERNEL_PRIM_PREFIX):
        return prim[len(KERNEL_PRIM_PREFIX):]
    return None


def spec_for_prim(prim: str) -> KernelSpec | None:
    """Registry lookup by IR prim (``kernel:<name>``)."""
    name = kernel_name(prim)
    return KERNELS.get(name) if name else None


def pallas_feasible(name: str, dims: dict) -> bool:
    """Whether the Pallas impl of ``name`` can tile role sizes ``dims``."""
    spec = KERNELS.get(name)
    return spec is not None and spec.feasible("pallas", dims)
