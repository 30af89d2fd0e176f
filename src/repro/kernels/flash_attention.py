"""Flash attention Pallas TPU kernel.

TPU-native design (not a CUDA port): the online-softmax accumulator state
(m, l, acc) lives in VMEM scratch that persists across the minor-most grid
dimension (the KV-block loop), so each (batch, head, q-block) streams KV
tiles HBM→VMEM while the q tile and the accumulator stay VMEM-resident.
``kernels.ops`` takes the block sizes from ``registry.flash_tiling``,
which picks them from the shape; the defaults here are the MXU edge.

Causal calls do no work on a tile whose first key lies after its last
query: no matmul, no exp, and no K/V copy (the K/V index map stays on
the last block the q-block needs, so the pipeline fetches nothing new).
Only tiles that straddle the diagonal build the position mask.  q and k
reach the QK^T product in their own dtype (a bf16 product is exact in
the f32 accumulator); the softmax statistics, the probabilities, the
P·V product and the accumulator are f32.

The backward (:func:`flash_attention_bwd`) recomputes the softmax from
row statistics in three passes and never writes a score-sized array to
HBM: ``stats`` gives each query's log-sum-exp and ``D = rowsum(dP∘P)``,
``dkv`` holds a k/v block and loops over the q-blocks for dK and dV,
``dq`` holds a q-block and loops over the k-blocks for dQ.  Each of its
five matmuls takes its operands in the input dtype and accumulates in
f32; P, dP, dS and the statistics are f32, and P and dS are rounded to
the input dtype only as operands of the matmuls the reference's vjp
gives them to.  ``stats`` and ``dkv`` compute the transposed tile (k
rows, q lanes), so the per-query statistics broadcast over sublanes and
dK = dSᵀ·q, dV = Pᵀ·dO need no transpose; ``dq`` turns the statistics
of its q-block into columns once.

Validated on CPU in interpret mode against ``ref.reference_attention``
and its vjp (tests/test_kernels.py); on TPU the same ``pl.pallas_call``
lowers to Mosaic (tests/test_tpu_compile.py compiles it for a described
v5e).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.registry import flash_last_block

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _causal_tiles(causal: bool, qi, ki, block_q: int, block_k: int, tile):
    """Run ``tile(masked)`` on tile (qi, ki): unmasked where every key
    lies at or before every query, masked where the diagonal crosses
    it, and not at all past the diagonal (causal only)."""
    if not causal:
        tile(False)
        return
    below = (ki + 1) * block_k - 1 <= qi * block_q
    pl.when(below)(functools.partial(tile, False))
    pl.when(~below & (ki <= flash_last_block(qi, block_q, block_k)))(
        functools.partial(tile, True))


def _scores(q, k, qi, ki, masked: bool, sm_scale: float,
            transposed: bool = False):
    """The f32 score tile ``q·kᵀ`` (block_q, block_k), or ``k·qᵀ``
    (block_k, block_q) when ``transposed``, with the entries past the
    diagonal at ``NEG_INF`` where ``masked``."""
    a, b = (k, q) if transposed else (q, k)
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if masked:
        rows, cols = s.shape
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
        if transposed:
            q_pos, k_pos = qi * cols + c, ki * rows + r
        else:
            q_pos, k_pos = qi * rows + r, ki * cols + c
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return s


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, causal: bool, block_q: int,
                  block_k: int, num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _tile(masked: bool):
        s = _scores(q_ref[0, 0], k_ref[0, 0], qi, ki, masked, sm_scale)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)     # (block_q, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # (block_q, block_k)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    _causal_tiles(causal, qi, ki, block_q, block_k, _tile)

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    sm_scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K):
    """q: (B, H, S, hd); k, v: (B, H, T, hd) — same head count (the ops
    wrapper expands GQA groups).  Returns (B, H, S, hd).

    ``interpret`` is required: ``kernels.ops.default_interpret`` resolves
    it from the backend (Mosaic on TPU, the interpreter elsewhere).
    Causal masking compares absolute positions, ``q_pos >= k_pos``.
    """
    B, H, S, hd = q.shape
    T = k.shape[2]
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    nq, nk = S // block_q, T // block_k

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=nk)

    def kv_index(b, h, qi, ki):
        if causal:
            # past the q-block's last needed K/V block the index stays
            # put, so the pipeline copies nothing for skipped tiles
            ki = jnp.minimum(ki, flash_last_block(qi, block_q, block_k))
        return (b, h, ki, 0)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_index),
            pl.BlockSpec((1, 1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        # the name of the jit around it in kernels.ops, so that a trace
        # finds the kernel by that prefix whichever name the event takes
        name=f"toast_kernel__flash_attention__causal_{int(causal)}",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: stats, dkv and dq passes
# ---------------------------------------------------------------------------

# rows of a q-block's statistics block: log-sum-exp, then D; the block
# is a whole (8, block_q) f32 tile so that any block_q can be copied
STATS_ROWS = 8


def _stats_kernel(q_ref, k_ref, v_ref, do_ref, stats_ref, m_scr, l_scr,
                  dsum_scr, *, sm_scale: float, causal: bool, block_q: int,
                  block_k: int, num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        dsum_scr[...] = jnp.zeros_like(dsum_scr)

    def _tile(masked: bool):
        st = _scores(q_ref[0, 0], k_ref[0, 0], qi, ki, masked, sm_scale,
                     transposed=True)
        m_prev = m_scr[...]                               # (1, block_q)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)                          # (block_k, block_q)
        alpha = jnp.exp(m_prev - m_new)
        dpt = jax.lax.dot_general(v_ref[0, 0], do_ref[0, 0],
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(pt, axis=0, keepdims=True)
        dsum_scr[...] = alpha * dsum_scr[...] + jnp.sum(pt * dpt, axis=0,
                                                        keepdims=True)
        m_scr[...] = m_new

    _causal_tiles(causal, qi, ki, block_q, block_k, _tile)

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        row = jax.lax.broadcasted_iota(jnp.int32, (STATS_ROWS, block_q), 0)
        stats_ref[0, 0, 0] = jnp.where(
            row == 0, m_scr[...] + jnp.log(l),
            jnp.where(row == 1, dsum_scr[...] / l, 0.0))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, stats_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, sm_scale: float, causal: bool,
                block_q: int, block_k: int, num_q_blocks: int):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _tile(masked: bool):
        q, do, v = q_ref[0, 0], do_ref[0, 0], v_ref[0, 0]
        st = _scores(q, k_ref[0, 0], qi, ki, masked, sm_scale,
                     transposed=True)
        pt = jnp.exp(st - stats_ref[0, 0, 0, 0:1, :])     # (block_k, block_q)
        dv_scr[...] += jax.lax.dot_general(
            pt.astype(v.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - stats_ref[0, 0, 0, 1:2, :]) * sm_scale
        dk_scr[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_tiles(causal, qi, ki, block_q, block_k, _tile)

    @pl.when(qi == num_q_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, stats_ref, dq_ref, dq_scr,
               cols_scr, *, sm_scale: float, causal: bool, block_q: int,
               block_k: int, num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # the q-block's statistics as columns: (block_q, STATS_ROWS)
        cols_scr[...] = stats_ref[0, 0, 0].T

    def _tile(masked: bool):
        k = k_ref[0, 0]
        s = _scores(q_ref[0, 0], k, qi, ki, masked, sm_scale)
        p = jnp.exp(s - cols_scr[:, 0:1])                 # (block_q, block_k)
        dp = jax.lax.dot_general(do_ref[0, 0], v_ref[0, 0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - cols_scr[:, 1:2]) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_tiles(causal, qi, ki, block_q, block_k, _tile)

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, do, *, interpret: bool, causal: bool = True,
                        sm_scale: float | None = None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K):
    """Gradients of :func:`flash_attention` with respect to q, k and v.

    q, do: (B, H, S, hd); k, v: (B, H, T, hd).  Returns ``(dq, dk, dv)``
    in the dtypes of q, k and v.  ``kernels.ops`` takes the blocks from
    ``registry.flash_bwd_tiling``; the three passes share them.
    """
    B, H, S, hd = q.shape
    T = k.shape[2]
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(hd)
    nq, nk = S // block_q, T // block_k
    name = f"toast_kernel__flash_attention_bwd__causal_{int(causal)}"
    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k)

    def q_at(b, h, qi, ki):
        return (b, h, qi, 0)

    def kv_at(b, h, qi, ki):
        # past the q-block's last needed block the index stays put, so
        # skipped tiles copy nothing
        if causal:
            ki = jnp.minimum(ki, flash_last_block(qi, block_q, block_k))
        return (b, h, ki, 0)

    q_spec = pl.BlockSpec((1, 1, block_q, hd), q_at)
    kv_spec = pl.BlockSpec((1, 1, block_k, hd), kv_at)
    stats_spec = pl.BlockSpec((1, 1, 1, STATS_ROWS, block_q),
                              lambda b, h, qi, ki: (b, h, qi, 0, 0))

    stats = pl.pallas_call(
        functools.partial(_stats_kernel, num_k_blocks=nk, **common),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec],
        out_specs=stats_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, nq, STATS_ROWS, block_q),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_q), jnp.float32)] * 3,
        interpret=interpret,
        name=f"{name}_stats",
    )(q, k, v, do)

    def q_from_k(b, h, ki, qi):
        # before the k-block's first q-block the index stays on that
        # block, so skipped tiles copy nothing
        if causal:
            qi = jnp.clip(qi, ki * block_k // block_q, nq - 1)
        return (b, h, qi, 0)

    def k_at(b, h, ki, qi):
        return (b, h, ki, 0)

    def stats_from_k(b, h, ki, qi):
        return q_from_k(b, h, ki, qi) + (0,)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q_blocks=nq, **common),
        grid=(B, H, nk, nq),
        in_specs=[pl.BlockSpec((1, 1, block_q, hd), q_from_k),
                  pl.BlockSpec((1, 1, block_k, hd), k_at),
                  pl.BlockSpec((1, 1, block_k, hd), k_at),
                  pl.BlockSpec((1, 1, block_q, hd), q_from_k),
                  pl.BlockSpec((1, 1, 1, STATS_ROWS, block_q),
                               stats_from_k)],
        out_specs=[pl.BlockSpec((1, 1, block_k, hd), k_at)] * 2,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32)] * 2,
        interpret=interpret,
        name=f"{name}_dkv",
    )(q, k, v, do, stats)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_k_blocks=nk, **common),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stats_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32),
                        pltpu.VMEM((block_q, STATS_ROWS), jnp.float32)],
        interpret=interpret,
        name=f"{name}_dq",
    )(q, k, v, do, stats)
    return dq, dk, dv
