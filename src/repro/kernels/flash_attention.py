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

Validated on CPU in interpret mode against ``ref.reference_attention``
(tests/test_kernels.py); on TPU the same ``pl.pallas_call`` lowers to
Mosaic (tests/test_tpu_compile.py compiles it for a described v5e).
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
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if masked:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)

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

    if not causal:
        _tile(masked=False)
    else:
        # every key at or before every query: no mask to build
        below = (ki + 1) * block_k - 1 <= qi * block_q
        pl.when(below)(functools.partial(_tile, masked=False))
        # the diagonal crosses the tile; tiles past it are skipped
        pl.when(~below & (ki <= flash_last_block(qi, block_q, block_k)))(
            functools.partial(_tile, masked=True))

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
