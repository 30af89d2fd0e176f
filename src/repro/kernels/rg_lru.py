"""RG-LRU linear-recurrence Pallas TPU kernel.

Computes ``h_t = a_t * h_{t-1} + b_t`` (the Griffin/RecurrentGemma gated
linear recurrence) for (B, S, R) gate/input tensors.

TPU-native layout: the channel dimension R is tiled in VPU-lane-aligned
blocks of 128; the sequence is tiled in chunks that stream HBM→VMEM along
the minor-most grid dimension while the running hidden state ``h`` lives
in a (1, block_r) VMEM scratch carried across sequence chunks.  Within a
chunk the recurrence runs as an in-VMEM ``fori_loop`` over sublane tiles
of 8 rows — the arithmetic-intensity-1 inner step never touches HBM.

(The pure-JAX model path uses an ``associative_scan``; this kernel is the
single-pass alternative with 2x fewer HBM reads.)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_R = 128
DEFAULT_BLOCK_S = 256


def _rglru_kernel(a_ref, b_ref, o_ref, h_scr, *, block_s: int, rows: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def body(c, h):
        # rows are read and written through the refs, ``rows`` at a time
        # (one sublane tile): Mosaic has no dynamic slice of a loaded
        # value, and a single-row access at a dynamic offset cannot be
        # proven tile-aligned for packed dtypes
        base = pl.multiple_of(c * rows, rows)
        a = a_ref[0, pl.ds(base, rows), :].astype(jnp.float32)
        b = b_ref[0, pl.ds(base, rows), :].astype(jnp.float32)
        out = []
        for j in range(rows):                       # (1, block_r) each
            h = a[j:j + 1] * h + b[j:j + 1]
            out.append(h)
        o_ref[0, pl.ds(base, rows), :] = \
            jnp.concatenate(out, axis=0).astype(o_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_s // rows, body, h_scr[...])


def rg_lru_scan(a, b, *, interpret: bool, block_r: int = DEFAULT_BLOCK_R,
                block_s: int = DEFAULT_BLOCK_S):
    """a, b: (B, S, R) -> h: (B, S, R) with h_t = a_t h_{t-1} + b_t.

    ``interpret`` is required: ``kernels.ops.default_interpret`` resolves
    it from the backend (Mosaic on TPU, the interpreter elsewhere).
    """
    B, S, R = a.shape
    block_r = min(block_r, R)
    block_s = min(block_s, S)
    assert R % block_r == 0 and S % block_s == 0, (S, R, block_s, block_r)
    ns, nr = S // block_s, R // block_r

    kernel = functools.partial(_rglru_kernel, block_s=block_s,
                               rows=math.gcd(block_s, 8))
    return pl.pallas_call(
        kernel,
        # sequence chunks on the minor-most axis: h carries across them
        grid=(B, nr, ns),
        in_specs=[
            pl.BlockSpec((1, block_s, block_r),
                         lambda bi, ri, si: (bi, si, ri)),
            pl.BlockSpec((1, block_s, block_r),
                         lambda bi, ri, si: (bi, si, ri)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_r),
                               lambda bi, ri, si: (bi, si, ri)),
        out_shape=jax.ShapeDtypeStruct((B, S, R), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_r), jnp.float32)],
        interpret=interpret,
    )(a, b)
