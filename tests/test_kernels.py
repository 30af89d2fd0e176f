"""Pallas kernel validation: interpret-mode allclose vs the jnp oracles,
with shape/dtype sweeps (hypothesis) per the assignment.

The hypothesis-driven block sweeps skip when the optional test extra is
absent (see pyproject.toml); everything else runs everywhere.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # optional test extra; see pyproject.toml
    given = settings = st = None

from repro.configs import ARCH_IDS, cells, get_config
from repro.kernels import ops, ref, registry
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_bwd)
from repro.kernels.rg_lru import rg_lru_scan


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_allclose(self, causal, dtype):
        key = jax.random.PRNGKey(0)
        B, H, S, hd = 2, 2, 256, 64
        q, k, v = (rand(jax.random.fold_in(key, i), (B, H, S, hd), dtype)
                   for i in range(3))
        out = flash_attention(q, k, v, causal=causal, block_q=64,
                              block_k=64, interpret=True)
        want = ref.reference_attention(q, k, v, causal=causal)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_cross_lengths(self):
        """S != T (prefill against a longer KV)."""
        key = jax.random.PRNGKey(1)
        B, H, S, T, hd = 1, 2, 64, 256, 32
        q = rand(key, (B, H, S, hd))
        k = rand(jax.random.fold_in(key, 1), (B, H, T, hd))
        v = rand(jax.random.fold_in(key, 2), (B, H, T, hd))
        out = flash_attention(q, k, v, causal=False, block_q=32, block_k=64,
                              interpret=True)
        want = ref.reference_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("kv_heads", [1, 2, 4, 8])
    def test_gqa_group_counts(self, kv_heads):
        """Every GQA group count (MQA .. MHA) matches the oracle."""
        key = jax.random.PRNGKey(10 + kv_heads)
        B, S, H, hd = 1, 128, 8, 32
        q = rand(key, (B, S, H, hd))
        k = rand(jax.random.fold_in(key, 1), (B, S, kv_heads, hd))
        v = rand(jax.random.fold_in(key, 2), (B, S, kv_heads, hd))
        out = ops.gqa_flash_attention(q, k, v, causal=True)
        g = H // kv_heads
        kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)
        vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
        want = ref.reference_attention(
            q.transpose(0, 2, 1, 3), kf, vf, causal=True
        ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_gqa_dtypes(self, dtype):
        key = jax.random.PRNGKey(17)
        q = rand(key, (1, 64, 4, 32), dtype)
        k = rand(jax.random.fold_in(key, 1), (1, 64, 2, 32), dtype)
        v = rand(jax.random.fold_in(key, 2), (1, 64, 2, 32), dtype)
        out = ops.gqa_flash_attention(q, k, v, causal=True)
        assert out.dtype == dtype
        kf = jnp.repeat(k, 2, axis=2)
        vf = jnp.repeat(v, 2, axis=2)
        want = ref.reference_attention(
            q.transpose(0, 2, 1, 3), kf.transpose(0, 2, 1, 3),
            vf.transpose(0, 2, 1, 3), causal=True).transpose(0, 2, 1, 3)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_prime_seq_explicit_pallas_raises(self):
        """A prime seq len cannot tile: an explicit Pallas choice raises,
        while the automatic default runs the reference and stays correct."""
        from repro.models.sharding import KernelDispatch, kernel_dispatch
        key = jax.random.PRNGKey(23)
        B, S, H, hd = 1, 131, 4, 32      # 131 is prime: block would be 1
        q, k, v = (rand(jax.random.fold_in(key, i), (B, S, H, hd))
                   for i in range(3))
        with pytest.raises(ValueError, match="no divisor block"):
            with kernel_dispatch(KernelDispatch(default_impl="pallas")):
                ops.gqa_flash_attention(q, k, v, causal=True)
        with kernel_dispatch(KernelDispatch()):
            out = ops.gqa_flash_attention(q, k, v, causal=True)
        want = ref.reference_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_wrapper_matches_model_layout(self):
        key = jax.random.PRNGKey(3)
        B, S, H, KV, hd = 2, 128, 8, 2, 32
        q = rand(key, (B, S, H, hd))
        k = rand(jax.random.fold_in(key, 1), (B, S, KV, hd))
        v = rand(jax.random.fold_in(key, 2), (B, S, KV, hd))
        out = ops.gqa_flash_attention(q, k, v, causal=True)
        # oracle: expand groups then reference
        g = H // KV
        kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)
        vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
        want = ref.reference_attention(
            q.transpose(0, 2, 1, 3), kf, vf, causal=True
        ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def _check_attention(q, k, v, causal, dtype, **blocks):
    out = flash_attention(q, k, v, causal=causal, interpret=True, **blocks)
    want = ref.reference_attention(q, k, v, causal=causal)
    assert out.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


class TestFlashTilingKernel:
    """The kernel at the blocks ``registry.flash_tiling`` picks."""

    @pytest.mark.parametrize("S,T,hd,causal,dtype,blocks", [
        # a skipped tile, a diagonal-free tile and two diagonal tiles
        (2048, 2048, 64, True, jnp.float32, (1024, 1024)),
        (1024, 1024, 64, True, jnp.bfloat16, (1024, 1024)),
        (1024, 1024, 64, False, jnp.bfloat16, (1024, 1024)),
        # S != T, non-causal, block_q < block_k and block_q > block_k
        (256, 2048, 64, False, jnp.float32, (256, 1024)),
        (2048, 256, 32, False, jnp.bfloat16, (1024, 256)),
        # causal with S != T (absolute positions): a skipped tile with
        # block_q < block_k; a diagonal-free tile with block_q > block_k
        (384, 2048, 32, True, jnp.float32, (384, 1024)),
        (2048, 384, 32, True, jnp.bfloat16, (1024, 384)),
    ])
    def test_matches_reference(self, S, T, hd, causal, dtype, blocks):
        t = registry.flash_tiling(S, T, hd, causal, jnp.dtype(dtype).itemsize)
        assert (t.block_q, t.block_k) == blocks
        key = jax.random.PRNGKey(S + T + hd)
        q = rand(key, (1, 2, S, hd), dtype)
        k = rand(jax.random.fold_in(key, 1), (1, 2, T, hd), dtype)
        v = rand(jax.random.fold_in(key, 2), (1, 2, T, hd), dtype)
        _check_attention(q, k, v, causal, dtype, block_q=t.block_q,
                         block_k=t.block_k)

    @pytest.mark.parametrize("S,hd,bq,bk", [
        (1024, 64, 512, 512),        # skip, diagonal-free and diagonal
        (1536, 32, 256, 512),
        (1536, 32, 512, 256),
        (1536, 32, 128, 384),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_explicit_blocks(self, S, hd, bq, bk, dtype):
        """Causal at explicit blocks smaller than the tiling picks, so
        every kind of tile occurs, square and uneven both ways."""
        key = jax.random.PRNGKey(bq + bk)
        q, k, v = (rand(jax.random.fold_in(key, i), (1, 1, S, hd), dtype)
                   for i in range(3))
        _check_attention(q, k, v, True, dtype, block_q=bq, block_k=bk)

    def test_dispatch_runs_tiled_kernel(self):
        """``ops.attention`` forced to Pallas runs the tiled kernel."""
        from repro.models.sharding import KernelDispatch, kernel_dispatch
        key = jax.random.PRNGKey(41)
        q, k, v = (rand(jax.random.fold_in(key, i), (1, 1024, 2, 64),
                        jnp.bfloat16) for i in range(3))
        with kernel_dispatch(KernelDispatch(default_impl="pallas")):
            out = ops.attention(q, k, v, causal=True)
        want = ref.reference_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)


def _attention_shapes():
    """(S, T, head_dim, causal) of every fused attention call of every
    config at its traffic lengths, plus the benchmark's cells."""
    out = {(1024, 1024, 64, True), (2048, 2048, 96, True)}
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        if "attn" not in cfg.pattern or cfg.sliding_window:
            continue
        hd = cfg.resolved_head_dim
        for shape in cells(arch):
            if shape.kind == "decode":
                continue
            n = shape.seq_len
            if cfg.is_encoder_decoder:
                n //= 2
                out.add((n, n, hd, False))
            out.add((n, n, hd, True))
    return sorted(out)


def _brute_tiles(S, T, bq, bk, causal):
    """Tiles of the score matrix with at least one unmasked entry."""
    if not causal:
        return (S // bq) * (T // bk)
    keep = np.arange(S)[:, None] >= np.arange(T)[None, :]
    return int(keep.reshape(S // bq, bq, T // bk, bk).any(axis=(1, 3)).sum())


class TestFlashTiling:
    """``registry.flash_tiling``: pure, and the same answer for every
    shape the parent's rule tiled."""

    @pytest.mark.parametrize("S,T,hd,causal", _attention_shapes() + [
        (1500, 1500, 64, False), (131, 131, 32, True), (96, 96, 16, True),
        (256, 1024, 64, False), (1024, 384, 32, True), (1200, 1200, 64, True),
    ])
    @pytest.mark.parametrize("dtype_bytes", [2, 4])
    def test_blocks(self, S, T, hd, causal, dtype_bytes):
        t = registry.flash_tiling(S, T, hd, causal, dtype_bytes)
        sublane = 32 // dtype_bytes
        for n, b in ((S, t.block_q), (T, t.block_k)):
            assert n % b == 0
            old = registry.pick_block(n, 128)
            # larger than the parent's block only where aligned
            assert b == old or (b > old and b % sublane == 0)
        assert t.tiles_total == (S // t.block_q) * (T // t.block_k)
        assert t.tiles_computed == _brute_tiles(S, T, t.block_q, t.block_k,
                                                causal)
        if max(t.block_q, t.block_k) > 128:
            assert registry.flash_vmem_bytes(
                t.block_q, t.block_k, hd,
                dtype_bytes) <= registry.FLASH_VMEM_BUDGET
        # the parent's feasibility rule, unchanged
        dims = {"batch": 1, "heads": 1, "q_seq": S, "kv_seq": T,
                "head_dim": hd}
        assert registry.pallas_feasible("flash_attention", dims) == (
            registry.pick_block(S, 128) >= registry.MIN_BLOCK and
            registry.pick_block(T, 128) >= registry.MIN_BLOCK)

    @pytest.mark.parametrize("S,T,hd,causal", _attention_shapes() + [
        (1500, 1500, 64, False), (131, 131, 32, True), (96, 96, 16, True),
        (256, 1024, 64, False), (1024, 384, 32, True), (1200, 1200, 64, True),
    ])
    @pytest.mark.parametrize("dtype_bytes", [2, 4])
    def test_bwd_blocks(self, S, T, hd, causal, dtype_bytes):
        """``flash_bwd_tiling``: blocks divide the shape, aligned where
        larger than the parent's, no larger than the forward's, within
        the VMEM budget, and the tile counts are those of the grid."""
        t = registry.flash_bwd_tiling(S, T, hd, causal, dtype_bytes)
        fwd = registry.flash_tiling(S, T, hd, causal, dtype_bytes)
        sublane = 32 // dtype_bytes
        for n, b, f in ((S, t.block_q, fwd.block_q),
                        (T, t.block_k, fwd.block_k)):
            assert n % b == 0 and b <= f
            old = registry.pick_block(n, 128)
            assert b == old or (b > old and b % sublane == 0)
        assert t.tiles_total == (S // t.block_q) * (T // t.block_k)
        assert t.tiles_computed == _brute_tiles(S, T, t.block_q, t.block_k,
                                                causal)
        if max(t.block_q, t.block_k) > 128:
            assert registry.flash_bwd_vmem_bytes(
                t.block_q, t.block_k, hd,
                dtype_bytes) <= registry.FLASH_VMEM_BUDGET

    @pytest.mark.parametrize("S,hd,want", [
        # qwen2_05b.train.s1k: 112 grid steps a call at (8, 14, 1024,
        # 64), against 7168 at the parent's 128-blocks
        (1024, 64, (1024, 1024, 1, 1)),
        (2048, 96, (1024, 1024, 3, 4)),          # phi3_mini
        (32768, 128, (1024, 1024, 528, 1024)),   # prefill_32k at hd 128
    ])
    def test_config_tilings(self, S, hd, want):
        t = registry.flash_tiling(S, S, hd, True, 2)
        assert (t.block_q, t.block_k, t.tiles_computed,
                t.tiles_total) == want

    def test_budget_halves_larger_side(self):
        """hd 256 in f32 does not fit 1024 x 1024: the q side halves."""
        t = registry.flash_tiling(4096, 4096, 256, True, 4)
        assert (t.block_q, t.block_k) == (512, 1024)
        assert registry.flash_vmem_bytes(
            1024, 1024, 256, 4) > registry.FLASH_VMEM_BUDGET

    @pytest.mark.parametrize("causal", [True, False])
    def test_bytes_count_computed_tiles(self, causal):
        """The cost model's Pallas bytes re-read a K and a V block per
        tile the kernel computes."""
        d = {"batch": 2, "heads": 3, "q_seq": 2048, "kv_seq": 2048,
             "head_dim": 96}
        t = registry.flash_tiling(2048, 2048, 96, causal, 2)
        want = 2 * 3 * 96 * 2 * (2 * 2048 + 2 * t.block_k * t.tiles_computed)
        got = registry.KERNELS["flash_attention"].bytes_moved(
            "pallas", d, {"causal": causal}, 2)
        assert got == want
        assert t.tiles_computed == (3 if causal else 4)


def _pallas_names(jaxpr) -> list[str]:
    """Names of the ``pallas_call``s in a jaxpr, nested jaxprs too."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(e.params["name"])
        for p in e.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                if hasattr(sub, "eqns"):
                    out += _pallas_names(sub)
                elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                    out += _pallas_names(sub.jaxpr)
    return out


def _model_layout_grads(q, k, v, g, causal, dispatch):
    """``(dq, dk, dv)`` of ``ops.attention`` under ``dispatch``, and the
    names of the Pallas calls its backward runs."""
    from repro.models.sharding import kernel_dispatch

    def attn(q, k, v):
        return ops.attention(q, k, v, causal=causal)

    with kernel_dispatch(dispatch):
        _, vjp = jax.vjp(attn, q, k, v)
        jaxpr = jax.make_jaxpr(vjp)(g)
        grads = vjp(g)
    return grads, _pallas_names(jaxpr.jaxpr)


def _reference_grads(q, k, v, g, causal):
    """The vjp of ``ref.reference_attention``, in the layout given."""
    _, vjp = jax.vjp(lambda a, b, c: ref.reference_attention(
        a, b, c, causal=causal), q, k, v)
    return vjp(g)


def _check_grads(got, want, dtype):
    """Each gradient within a share of its largest entry.  In bf16 the
    reference also rounds the scores and dP to bf16, where the kernel
    keeps them f32: each lies within about 1% of an f32 computation."""
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= tol * np.abs(b).max()


class TestFlashAttentionBwd:
    """The Pallas backward against the vjp of the reference."""

    @pytest.mark.parametrize("S,hd,causal,dtype", [
        (256, 64, True, jnp.float32),
        (256, 64, False, jnp.bfloat16),
        (256, 96, True, jnp.bfloat16),
        (256, 96, False, jnp.float32),
        # qwen2_05b's head size at 2048 tokens, at 1024-blocks
        (2048, 64, True, jnp.bfloat16),
    ])
    def test_dispatch_matches_reference_vjp(self, S, hd, causal, dtype):
        """``ops.attention`` forced to Pallas runs the three backward
        passes at the blocks ``registry.flash_bwd_tiling`` picks."""
        from repro.models.sharding import KernelDispatch
        key = jax.random.PRNGKey(S + hd)
        q, k, v, g = (rand(jax.random.fold_in(key, i), (1, S, 2, hd), dtype)
                      for i in range(4))
        got, names = _model_layout_grads(
            q, k, v, g, causal, KernelDispatch(default_impl="pallas"))
        prefix = f"toast_kernel__flash_attention_bwd__causal_{int(causal)}"
        assert sorted(names) == sorted(
            f"{prefix}_{p}" for p in ("stats", "dkv", "dq"))
        t = [x.transpose(0, 2, 1, 3) for x in (q, k, v, g)]
        want = [x.transpose(0, 2, 1, 3)
                for x in _reference_grads(*t, causal)]
        _check_grads(got, want, dtype)
        # causal blocks of at most half the length: tiles are skipped
        t = registry.flash_bwd_tiling(S, S, hd, causal,
                                      jnp.dtype(dtype).itemsize)
        assert (t.tiles_computed < t.tiles_total) == causal

    @pytest.mark.parametrize("S,T,bq,bk", [
        (512, 512, 128, 256),       # skipped, diagonal-free, diagonal
        (512, 512, 256, 128),
        (512, 512, 128, 128),
        (128, 512, 64, 128),        # keys past every query: dK = dV = 0
        (512, 128, 128, 64),        # queries past every key
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_explicit_blocks(self, S, T, bq, bk, dtype):
        """Causal at explicit blocks, square and uneven both ways, and
        at S != T (absolute positions), so every kind of tile occurs."""
        key = jax.random.PRNGKey(bq + 3 * bk + T)
        q, g = (rand(jax.random.fold_in(key, i), (1, 2, S, 32), dtype)
                for i in (0, 3))
        k, v = (rand(jax.random.fold_in(key, i), (1, 2, T, 32), dtype)
                for i in (1, 2))
        got = flash_attention_bwd(q, k, v, g, causal=True, block_q=bq,
                                  block_k=bk, interpret=True)
        _check_grads(got, _reference_grads(q, k, v, g, True), dtype)

    @pytest.mark.parametrize("S,dispatch", [
        (256, {}),                             # CPU: nothing asks for Pallas
        (131, {"default_impl": "pallas"}),     # cannot tile: refused
    ])
    def test_backward_is_reference_where_forward_is(self, S, dispatch):
        """Where the forward site resolves to the reference, so does the
        backward: no Pallas call, the reference's gradients."""
        from repro.models.sharding import KernelDispatch
        key = jax.random.PRNGKey(S)
        q, k, v, g = (rand(jax.random.fold_in(key, i), (1, S, 2, 32))
                      for i in range(4))
        d = KernelDispatch(**dispatch)
        if dispatch:
            # the explicit Pallas choice raises at the forward ...
            with pytest.raises(ValueError, match="no divisor block"):
                _model_layout_grads(q, k, v, g, True, d)
            d = KernelDispatch()         # ... and the automatic one runs ref
        got, names = _model_layout_grads(q, k, v, g, True, d)
        assert names == []
        t = [x.transpose(0, 2, 1, 3) for x in (q, k, v, g)]
        want = [x.transpose(0, 2, 1, 3) for x in _reference_grads(*t, True)]
        _check_grads(got, want, jnp.float32)


class TestRGLRU:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_allclose(self, dtype):
        key = jax.random.PRNGKey(0)
        B, S, R = 2, 512, 256
        a = jax.nn.sigmoid(rand(key, (B, S, R))).astype(dtype)
        b = rand(jax.random.fold_in(key, 1), (B, S, R), dtype, 0.1)
        out = rg_lru_scan(a, b, block_r=128, block_s=128, interpret=True)
        want = ref.reference_rg_lru(a, b)
        tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dispatch_entry_matches_ref(self, dtype):
        """``ops.rg_lru`` (dispatch entry point) vs the jnp oracle."""
        key = jax.random.PRNGKey(29)
        a = jax.nn.sigmoid(rand(key, (2, 96, 128))).astype(dtype)
        b = rand(jax.random.fold_in(key, 1), (2, 96, 128), dtype, 0.1)
        out = ops.rg_lru(a, b)
        want = ref.reference_rg_lru(a, b)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_prime_channels_explicit_pallas_raises(self):
        """A prime channel count cannot tile: an explicit Pallas choice
        raises, the automatic default runs the reference correctly."""
        from repro.models.sharding import KernelDispatch, kernel_dispatch
        key = jax.random.PRNGKey(31)
        a = jax.nn.sigmoid(rand(key, (1, 64, 131)))  # prime > block
        b = rand(jax.random.fold_in(key, 1), (1, 64, 131), scale=0.1)
        with pytest.raises(ValueError, match="no divisor block"):
            with kernel_dispatch(KernelDispatch(default_impl="pallas")):
                ops.rg_lru(a, b)
        out = ops.rg_lru(a, b)
        want = ref.reference_rg_lru(a, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_decay_stability(self):
        """Long sequence with strong decay stays bounded (no NaN/Inf)."""
        B, S, R = 1, 2048, 128
        a = jnp.full((B, S, R), 0.999, jnp.float32)
        b = jnp.ones((B, S, R), jnp.float32) * 0.01
        out = rg_lru_scan(a, b, block_r=128, block_s=256, interpret=True)
        assert np.isfinite(np.asarray(out)).all()
        # closed form limit: b / (1 - a)
        np.testing.assert_allclose(float(out[0, -1, 0]),
                                   0.01 * (1 - 0.999 ** S) / 0.001,
                                   rtol=1e-3)


if st is not None:
    class TestBlockSweeps:
        """Hypothesis block-shape sweeps (optional test extra)."""

        @settings(max_examples=8, deadline=None)
        @given(
            bq=st.sampled_from([32, 64, 128]),
            bk=st.sampled_from([32, 64, 128]),
            s_mult=st.integers(1, 3),
            hd=st.sampled_from([32, 64, 128]),
        )
        def test_flash_block_shape_sweep(self, bq, bk, s_mult, hd):
            S = 128 * s_mult
            key = jax.random.PRNGKey(bq * bk + hd)
            q, k, v = (rand(jax.random.fold_in(key, i), (1, 1, S, hd))
                       for i in range(3))
            out = flash_attention(q, k, v, causal=True,
                                  block_q=min(bq, S), block_k=min(bk, S),
                                  interpret=True)
            want = ref.reference_attention(q, k, v, causal=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       rtol=3e-5, atol=3e-5)

        @settings(max_examples=8, deadline=None)
        @given(
            bs=st.sampled_from([64, 128, 256]),
            br=st.sampled_from([64, 128]),
            s=st.sampled_from([256, 512]),
            r=st.sampled_from([128, 384]),
        )
        def test_lru_block_sweep(self, bs, br, s, r):
            key = jax.random.PRNGKey(bs + br + s + r)
            a = jax.nn.sigmoid(rand(key, (1, s, r)))
            b = rand(jax.random.fold_in(key, 1), (1, s, r), scale=0.1)
            out = rg_lru_scan(a, b, block_r=min(br, r),
                              block_s=min(bs, s), interpret=True)
            want = ref.reference_rg_lru(a, b)
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)
