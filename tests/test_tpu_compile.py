"""Compiles for a described TPU v5e: the chip's compiler, no chip.

Interpret mode accepts kernels that Mosaic refuses (a dynamic slice of a
loaded value, a slice not aligned to the tiling, too much VMEM).  These
tests compile the main path's kernels at real widths, and the fused
dispatch through ``shard_map`` on a 2x2 mesh, with the TPU compiler for a
``v5e:2x2`` topology described in a fixture.  Nothing runs: the
assertions are about what the compiled HLO holds.

The topology is described inside a module-scoped fixture, never at
import, so that every pytest-xdist worker collects the same tests and
only the worker given this file loads the TPU library.  The persistent
compilation cache is off around these compiles: a compile for a
described chip cannot be read back without one.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, registry
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_bwd)
from repro.kernels.rg_lru import rg_lru_scan
from repro.models.sharding import KernelDispatch, kernel_dispatch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                      # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh22(topo):
    return Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _holds_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


BWD = "toast_kernel__flash_attention_bwd__causal_1"


def _holds_backward(text: str) -> bool:
    """The three Pallas passes of the causal backward are in the HLO."""
    return all(f"{BWD}_{p}" in text for p in ("stats", "dkv", "dq"))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_compiles_at_qwen2_05b_width(one_chip, dtype):
    """qwen2_05b attention: 14 heads of 64, 1024 tokens."""
    q = _sds((1, 14, 1024, 64), dtype, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False)
    ).lower(q, q, q).compile()
    assert _holds_kernel(compiled)


def _compile_flash(sharding, shape, dtype, causal=True):
    B, H, S, hd = shape
    t = registry.flash_tiling(S, S, hd, causal, jnp.dtype(dtype).itemsize)
    q = _sds(shape, dtype, sharding)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        block_q=t.block_q, block_k=t.block_k,
                                        interpret=False)
    ).lower(q, q, q).compile()
    assert _holds_kernel(compiled)
    return t


def _compile_flash_bwd(sharding, shape, dtype, causal=True):
    B, H, S, hd = shape
    t = registry.flash_bwd_tiling(S, S, hd, causal,
                                  jnp.dtype(dtype).itemsize)
    q = _sds(shape, dtype, sharding)
    compiled = jax.jit(
        lambda q, k, v, g: flash_attention_bwd(
            q, k, v, g, causal=causal, block_q=t.block_q,
            block_k=t.block_k, interpret=False)
    ).lower(q, q, q, q).compile()
    assert _holds_kernel(compiled)
    return t


PICKED_SHAPES = [
    ((8, 14, 1024, 64), jnp.bfloat16),   # qwen2_05b.train.s1k
    ((4, 16, 2048, 96), jnp.bfloat16),   # phi3_mini per device on 2x2
    ((1, 8, 4096, 128), jnp.bfloat16),   # llama3, qwen15_32b, arctic
    ((1, 8, 4096, 128), jnp.float32),
    ((1, 2, 4096, 256), jnp.bfloat16),
    ((1, 2, 4096, 256), jnp.float32),    # over budget at 1024: halves
]


@pytest.mark.parametrize("shape,dtype", PICKED_SHAPES)
def test_flash_attention_bwd_compiles_at_picked_tiling(one_chip, shape,
                                                       dtype):
    """The backward's three passes compile at the blocks
    ``registry.flash_bwd_tiling`` picks, within the VMEM budget."""
    t = _compile_flash_bwd(one_chip, shape, dtype)
    assert t.block_q >= 128 and t.block_k >= 128
    assert registry.flash_bwd_vmem_bytes(
        t.block_q, t.block_k, shape[3],
        jnp.dtype(dtype).itemsize) <= registry.FLASH_VMEM_BUDGET


@pytest.mark.parametrize("shape,dtype", PICKED_SHAPES)
def test_flash_attention_compiles_at_picked_tiling(one_chip, shape, dtype):
    """The blocks ``registry.flash_tiling`` picks compile (Mosaic refuses
    a kernel whose VMEM overflows its scoped limit)."""
    t = _compile_flash(one_chip, shape, dtype)
    assert t.block_q > 128 and t.block_k > 128
    assert registry.flash_vmem_bytes(
        t.block_q, t.block_k, shape[3],
        jnp.dtype(dtype).itemsize) <= registry.FLASH_VMEM_BUDGET


def test_flash_attention_compiles_at_every_config_shape(one_chip):
    """Every config's fused attention at its train and prefill lengths
    (whisper's encoder and decoder at half of them), forward and
    backward: where the tiling differs from the 128-blocks, it
    compiles."""
    from repro.configs import ARCH_IDS, cells, get_config
    shapes = set()
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        if "attn" not in cfg.pattern or cfg.sliding_window:
            continue
        for shape in cells(arch):
            if shape.kind != "decode":
                n = shape.seq_len // (2 if cfg.is_encoder_decoder else 1)
                shapes.add((n, cfg.resolved_head_dim))
    base = lambda S: (registry.pick_block(S, 128),) * 2     # noqa: E731
    for S, hd in sorted(shapes):
        for causal in (True, False):
            t = registry.flash_tiling(S, S, hd, causal, 2)
            if (t.block_q, t.block_k) != base(S):
                _compile_flash(one_chip, (1, 2, S, hd), jnp.bfloat16, causal)
            t = registry.flash_bwd_tiling(S, S, hd, causal, 2)
            if (t.block_q, t.block_k) != base(S):
                _compile_flash_bwd(one_chip, (1, 2, S, hd), jnp.bfloat16,
                                   causal)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rg_lru_compiles_at_recurrentgemma_width(one_chip, dtype):
    """recurrentgemma_2b's RG-LRU as ``layers.rglru_apply`` calls it:
    R = 3840 channels (f32 there), blocks as ``kernels.ops`` picks them."""
    B, S, R = 1, 2048, 3840
    a = _sds((B, S, R), dtype, one_chip)
    compiled = jax.jit(
        lambda a, b: rg_lru_scan(a, b, block_r=registry.pick_block(R, 128),
                                 block_s=registry.pick_block(S, 256),
                                 interpret=False)
    ).lower(a, a).compile()
    assert _holds_kernel(compiled)


def test_sharded_attention_site_lowers_through_shard_map(mesh22):
    """A plan-sharded site (batch on data, heads on model) lowers to a
    per-device Mosaic kernel, forward and backward."""
    spec = P("data", None, "model", None)
    disp = KernelDispatch(impls={"flash_attention:0": "pallas"},
                          interpret=False, mesh=mesh22,
                          specs={"flash_attention:0": ((spec,) * 3, spec)})
    x = _sds((4, 256, 14, 64), jnp.bfloat16, NamedSharding(mesh22, spec))

    def loss(q, k, v):
        o = ops.attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    with kernel_dispatch(disp):
        lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))
                          ).lower(x, x, x)
    compiled = lowered.compile()
    assert _holds_kernel(compiled)
    # per-device kernel: each of the 4 devices sees 2 of 4 rows and 7 of
    # 14 heads; the backward runs as the Pallas passes there too
    text = compiled.as_text()
    assert "bf16[2,7,256,64]" in text
    assert _holds_backward(text)


def test_fused_train_step_plan_compiles_on_2x2(mesh22, monkeypatch):
    """Session -> partition -> plan.apply of a fused (``use_pallas``)
    train step, compiled for four described chips."""
    from repro.api import Request, Session
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.cost_model import MeshSpec
    from repro.launch.specs import step_and_inputs

    # the dispatch asks the running backend (here the CPU) whether to
    # interpret; this compile targets the TPU
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("qwen2_05b").reduced(),
                              use_pallas=True)
    fn, args, names = step_and_inputs(
        cfg, ShapeConfig("tpu_compile", 256, 4, "train"))
    plan = Session(fn, args).partition(Request(
        mesh=MeshSpec(("data", "model"), (2, 2)), backend="greedy",
        min_dims=1, logical_axes=names))
    assert plan.kernel_sites
    assert all(r["impl"] == "pallas" for r in plan.kernel_sites)
    with jax.set_mesh(mesh22):
        compiled = plan.apply(fn, mesh22).lower(*args).compile()
    assert _holds_kernel(compiled)


def test_phi3_mini_2x2_cell_plan_fits_a_v5e_chip(mesh22, monkeypatch):
    """The benchmark's ``phi3_mini.train.2x2`` step as its harness builds
    it (published widths at 16 layers, 8 x 2048 tokens, MCTS seed 0 on
    ``data`` x ``model`` = 2 x 2) plans with both flash sites fused and
    sharded, lowers them through ``shard_map``, and compiles at all 16
    layers to under 16 GiB a device by ``memory_analysis()``."""
    from perfbench import catalog, harness

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    bench = catalog.benchmark()
    cell = catalog.workload(bench, "phi3_mini.train.2x2")
    conf = catalog.config(bench, cell["config"])
    mix = catalog.traffic(cell["traffic"])
    assert (conf["num_hidden_layers"], mix["batch"], mix["seq_len"],
            mix["mesh"], mix["search"]) == (
        16, 8, 2048, [2, 2], {"backend": "mcts", "seed": 0})
    tc = harness.TrainCell(conf, mix, list(mesh22.devices.flat))
    ev = tc.plan_evidence()
    # the searched plan of record (MCTS seed 0 and its final descent):
    # the cost model prices the backward as the reference, so the Pallas
    # backward moves nothing here
    assert (ev["plan_hash"], ev["evaluations"]) == ("abeb3067f2467d38", 1516)
    assert ev["cost"] == pytest.approx(0.244020, abs=1e-6)
    assert [(r["impl"], r["sharded"], r["bwd_impl"])
            for r in tc.plan.kernel_sites] == [("pallas", True, "pallas")] * 2
    assert tc.kernels_shard_mapped
    assert _holds_backward(tc.compiled.as_text())
    assert tc.memory_analysis()["step_bytes"] < 16 * 2**30


def test_qwen2_05b_cell_plan_and_backward_compile_for_a_v5e_chip(
        topo, monkeypatch):
    """The benchmark's ``qwen2_05b.train.s1k`` step as its harness
    builds it keeps its plan and compiles with the Pallas backward, at
    the blocks its site record names."""
    from perfbench import catalog, harness

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    bench = catalog.benchmark()
    cell = catalog.workload(bench, "qwen2_05b.train.s1k")
    mix = catalog.traffic(cell["traffic"])
    tc = harness.TrainCell(catalog.config(bench, cell["config"]), mix,
                           [topo.devices[0]])
    assert tc.plan_evidence()["plan_hash"] == "4882248af0175949"
    t = registry.flash_bwd_tiling(1024, 1024, 64, True, 2)
    for r in tc.plan.kernel_sites:
        assert (r["bwd_impl"], r["bwd_block_q"], r["bwd_block_k"],
                r["bwd_tiles_computed"], r["bwd_tiles_total"]) == (
            "pallas", t.block_q, t.block_k, t.tiles_computed,
            t.tiles_total)
    assert _holds_backward(tc.compiled.as_text())
    assert tc.memory_analysis()["step_bytes"] < 16 * 2**30
