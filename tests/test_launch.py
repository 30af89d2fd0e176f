"""Launch-layer tests: input-spec/name alignment, rule-driven specs, the
loop-aware HLO analyzer, and a subprocess mini dry-run on 8 fake devices."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, SHAPES, cells, get_config
from repro.core.partitioner import flatten_logical_axes
from repro.launch.hlo_analysis import summarize
from repro.launch.specs import specs_from_rules, step_and_inputs


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
def test_specs_and_names_aligned(arch, shape):
    """Every arch×shape cell: logical-name tree flattens leaf-for-leaf with
    the abstract inputs (regression: empty tuples / None desync)."""
    cfg = get_config(arch)
    fn, args, names = step_and_inputs(cfg, SHAPES[shape])
    flat_args = jax.tree_util.tree_leaves(args)
    flat_names = flatten_logical_axes(names)
    assert len(flat_args) == len(flat_names)
    for leaf, nm in zip(flat_args, flat_names):
        if nm is not None:
            assert len(nm) == leaf.ndim, (arch, shape, leaf.shape, nm)


def test_specs_from_rules_divisibility():
    tree = {"a": jax.ShapeDtypeStruct((30, 64), jnp.float32)}
    names = {"a": ("batch", "hidden")}
    specs = specs_from_rules(tree, names,
                             {"batch": ("data",), "hidden": ("model",)},
                             {"data": 16, "model": 16})
    # 30 % 16 != 0 -> batch axis dropped; 64 % 16 == 0 -> kept
    assert specs["a"] == jax.sharding.PartitionSpec(None, "model")


def test_specs_axis_used_once_per_leaf():
    tree = {"a": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
    names = {"a": ("hidden", "hidden")}
    specs = specs_from_rules(tree, names, {"hidden": ("model",)},
                             {"model": 16})
    assert specs["a"] == jax.sharding.PartitionSpec("model", None)


class TestHloAnalyzer:
    def test_loop_free_exact(self):
        def f(x, w):
            return (x @ w).sum()
        c = jax.jit(f).lower(jnp.ones((64, 32)), jnp.ones((32, 16))).compile()
        s = summarize(c.as_text())
        assert s.flops == pytest.approx(2 * 64 * 32 * 16, rel=0.01)

    def test_scan_trip_scaling(self):
        def loop(x, ws):
            def body(h, w):
                return jnp.tanh(h @ w), ()
            h, _ = jax.lax.scan(body, x, ws)
            return h.sum()
        c = jax.jit(loop).lower(jnp.ones((32, 64)),
                                jnp.ones((12, 64, 64))).compile()
        s = summarize(c.as_text())
        assert s.flops == pytest.approx(12 * 2 * 32 * 64 * 64, rel=0.02)
        assert 12 in s.while_trips.values()
        # XLA's own analysis undercounts by the trip count
        assert c.cost_analysis()["flops"] < s.flops / 6

    def test_nested_grad_scan(self):
        def loop(x, ws):
            def body(h, w):
                return jnp.tanh(h @ w), ()
            h, _ = jax.lax.scan(body, x, ws)
            return h.sum()
        g = jax.jit(jax.grad(loop, argnums=1))
        c = g.lower(jnp.ones((8, 32)), jnp.ones((5, 32, 32))).compile()
        s = summarize(c.as_text())
        # fwd (1 dot) + bwd (2 dots) per layer, 5 layers
        expect = 5 * 3 * 2 * 8 * 32 * 32
        assert s.flops == pytest.approx(expect, rel=0.25)


MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_config, SHAPES
from repro.configs.base import ShapeConfig
from repro.launch.specs import step_and_inputs, specs_from_rules
from repro.launch.hlo_analysis import summarize
from repro.launch.mesh import make_mesh
from repro.models.sharding import MANUAL_RULES, logical_rules

cfg = get_config("qwen2_05b").reduced()
shape = ShapeConfig("mini", 64, 8, "train")
mesh = make_mesh((2, 4), ("data", "model"))
axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
fn, args, names = step_and_inputs(cfg, shape)
spec_tree = specs_from_rules(args, names, dict(MANUAL_RULES), axis_sizes)
in_sh = jax.tree_util.tree_map(
    lambda s: jax.sharding.NamedSharding(mesh, s), spec_tree,
    is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
with jax.set_mesh(mesh), logical_rules(dict(MANUAL_RULES)):
    compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
mem = compiled.memory_analysis()
s = summarize(compiled.as_text())
assert s.flops > 0
assert sum(s.coll_bytes.values()) > 0, "sharded grads need collectives"
assert mem.argument_size_in_bytes > 0
print("MINI_DRYRUN_OK", int(s.flops), int(sum(s.coll_bytes.values())))
"""


def test_mini_dryrun_subprocess():
    """End-to-end dry-run machinery on 8 fake devices (subprocess because
    the XLA device count locks at first jax init).  The subprocess
    inherits the environment: a stripped env makes jax's backend init
    stall for minutes on platform probing."""
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", MINI_DRYRUN],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert "MINI_DRYRUN_OK" in res.stdout, res.stderr[-2000:]


def test_cells_skip_rules():
    """long_500k runs only for sub-quadratic archs (DESIGN.md policy)."""
    with_long = {a for a in ARCH_IDS
                 if any(c.name == "long_500k" for c in cells(a))}
    assert with_long == {"mixtral_8x22b", "recurrentgemma_2b", "xlstm_350m"}
    # 33 cells total = 10 archs x 3 + 3 long_500k
    assert sum(len(cells(a)) for a in ARCH_IDS) == 33


# --- zoo mesh-spec parsing (regression: malformed specs -> tracebacks) ------


class TestParseMesh:
    def test_valid_specs(self):
        from repro.launch.zoo import parse_mesh
        m = parse_mesh("4x2")
        assert m.axes == ("data", "model") and m.sizes == (4, 2)
        m3 = parse_mesh("2x4x2")
        assert m3.axes == ("data", "seq", "model")
        m4 = parse_mesh("2x2x2x2")
        assert m4.dcn_axes == ("pod",)
        assert parse_mesh("8").sizes == (8,)

    @pytest.mark.parametrize("bad", ["", "4x", "x4", "axb", "4x-2",
                                     "0x2", "2x0", "1.5x2",
                                     "2x2x2x2x2"])
    def test_malformed_specs_raise_value_error(self, bad):
        from repro.launch.zoo import parse_mesh
        with pytest.raises(ValueError, match="mesh spec"):
            parse_mesh(bad)

    def test_cli_exits_with_usage_not_traceback(self, capsys):
        from repro.launch import zoo
        with pytest.raises(SystemExit) as exc:
            zoo.main(["--mesh", "4x"])
        assert exc.value.code == 2              # argparse usage error
        err = capsys.readouterr().err
        assert "bad mesh spec" in err
        assert "usage:" in err


# --- persistent compilation cache placement ---------------------------------


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_config(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        from repro.launch.compile_cache import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_dir_in_checkout_when_unset(self, monkeypatch):
        import pathlib
        from repro.launch.compile_cache import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = pathlib.Path(__file__).resolve().parents[1]
        got = enable_compile_cache()
        assert got == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        ignored = (root / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored


# --- the CPU measurement worker is never started from the chip -------------


@pytest.mark.parametrize("entry", ["measure_plan", "hlo_for_plan"])
def test_measure_refuses_when_process_holds_a_tpu(entry, monkeypatch):
    from repro.launch import measure
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(measure, "_run_worker_subprocess",
                        lambda *a, **k: pytest.fail("spawned a CPU worker"))
    with pytest.raises(RuntimeError, match="holds a TPU"):
        getattr(measure, entry)("qwen2_05b", {}, None)


# --- chip_smoke.py never reports a result off the chip ----------------------


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_tpu(alone, tmp_path):
    """On the CPU, and from a directory holding nothing else of the
    repo, the smoke exits non-zero and prints no JSON verdict."""
    import os
    import pathlib
    import shutil
    script = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    if alone:
        script = pathlib.Path(shutil.copy(script, tmp_path))
    res = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=300, cwd=tmp_path,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
