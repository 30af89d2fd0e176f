"""Public-API docstring gate (ruff pydocstyle rules).

The documented surface (auto_partition / ShardingPlan / SearchBackend /
IncrementalEvaluator / portfolio / plan store / zoo driver) must carry
docstrings with complete Args sections.  Runs only where ruff is
installed (CI installs it via the ``[test]`` extra); mirrors the explicit
CI step in ``.github/workflows/ci.yml``.
"""

import pathlib
import shutil
import subprocess

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

DOC_GATED_FILES = [
    "src/repro/api.py",
    "src/repro/core/constraints.py",
    "src/repro/core/partitioner.py",
    "src/repro/core/search.py",
    "src/repro/core/evaluator.py",
    "src/repro/core/portfolio.py",
    "src/repro/ckpt/plan_store.py",
    "src/repro/launch/zoo.py",
    "src/repro/core/measure.py",
    "src/repro/launch/measure.py",
    "src/repro/core/mesh_search.py",
    "src/repro/core/verify.py",
    "src/repro/kernels/registry.py",
    "src/repro/kernels/ops.py",
    "src/repro/kernels/flash_attention.py",
    "src/repro/kernels/rg_lru.py",
    "src/repro/kernels/ref.py",
]

RULES = "D101,D102,D103,D417"


@pytest.mark.skipif(shutil.which("ruff") is None,
                    reason="ruff not installed")
def test_public_api_docstrings():
    out = subprocess.run(
        ["ruff", "check", "--select", RULES, *DOC_GATED_FILES],
        cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 0, \
        f"docstring gate failed:\n{out.stdout}\n{out.stderr}"
