"""Install-matrix check (VERDICT r4 missing #5).

The reference CI-checks its extension builds across images
(tests/docker_extension_builds/run.sh: setup.py install with each
feature-flag combination, then import the built extension). On TPU
there is nothing to compile at install time — the matrix collapses to
ONE axis: the wheel must build from pyproject.toml and the FULL public
surface must import from the installed artifact alone (no repo
checkout on the path), with the on-demand native runtime source shipped
inside. Offline throughout: --no-build-isolation, --no-deps, and the
wheel is unzipped rather than pip-installed so the environment is never
mutated.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
import zipfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every public subpackage = the reference's per-extension import checks
PUBLIC_MODULES = [
    "apex_tpu", "apex_tpu.amp", "apex_tpu.optimizers", "apex_tpu.parallel",
    "apex_tpu.contrib.multihead_attn", "apex_tpu.contrib.optimizers",
    "apex_tpu.contrib.groupbn", "apex_tpu.contrib.xentropy",
    "apex_tpu.contrib.sparsity", "apex_tpu.contrib.moe",
    "apex_tpu.models", "apex_tpu.ops", "apex_tpu.prof", "apex_tpu.RNN",
    "apex_tpu.mlp", "apex_tpu.fp16_utils", "apex_tpu.reparameterization",
    "apex_tpu.normalization", "apex_tpu.utils", "apex_tpu.data",
    "apex_tpu.runtime",
]


@pytest.fixture(scope="module")
def wheel(tmp_path_factory):
    # Build from a pristine COPY of the sources, not in-tree: an in-tree
    # build drops build//*.egg-info into the repo root, and setuptools
    # reuses a stale build/lib on later runs — a deleted module could
    # still ship (and import-check green) from the leftovers.
    import shutil
    src = tmp_path_factory.mktemp("src")
    for f in ("pyproject.toml", "README.md"):
        shutil.copy(os.path.join(REPO, f), src / f)
    shutil.copytree(os.path.join(REPO, "apex_tpu"), src / "apex_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = tmp_path_factory.mktemp("wheel")
    r = subprocess.run(
        [sys.executable, "-m", "pip", "wheel", "--no-deps",
         "--no-build-isolation", "--wheel-dir", str(out), str(src)],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    whls = glob.glob(str(out / "apex_tpu-*.whl"))
    assert len(whls) == 1, whls
    return whls[0]


def test_wheel_ships_native_runtime_source(wheel):
    with zipfile.ZipFile(wheel) as z:
        names = z.namelist()
    assert any(n.endswith("csrc/flat_runtime.cpp") for n in names), \
        "on-demand g++ build needs the csrc source inside the wheel"
    assert any(n.endswith("csrc/image_pipeline.cpp") for n in names)


def test_public_surface_imports_from_wheel_alone(wheel, tmp_path):
    site = tmp_path / "site"
    with zipfile.ZipFile(wheel) as z:
        z.extractall(site)
    code = "import importlib\n" + "".join(
        f"importlib.import_module({m!r})\n" for m in PUBLIC_MODULES
    ) + "print('ALL_IMPORTS_OK')"
    env = {"PATH": os.environ.get("PATH", ""),
           "HOME": os.environ.get("HOME", "/root"),
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(site)}   # the wheel contents, NOT the repo
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ALL_IMPORTS_OK" in r.stdout


def test_extras_map_reference_feature_flags():
    """The reference's build flags map to extras (pyproject rationale
    comment); the extras must exist and carry only real dep names."""
    tomllib = pytest.importorskip(
        "tomllib", reason="stdlib tomllib needs python >= 3.11; the "
        "package itself supports 3.10")
    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        meta = tomllib.load(f)
    extras = meta["project"]["optional-dependencies"]
    assert set(extras) >= {"checkpoint", "test", "examples"}
    for name, deps in extras.items():
        assert deps and all(isinstance(d, str) and d for d in deps), \
            (name, deps)
    # console entry point for the launcher survives packaging
    assert "apex-tpu-multiproc" in meta["project"]["scripts"]
