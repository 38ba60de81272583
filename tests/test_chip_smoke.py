"""``chip_smoke.py`` at a tiny size on the CPU, and the compile cache.

The smoke's phase functions are the ones the chip runs, called here with
small instances and interpret-mode kernels (the Mosaic-kernel check is
the chip's: ``require_kernels=False``).  The script itself must refuse to
run without a TPU, and fail where the repository is absent.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke as cs  # noqa: E402


def _cpu_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"), **extra)
    return env


def test_smoke_dense_phase_paper_instance():
    moves = cs.dense_phase("paper", *cs.paper_instance(),
                           require_kernels=False)
    assert len(set(moves.values())) == 1      # every path, same moves


def test_smoke_dense_phase_random_instance():
    cs.dense_phase("N=256", *cs.dense_instance(256, 8),
                   require_kernels=False)


def test_smoke_sparse_phase():
    c0s = cs.sparse_phase(8192, 8, require_kernels=False)
    assert set(c0s) == {"jnp election", "edge kernel"}


def test_smoke_des_phase():
    ticks = cs.des_phase(64, 4, threads=8, capacity=64)
    assert set(ticks) == {"single", "distributed"}


def test_smoke_multichip_phase_on_one_device():
    assert cs.multichip_phase(256, 8, num_shards=1)["moves"] > 0


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_smoke_refuses_to_run_without_a_tpu(args):
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py"),
                          *args], env=_cpu_env(), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_smoke_fails_without_the_repository(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = _cpu_env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


_CACHE_PROBE = (
    "import jax, jax.numpy as jnp\n"
    "from repro.compile_cache import enable_compile_cache\n"
    "path = enable_compile_cache()\n"
    "jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(8)).block_until_ready()\n"
    "print(json.dumps([path, jax.config.jax_compilation_cache_dir]))\n")


def test_compile_cache_lands_in_the_env_directory(tmp_path):
    out = subprocess.run(
        [sys.executable, "-c", "import json\n" + _CACHE_PROBE],
        env=_cpu_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout.splitlines()[-1]) == [str(tmp_path)] * 2
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_compile_cache_defaults_to_the_ignored_repo_directory():
    from repro import compile_cache

    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()
    code = ("import json, jax\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "print(json.dumps([enable_compile_cache(), "
            "jax.config.jax_compilation_cache_dir]))\n")
    out = subprocess.run([sys.executable, "-c", code], env=_cpu_env(),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout.splitlines()[-1]) == \
        [str(REPO / ".jax_cache")] * 2
