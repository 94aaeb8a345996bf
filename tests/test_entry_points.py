"""Entry points leave the device alone until they mean to use it.

A TPU belongs to one process at a time.  A parent that initialises a JAX
backend before it starts its child (`launch/serve.py --supervise`,
`bench_serving --crash-smoke`) holds the chip and starves the child, so
importing the entry points must initialise no backend.  The compile
cache is placed from outside, and `chip_smoke.py` refuses anything but a
TPU.  Each check runs in a fresh `JAX_PLATFORMS=cpu` interpreter.
"""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _python(code: str, **env) -> subprocess.CompletedProcess:
    full = {**os.environ, "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.pathsep.join([str(ROOT / "src"), str(ROOT)]),
            **env}
    full = {k: v for k, v in full.items() if v is not None}
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=full,
                          capture_output=True, text=True, timeout=300)


def test_imports_initialise_no_backend():
    out = _python(
        "import repro.launch.serve, benchmarks.bench_serving\n"
        "from jax._src import xla_bridge\n"
        "print(sorted(xla_bridge._backends))\n")
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "[]", out.stdout


def test_compile_cache_uses_the_variable_when_set(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper takes that directory
    as it is, and compiled programs land there."""
    out = _python(
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir()), "no compiled program was cached"


def test_compile_cache_defaults_to_ignored_checkout_dir():
    """Unset, the cache is a fixed directory of the checkout that git
    ignores (checked without compiling into it)."""
    out = _python(
        "import jax\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n",
        JAX_COMPILATION_CACHE_DIR=None)
    assert out.returncode == 0, out.stderr
    returned, configured = out.stdout.strip().splitlines()[-2:]
    assert returned == configured == str(ROOT / ".jax_cache")
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_chip_smoke_refuses_the_cpu():
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
