"""Entry-point set-up: no silent CPU fallback, and a compile cache placed
from outside.  Every child process here pins ``JAX_PLATFORMS=cpu`` and
never reaches for an accelerator."""

import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(args, **env_over):
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    for k, v in env_over.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=env, cwd=ROOT, timeout=300)


def test_chip_smoke_refuses_cpu():
    """Finding no TPU is a failure that names the platform found, with no
    result line — never a smoke run in interpret mode on the CPU."""
    p = _run(["chip_smoke.py"])
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert '"ok"' not in p.stdout


CACHE_PROBE = (
    "import jax; from repro.launch.runtime import use_compile_cache; "
    "print(use_compile_cache()); print(jax.config.jax_compilation_cache_dir)")


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(tmp_path, from_env):
    """Set from outside, the cache goes there and nowhere else; unset, it
    goes to one fixed path in the checkout that git ignores."""
    want = str(tmp_path / "cache") if from_env else None
    p = _run(["-c", CACHE_PROBE], JAX_COMPILATION_CACHE_DIR=want)
    assert p.returncode == 0, p.stderr[-2000:]
    used, configured = p.stdout.split()
    if from_env:
        assert used == configured == want
    else:
        assert used == configured == os.path.join(
            os.path.abspath(ROOT), ".jax_cache")
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


def test_require_backend_refuses_silent_cpu_fallback():
    from repro.launch.runtime import require_backend

    jax.devices()                        # the backend has started (cpu)
    asked = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", "cpu")
        assert require_backend() == "cpu"
        jax.config.update("jax_platforms", None)
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            require_backend()
    finally:
        jax.config.update("jax_platforms", asked)
