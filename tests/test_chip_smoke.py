"""`chip_smoke.py` refuses to run off the chip, and the persistent compile
cache lands where `repro.launch.compile_cache` says."""

import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd, env_extra=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("chips", [[], ["--chips", "4"]])
def test_chip_smoke_refuses_cpu_before_any_phase(chips):
    out = _run([SMOKE] + chips, cwd=REPO)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "phase=" not in out.stdout
    assert "no TPU found" in out.stderr


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path)
    out = _run([str(tmp_path / "chip_smoke.py")], cwd=tmp_path,
               drop=("PYTHONPATH",))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


_CACHE_PROBE = textwrap.dedent("""
    import os, sys
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    print(path)
    print(jax.config.jax_compilation_cache_dir)
    print(sorted(os.listdir(path)) != [])
""")


def test_compile_cache_env_dir_wins(tmp_path):
    cache = tmp_path / "env_cache"
    out = _run(["-c", _CACHE_PROBE], cwd=tmp_path,
               env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache),
                          "PYTHONPATH": os.path.join(REPO, "src")})
    assert out.returncode == 0, out.stderr
    path, configured, written = out.stdout.split()
    assert path == configured == str(cache)
    assert written == "True"


def test_compile_cache_default_is_fixed_repo_path():
    from repro.launch import compile_cache
    assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
