"""chip_smoke.py off the chip, and the compile-cache helper every entry point
calls.  Both run in subprocesses: the script must fail where JAX finds no
TPU, and the helper changes the process's environment and JAX config."""

import json
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from _subproc import REPO_ROOT, run_env


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_a_tpu(where, tmp_path):
    """Under JAX_PLATFORMS=cpu, and in a directory holding the script and
    nothing else of the repository, it exits non-zero and prints no result."""
    script = Path(REPO_ROOT) / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = run_env()
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=300, env=env, cwd=script.parent,
    )
    assert proc.returncode != 0, proc.stdout
    assert "FAIL" in proc.stderr
    assert '"ok"' not in proc.stdout


_CACHE_SCRIPT = textwrap.dedent("""
    import json
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    import jax.numpy as jnp

    path = enable_compile_cache()
    out = {"path": path, "config": jax.config.jax_compilation_cache_dir}
    if {compile}:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(8)).block_until_ready()
    print(json.dumps(out))
""")


def _run_cache_script(env, compile_):
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT.replace("{compile}", str(compile_))],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_honours_the_environment(tmp_path):
    env = run_env()
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = _run_cache_script(env, compile_=True)
    assert out["path"] == out["config"] == str(tmp_path / "cache")
    assert any((tmp_path / "cache").iterdir())  # entries land there


def test_compile_cache_defaults_to_a_fixed_ignored_path():
    env = run_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = _run_cache_script(env, compile_=False)
    want = str(Path(REPO_ROOT) / ".jax_cache")
    assert out["path"] == out["config"] == want
    ignored = (Path(REPO_ROOT) / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
