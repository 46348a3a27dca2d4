"""repro.compile_cache: where the persistent compilation cache lands.

Each case runs in a fresh CPU-only interpreter, so the global JAX config
of the test process is never touched."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

_SCRIPT = textwrap.dedent("""
    import json, jax, jax.numpy as jnp
    from repro import compile_cache
    path = compile_cache.configure()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
    print(json.dumps({
        "path": path,
        "config": jax.config.jax_compilation_cache_dir,
        "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
        "default": str(compile_cache.DEFAULT_DIR)}))
""")


def _run(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_directory(tmp_path, env_set):
    if env_set:
        got = _run(tmp_path)
        assert got["path"] == got["config"] == str(tmp_path)
        assert any(tmp_path.iterdir())      # the compile was cached there
    else:
        got = _run(None)
        assert got["path"] == got["config"] == got["default"]
        assert Path(got["default"]) == REPO / ".jax_cache"
        ignored = (REPO / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored
    assert got["min_secs"] == 0
