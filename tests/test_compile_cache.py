"""Where the entry points put JAX's persistent compilation cache.

Each case runs in a child process: the cache directory is process-global
JAX state and is fixed once the first compile initializes the cache.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

CHILD = """
    import os, sys
    from pathlib import Path
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache as cc
    assert cc.DEFAULT_DIR == Path({root!r}) / ".jax_cache", cc.DEFAULT_DIR
    if {patch_default!r}:
        cc.DEFAULT_DIR = Path({patch_default!r})
    used = cc.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == used, used
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
    print("USED", used)
"""


def _run(tmp_path, env_dir, patch_default):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    code = textwrap.dedent(CHILD.format(root=str(ROOT),
                                        patch_default=patch_default))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout.split("USED", 1)[1].strip()


@pytest.mark.parametrize("env_set", [True, False],
                         ids=["env-dir", "in-checkout-default"])
def test_cache_goes_where_the_env_says_else_fixed_dir(tmp_path, env_set):
    env_dir = tmp_path / "from_env"
    default = tmp_path / "default"
    used = _run(tmp_path, str(env_dir) if env_set else None, str(default))
    want, other = (env_dir, default) if env_set else (default, env_dir)
    assert used == str(want)
    assert want.is_dir() and any(want.iterdir())    # the compile landed
    assert not other.exists()                       # and nowhere else
