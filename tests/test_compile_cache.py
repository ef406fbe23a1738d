"""Where ``use_compile_cache`` puts JAX's persistent compilation cache.

Each case runs in a fresh CPU-only process (the cache directory is fixed
for a process at its first compile) and compiles one small program with
the cache's size and time thresholds at zero, so every entry is written.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]

_CHILD = """
import sys
from pathlib import Path
import jax, jax.numpy as jnp
from repro import compile_cache
compile_cache.CHECKOUT = Path(sys.argv[1])   # a stand-in checkout
print(compile_cache.use_compile_cache())
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [False, True], ids=["checkout", "env"])
def test_entries_land_only_in_the_chosen_directory(tmp_path, env_dir):
    assert compile_cache.CHECKOUT == REPO
    root, chosen = tmp_path / "checkout", tmp_path / "env-cache"
    env = dict(os.environ)
    env.update(
        PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
    )
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(chosen)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, str(root)], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = chosen if env_dir else root / ".jax_cache"
    assert proc.stdout.strip() == str(want)
    written = [p for p in tmp_path.rglob("*") if p.is_file()]
    assert written, "no cache entry was written"
    assert all(want in p.parents for p in written), written
