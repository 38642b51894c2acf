"""The persistent compilation cache of the entry points
(``repro.compile_cache``): JAX's own variable wins, otherwise one fixed,
git-ignored directory in the checkout.  Each case runs in a fresh
interpreter, as an entry point does."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import compile_cache

REPO = Path(compile_cache.__file__).resolve().parents[2]

_PROBE = """
import json, sys, jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
where = enable_compile_cache()
chosen = jax.config.jax_compilation_cache_dir
if len(sys.argv) > 1:
    jax.jit(lambda x: jnp.sin(x) @ x).lower(jnp.ones((64, 64))).compile()
print(json.dumps({"returned": where, "config": chosen}))
"""


def _probe(env_dir, compile_too: bool) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    argv = [sys.executable, "-c", _PROBE] + (["compile"] if compile_too
                                             else [])
    out = subprocess.run(argv, env=env, check=True, capture_output=True,
                         text=True, timeout=120)
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("env_var", [True, False])
def test_cache_directory(tmp_path, env_var):
    if env_var:
        got = _probe(tmp_path, compile_too=True)
        assert got["returned"] == got["config"] == str(tmp_path)
        assert any(tmp_path.iterdir()), "nothing landed in the cache"
    else:
        got = _probe(None, compile_too=False)
        assert got["returned"] == got["config"] == str(REPO / ".jax_cache")
        assert ".jax_cache/" in (REPO / ".gitignore").read_text()
