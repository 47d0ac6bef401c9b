"""Where the entry points keep JAX's persistent compile cache, and which
compiles they keep there. Each case runs in a fresh process: the cache
settings are process-global."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
CODE = ("import json, jax\n"
        "from repro.runtime.compile_cache import enable_compile_cache\n"
        "path = enable_compile_cache()\n"
        "print(json.dumps([path, jax.config.jax_compilation_cache_dir,\n"
        "    jax.config.jax_persistent_cache_min_compile_time_secs]))\n")


def _run(**env):
    base = {k: v for k, v in os.environ.items()
            if not k.startswith("JAX_COMPILATION_CACHE")}
    p = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, timeout=120, check=True,
                       env=dict(base, PYTHONPATH=str(ROOT / "src"), **env))
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("env,want_dir,want_min", [
    ({}, str(ROOT / ".jax_cache"), 0.0),
    ({"JAX_COMPILATION_CACHE_DIR": "/cache/from/env"}, "/cache/from/env",
     0.0),
    # a size-limited cache scans its directory on every write: only
    # compiles over JAX's default second are kept
    ({"JAX_COMPILATION_CACHE_DIR": "/cache/from/env",
      "JAX_COMPILATION_CACHE_MAX_SIZE": str(1 << 27)}, "/cache/from/env",
     1.0),
])
def test_cache_dir_and_min_compile_time(env, want_dir, want_min):
    path, config_dir, min_secs = _run(**env)
    assert path == config_dir == want_dir
    assert min_secs == want_min
