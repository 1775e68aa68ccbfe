"""The entry points' persistent compilation cache: an operator's
``JAX_COMPILATION_CACHE_DIR`` wins untouched; without it the cache sits at a
fixed, git-ignored directory inside the checkout."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
_PROBE = ("import jax; from repro.runtime.compile_cache import enable_compile_cache; "
          "print(enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)")


def _probe(env_dir: str | None) -> list[str]:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env, capture_output=True,
                         text=True, check=True, timeout=120)
    return out.stdout.split()


@pytest.mark.parametrize("env_dir", [None, "operator"])
def test_compile_cache_placement(tmp_path, env_dir):
    want = str(tmp_path / env_dir) if env_dir else str(ROOT / ".jax_cache")
    returned, configured = _probe(want if env_dir else None)
    assert returned == configured == want


def test_checkout_cache_dir_is_ignored_by_git():
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_import_sets_no_cache():
    import jax
    import repro  # noqa: F401
    import repro.runtime  # noqa: F401

    assert jax.config.jax_compilation_cache_dir in (None, os.environ.get(
        "JAX_COMPILATION_CACHE_DIR"))
