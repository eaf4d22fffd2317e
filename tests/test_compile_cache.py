"""The entry points' persistent compilation cache directory."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.utils import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_env_var_is_honoured(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cc"))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    assert compile_cache.compilation_cache_dir() == tmp_path / "cc"
    assert compile_cache.enable_compilation_cache() == tmp_path / "cc"
    assert calls == []          # JAX reads the variable itself


def test_default_is_checkout_root(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    path = compile_cache.enable_compilation_cache()
    assert path == ROOT / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))]


@pytest.mark.parametrize("where", ["root", "elsewhere"])
def test_same_path_from_any_working_directory(tmp_path, where):
    cwd = ROOT if where == "root" else tmp_path
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop(compile_cache.ENV_VAR, None)
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.utils.compile_cache import compilation_cache_dir; "
         "print(compilation_cache_dir())"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=60,
        check=True)
    assert Path(out.stdout.strip()) == ROOT / ".jax_cache"
