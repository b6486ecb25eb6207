"""Entry-point contracts: chip_smoke.py refuses to report without a TPU or
without the repo's sources, and the compile cache lives where it says."""
import importlib.util
import shutil
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _load(path: Path):
    spec = importlib.util.spec_from_file_location("chip_smoke_under_test",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_fails_without_tpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached: the script would run for real")
    assert _load(REPO / "chip_smoke.py").main([]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_chip_smoke_fails_without_repo_sources(tmp_path, capsys):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    assert _load(tmp_path / "chip_smoke.py").main([]) == 2
    assert capsys.readouterr().out == ""


def test_compile_cache_dir(monkeypatch):
    set_dirs = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: set_dirs.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert set_dirs == []            # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert set_dirs == [("jax_compilation_cache_dir", fixed)]
