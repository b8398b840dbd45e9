"""Where the entry points put JAX's persistent compilation cache, and
that ``chip_smoke.py`` refuses to run anywhere but on a TPU."""

import importlib.util
import json
import os

import jax
import pytest

from repro.launch import cache

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_placed_from_outside_sets_nothing(monkeypatch, tmp_path,
                                                cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(os.path.realpath(ROOT), ".jax_cache")
    assert cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_chip_smoke_refuses_cpu(capsys):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert out[0].startswith("device: platform=cpu")
    for line in out:
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
