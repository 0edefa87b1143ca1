"""Serving launch helpers: the shared build path and the compile cache."""

import os

import jax
import numpy as np
import pytest

from repro.launch import compile_cache
from repro.launch.serve import build_engine, serving_config


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test changes it."""
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_leaves_env_dir_to_jax(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set here


def test_compile_cache_defaults_to_fixed_checkout_path(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path  # same path every call


def test_build_engine_serves_the_cli_config():
    """The CLI's CPU default through the shared build path: quantized leaves,
    fused projections, and a greedy generation of the requested length."""
    cfg = serving_config("llama3.2-3b")
    assert cfg.d_model == 256 and cfg.param_dtype == "float32"
    engine = build_engine(cfg, q=3, g=128, max_seq=32)
    attn = engine.params["stages"][0]["b0"]["attn"]
    assert "wqkv" in attn and attn["wqkv"].fmt == "bcq"
    out = engine.generate(np.arange(8, dtype=np.int32)[None], 4)
    assert out.tokens.shape == (1, 12)
