"""The serving entry point (``launch/serve.py``), its compile-cache
helper, the one-chip InternVL3-14B cut and the stacked parameter init."""
import dataclasses
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.configs.internvl3_14b_paper import REDUCED_1CHIP
from repro.launch import serve
from repro.models import transformer as tfm


def test_serve_reports_every_window():
    rep = serve.serve("internvl3-14b-smoke", "codecflow", videos=2,
                      frames=12, window=8, stride=4, streams=2)
    assert rep["windows_total"] == 2 * 2
    assert rep["logits_finite"]
    assert rep["kernel_fallbacks"] == 0
    assert rep["max_window_kernel_fallbacks"] == 0
    dev = jax.devices()[0]
    assert rep["device"] == {"platform": dev.platform,
                             "kind": dev.device_kind, "count": 1}


@pytest.fixture
def cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path,
                                             cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert serve.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout_root(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = Path(__file__).resolve().parents[1]
    assert serve.enable_compile_cache() == str(root / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_one_chip_internvl3_cuts_depth_only():
    full, cut = get_config("internvl3-14b"), get_config("internvl3-14b-1chip")
    changed = {f.name for f in dataclasses.fields(full)
               if getattr(full, f.name) != getattr(cut, f.name)}
    assert changed == {"name", "n_layers", "source"}
    assert set(REDUCED_1CHIP) == {"n_layers"}
    assert cut.vit == full.vit


@pytest.mark.parametrize("arch", ["internvl3-14b", "jamba-v0.1-52b",
                                  "whisper-large-v3"])
def test_stacked_init_matches_abstract_shapes(arch):
    cfg = get_config(arch + "-smoke")
    params, specs = tfm.init_params(cfg, jax.random.key(0))
    abstract, abstract_specs = tfm.init_params(cfg, jax.random.key(0),
                                               abstract=True)
    assert specs == abstract_specs
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), abstract)
    assert got == want
    for blocks in params["blocks"]:
        for leaf in jax.tree_util.tree_leaves(blocks):
            assert leaf.shape[0] == cfg.repeats
