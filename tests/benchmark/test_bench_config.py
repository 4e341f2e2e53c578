"""Configuration files: by registry name and inline, the numbers as run,
and the weight layout the benchmark draws against the program's."""
import json

import jax
import pytest

from bench_helpers import DATA, ROOT
from bench.lib import blocks, harness, weights

CONFIGS = ROOT / "bench" / "configs"


def _conf(name, d=CONFIGS):
    return json.loads((d / f"{name}.json").read_text())


def _registry_form():
    """The 14B file in registry form: the program's entry by name, with
    the numbers that entry runs (its RoPE base, norm eps and bias)."""
    conf = _conf("internvl3-14b-1chip")
    conf["arch"] = "internvl3-14b-1chip"
    conf["lm"].update(qkv_bias=False, rope_theta=10000.0, norm_eps=1e-05)
    return conf


def test_registry_config_matches_its_file():
    from repro.configs import get_config

    conf = _registry_form()
    cfg, v = harness.program_cfg(conf, blocks.load(conf["lm"]))
    assert cfg == get_config("internvl3-14b-1chip")
    assert v == cfg.vit and cfg.n_layers == 16 and cfg.d_model == 5120


def test_registry_config_that_drifts_is_refused():
    conf = _registry_form()
    conf["lm"]["n_layers"] = 48
    with pytest.raises(ValueError, match="differs"):
        harness.program_cfg(conf, blocks.load(conf["lm"]))


def test_inline_config():
    conf = _conf("internvl3-2b")
    cfg, v = harness.program_cfg(conf, blocks.load(conf["lm"]))
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_head,
            cfg.d_ff, cfg.vocab) == (28, 1536, 12, 2, 128, 8960, 151674)
    assert cfg.qkv_bias and cfg.tied_embeddings and cfg.family == "vlm"
    assert v == cfg.vit and (v.n_layers, v.d_model, v.image) == (24, 1024, 448)


def test_14b_config_states_the_published_lm():
    conf = _conf("internvl3-14b-1chip")
    cfg, _ = harness.program_cfg(conf, blocks.load(conf["lm"]))
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_ff,
            cfg.vocab) == (16, 5120, 40, 8, 13824, 151674)
    assert cfg.qkv_bias and not cfg.tied_embeddings
    assert (cfg.rope_theta, cfg.norm_eps) == (1e6, 1e-6)


@pytest.mark.parametrize("name,d", [("internvl3-14b-1chip", CONFIGS),
                                    ("internvl3-2b", CONFIGS),
                                    ("tiny", DATA / "configs")])
def test_weight_layout_is_the_programs(name, d):
    from repro.launch import serve

    conf = _conf(name, d)
    block = blocks.load(conf["lm"])
    cfg, v = harness.program_cfg(conf, block)
    weights.check_layout(weights.shapes(conf["lm"], conf["vit"], block),
                         jax.eval_shape(lambda: serve.init_weights(cfg, v, 0)))


def test_layout_mismatch_is_refused():
    conf = _conf("tiny", DATA / "configs")
    block = blocks.load(conf["lm"])
    mine = weights.shapes(conf["lm"], conf["vit"], block)
    conf["lm"]["d_ff"] = 96
    with pytest.raises(ValueError, match="differs"):
        weights.check_layout(mine, weights.shapes(conf["lm"], conf["vit"],
                                                  block))


def test_weights_are_drawn_from_the_seed():
    conf = _conf("tiny", DATA / "configs")
    block = blocks.load(conf["lm"])
    a = weights.make_weights(conf["lm"], conf["vit"], 2**33 + 1, block)
    b = weights.make_weights(conf["lm"], conf["vit"], 2**33 + 1, block)
    c = weights.make_weights(conf["lm"], conf["vit"], 1, block)
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all((x == y).all() for x, y in zip(la, lb))
    assert not all((x == y).all() for x, y in zip(la, lc))


def test_every_cell_finds_its_files():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        cell = harness.load_cell(w["name"], bm)
        assert cell.streams >= 1 and cell.chips == 1
        names = {m["name"] for m in cell.end_to_end}
        assert {"windows_per_s", "setup_s"} <= names
        for m in cell.per_layer:
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
