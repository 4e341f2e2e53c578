"""The benchmark's copy of the FLOP ledger against the program's, and the
lower bounds of the per-window work."""
import json

import numpy as np
import pytest

from bench_helpers import DATA, ROOT
from bench.lib import blocks, flops, harness
from repro.serving import flops as ledger

CONFIGS = [DATA / "configs" / "tiny.json",
           ROOT / "bench" / "configs" / "internvl3-2b.json"]


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_copy_matches_program_ledger(path):
    conf = json.loads(path.read_text())
    cfg, v = harness.program_cfg(conf, blocks.load(conf["lm"]))
    for n in (4, 64, 1024):
        assert flops.vit_flops(conf["vit"], n) == ledger.vit_flops(v, n)
    for n_q, n_kv in ((1, 100), (50, 300), (300, 300)):
        assert flops.prefill_flops(conf["lm"], n_q, n_kv) == pytest.approx(
            ledger.prefill_flops(cfg, n_q, n_kv))
    assert flops.decode_flops(conf["lm"], 777) == pytest.approx(
        ledger.decode_flops(cfg, 777))


def _geo_2b():
    conf = json.loads((ROOT / "bench" / "configs" / "internvl3-2b.json").read_text())
    codec = json.loads((ROOT / "bench" / "traffic" / "cctv-sessions.json").read_text())["codec"]
    return conf, harness.geometry(conf, codec, blocks.load(conf["lm"]))


def test_fresh_window_with_every_slot_valid_is_the_ledger_less_padding():
    conf, geo = _geo_2b()
    lm, v = conf["lm"], conf["vit"]
    T = geo["total"]
    w = {"tokens_refreshed": T, "valid": np.ones(T, bool),
         "kept": [1024] * 16}
    k = flops.window_work(w, geo, lm, v)
    # causal prefill over all T positions, the head at one position
    assert k["lm"] == pytest.approx(
        flops.prefill_flops(lm, T, T, head_positions=0)
        + lm["n_layers"] * flops.attn_flops(lm, T / 2.0))
    assert k["vit"] > 16 * flops.vit_flops(v, 0)


def test_padding_and_incremental_windows_need_less():
    conf, geo = _geo_2b()
    lm, v = conf["lm"], conf["vit"]
    T = geo["total"]
    rng = np.random.default_rng(1)
    valid = rng.random(T) < 0.6
    valid[-8:] = True
    full = {"tokens_refreshed": T, "valid": np.ones(T, bool), "kept": [1024] * 16}
    padded = {"tokens_refreshed": T, "valid": valid, "kept": [1024] * 4 + [200] * 12}
    inc = {"tokens_refreshed": len(geo["refresh"]), "valid": valid,
           "kept": [1024, 200, 200, 200]}
    f = [flops.window_flops(w, geo, lm, v) for w in (full, padded, inc)]
    assert f[0] > f[1] > f[2] > 0
    k = flops.window_work(inc, geo, lm, v)
    assert k["packed_attn_flops"] == pytest.approx(
        v["n_layers"] * 4.0 * 3 * 200 ** 2 * v["d_model"])
