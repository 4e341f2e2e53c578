"""The dense block, moved out of the harness into ``bench/lm/gqa.py``,
draws the same weights, computes the same reference logits and counts
the same work, bit for bit, as the harness did before the move.

The golden values were computed on the CPU with the benchmark as of
commit 919c075, before the LM block was found by name."""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench_helpers import DATA, ROOT
from bench.lib import blocks, flops, harness, traffic, weights
from bench.lib.reference import Reference

SEED = 2**33 + 5
WEIGHTS = "d3d6a76157898629e1913e5ac174852aead2e56646f1997754781d3a8ccebe69"
# the tiny cell's longest stream (camera 0, clip 0, 4 windows)
LOGITS = {
    "f32": "a445b2323d2b5ca005f2e879d22a0d962570d92deff8966318b99b9794c08585",
    "bf16": "635c4ac6c23137dad94051c87435b8aee973111986589861ae29598e2c84e57d",
}
# flops.window_work for internvl3-14b-1chip under cctv-sessions' codec
WORK = {
    "fresh": {
        "vit": 15021782335488.0, "lm": 23699926548480.0,
        "head": 1553141760.0, "decode": 11202990080.0,
        "refresh_attn_flops": 1081725747200.0,
        "refresh_attn_bytes": 1178533888.0,
        "packed_attn_flops": 0.0, "packed_attn_bytes": 0.0},
    "padded": {
        "vit": 5761495990272.0, "lm": 13646049443840.0,
        "head": 1553141760.0, "decode": 10855321600.0,
        "refresh_attn_flops": 372829716480.0,
        "refresh_attn_bytes": 691798016.0,
        "packed_attn_flops": 47185920000.0, "packed_attn_bytes": 471859200.0},
    "incremental": {
        "vit": 1440373997568.0, "lm": 7559462912000.0,
        "head": 1553141760.0, "decode": 10855321600.0,
        "refresh_attn_flops": 231669104640.0,
        "refresh_attn_bytes": 470614016.0,
        "packed_attn_flops": 11796480000.0, "packed_attn_bytes": 117964800.0},
}


@pytest.fixture(scope="module")
def tiny():
    conf = json.loads((DATA / "configs" / "tiny.json").read_text())
    return conf, weights.make_weights(conf["lm"], conf["vit"], SEED,
                                      blocks.load(conf["lm"]))


def test_weights_are_the_parents(tiny):
    _, (params, vparams) = tiny
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path((params, vparams))[0]:
        a = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == WEIGHTS


@pytest.mark.parametrize("precision", sorted(LOGITS))
def test_reference_logits_are_the_parents(tiny, precision):
    conf, (params, vparams) = tiny
    mix = traffic.load_mix("tiny-sessions", DATA / "traffic")
    F = 3
    pool = traffic.build_pool(mix, F)
    sch = traffic.Schedule(mix, F)
    seg = max(sch.first(), key=lambda s: s.windows)
    ref = Reference(conf["lm"], conf["vit"], mix.codec, params, vparams,
                    blocks.load(conf["lm"]), precision=precision)
    logits = ref.serve(sch.frames(seg, pool), seg.windows)
    assert len(logits) == seg.windows == 4
    h = hashlib.sha256()
    for x in logits:
        h.update(np.asarray(x, np.float32).tobytes())
    assert h.hexdigest() == LOGITS[precision]


def _records(geo):
    T = geo["total"]
    rng = np.random.default_rng(15)
    valid = rng.random(T) < 0.6
    valid[-8:] = True
    return {
        "fresh": {"tokens_refreshed": T, "valid": np.ones(T, bool),
                  "kept": [1024] * 16},
        "padded": {"tokens_refreshed": T, "valid": valid,
                   "kept": [1024] * 4 + [200] * 12},
        "incremental": {"tokens_refreshed": len(geo["refresh"]),
                        "valid": valid, "kept": [1024, 200, 200, 200]},
    }


@pytest.mark.parametrize("kind", sorted(WORK))
def test_window_work_is_the_parents(kind):
    conf = json.loads(
        (ROOT / "bench" / "configs" / "internvl3-14b-1chip.json").read_text())
    codec = json.loads(
        (ROOT / "bench" / "traffic" / "cctv-sessions.json").read_text())["codec"]
    geo = harness.geometry(conf, codec, blocks.load(conf["lm"]))
    k = flops.window_work(_records(geo)[kind], geo, conf["lm"], conf["vit"])
    assert list(k) == list(WORK[kind])
    assert k == WORK[kind]


def test_dense_block_sums_the_parents_kernels():
    cell = harness.load_cell("ivl3-14b.cctv-sessions")
    assert harness.kernels(cell.block) == ("flash_refresh_paged",
                                           "flash_packed")
