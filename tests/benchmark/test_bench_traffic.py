"""Traffic generator: deterministic per seed; the pan mix moves far more
of the frame than fixed CCTV; the benchmark's codec and token selection
agree exactly with the program's on generated frames."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import DATA, ROOT
from bench.lib import reference, traffic

MIXES = ROOT / "bench" / "traffic"


def _short(name, frames=8, size=None):
    mix = traffic.load_mix(name, MIXES)
    kw = {"n_frames": frames}
    if size:
        kw["size"] = size
    return mix, dataclasses.replace(mix.scene, **kw)


def test_clip_is_deterministic_per_seed():
    _, spec = _short("cctv-sessions", frames=6, size=112)
    a = traffic.generate_clip(spec, 2**31 + 7)
    b = traffic.generate_clip(spec, 2**31 + 7)
    c = traffic.generate_clip(spec, 2**31 + 8)
    assert a.dtype == np.uint8 and a.shape == (6, 112, 112)
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_pool_and_large_seeds():
    mix = traffic.load_mix("cctv-sessions", MIXES)
    assert traffic.clip_seed(2**40 + 3, 1) == traffic.clip_seed(2**40 + 3, 1)
    assert traffic.clip_seed(2**40 + 3, 1) != traffic.clip_seed(3, 1)
    assert mix.frames_for(mix.segment_windows) == 92
    tiny = traffic.load_mix("tiny-sessions", DATA / "traffic")
    a, b = traffic.build_pool(tiny, 2), traffic.build_pool(tiny, 2)
    assert len(a) == 2 and a[0].shape == (28, 112, 112)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], a[1])       # every camera its own scene


def test_schedule_spreads_first_segments():
    mix = traffic.load_mix("cctv-sessions", MIXES)
    sch = traffic.Schedule(mix, 8)
    first = sch.first()
    starts = [s.first_window for s in first]
    assert starts == [round(c * 20 / 8) for c in range(8)]
    assert all(s.first_window + s.windows == 20 for s in first)
    nxt = sch.next(3)
    assert (nxt.clip, nxt.first_window, nxt.windows) == (3, 0, 20)
    pool = [np.zeros((92, 4, 4), np.uint8) for _ in range(8)]
    assert len(sch.frames(first[5], pool)) == mix.frames_for(first[5].windows)


def test_clip_schedule_cycles_the_pool():
    mix = traffic.load_mix("event-clips", MIXES)
    sch = traffic.Schedule(mix, 3)
    assert [s.clip for s in sch.first()] == [0, 1, 2]
    assert [sch.next(1).clip for _ in range(3)] == [4, 1, 4]


def _dynamic_share(name):
    mix, spec = _short(name)
    frames = traffic.generate_clip(spec, 11)
    c = mix.codec
    _, mv = reference.codec(jnp.asarray(frames), c["gop"], c["block"],
                            c["search_radius"])
    _, valid = reference.select(np.asarray(mv), c["gop"], 32, 2, 128,
                                c["mv_threshold"])
    p = np.arange(len(frames)) % c["gop"] != 0
    return valid[p].sum() / (p.sum() * 256)


def test_pan_moves_far_more_than_cctv():
    cctv, pan = _dynamic_share("cctv-sessions"), _dynamic_share("pan-sessions")
    assert cctv < 0.25
    assert pan >= 0.45          # every P-frame at the 128-of-256 keep cap
    assert pan > 3 * cctv


@pytest.mark.parametrize("name", ["cctv-sessions", "pan-sessions"])
def test_reference_codec_matches_program(name):
    from repro.codec import encode_stream
    from repro.codec.decoder import decode_stream
    from repro.configs import CodecCfg, ViTCfg
    from repro.core import motion_mask, select_tokens

    mix, spec = _short(name, frames=8, size=112)
    frames = traffic.generate_clip(spec, 5)
    c = mix.codec
    codec = CodecCfg(**c)
    bs, meta = encode_stream(jnp.asarray(frames, jnp.float32), codec)
    recon, mv = reference.codec(jnp.asarray(frames), c["gop"], c["block"],
                                c["search_radius"])
    assert np.array_equal(np.asarray(mv), np.asarray(meta.mv))
    assert np.array_equal(np.asarray(recon),
                          np.asarray(decode_stream(bs, c["block"])))
    v = ViTCfg(n_layers=1, d_model=8, n_heads=1, d_ff=8, patch=14,
               image=112, group=2)
    dyn, score = motion_mask(meta, codec, v.patches_per_side)
    want = select_tokens(dyn, score, v, 8)
    idx, valid = reference.select(np.asarray(mv), c["gop"], 8, 2, 8,
                                  c["mv_threshold"])
    p = np.arange(8) % c["gop"] != 0
    assert np.array_equal(idx[p], np.asarray(want.group_idx)[p])
    assert np.array_equal(valid[p], np.asarray(want.group_valid)[p])
