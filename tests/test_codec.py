"""Codec substrate: roundtrip exactness, metadata fidelity, single-pass
window serving, gather-free motion compensation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # optional dev dep

from repro.codec import (
    NaiveDecoder, StreamDecoder, decode_stream, encode_stream, estimate_bits,
)
from repro.codec import decoder, encoder
from repro.codec.metadata import Bitstream
from repro.configs.base import CodecCfg
from repro.data.video import VideoSpec, generate_video, motion_level_spec
from repro.kernels import ops

CFG = CodecCfg(gop=8, block=16, search_radius=4, window_frames=16, stride_frames=8)


@pytest.fixture(scope="module")
def stream():
    frames, labels = generate_video(
        VideoSpec(n_frames=32, height=64, width=64, anomaly=True, seed=7)
    )
    bs, md = encode_stream(jnp.asarray(frames), CFG)
    return frames, labels, bs, md


def test_roundtrip_bounded_by_quantizer(stream):
    frames, _, bs, _ = stream
    rec = decode_stream(bs, CFG.block)
    assert float(jnp.max(jnp.abs(rec - frames))) <= 2.0 + 1e-4  # quant/2


def test_gop_structure(stream):
    _, _, bs, _ = stream
    ft = np.asarray(bs.frame_types)
    assert (ft[::8] == 0).all()
    assert (np.delete(ft, np.arange(0, 32, 8)) == 1).all()


def test_metadata_shapes(stream):
    frames, _, _, md = stream
    T, H, W = frames.shape
    assert md.mv.shape == (T, H // 16, W // 16, 2)
    assert md.residual.shape == (T, H // 16, W // 16)
    assert float(md.mv_magnitude.max()) <= np.hypot(4, 4) + 1e-6


def test_motion_level_monotonicity():
    """Property (paper Fig. 14 premise): higher-motion content produces
    larger codec motion signals."""
    mags = []
    for level in ["low", "medium", "high"]:
        f, _ = generate_video(motion_level_spec(level, seed=3, n_frames=24,
                                                height=64, width=64))
        _, md = encode_stream(jnp.asarray(f), CFG)
        mags.append(float(md.mv_magnitude[np.asarray(md.frame_types) == 1].mean()))
    assert mags[0] < mags[1] < mags[2], mags


def test_single_pass_decode_counts(stream):
    frames, _, bs, md = stream
    sd = StreamDecoder(CFG)
    sd.ingest(bs, md)
    for k in range(sd.n_windows()):
        sd.window(k)
    assert (sd.decode_count == 1).all()           # decode-once (paper §3.2)
    nd = NaiveDecoder(CFG)
    nd.ingest(bs, md)
    for k in range(nd.n_windows() if hasattr(nd, "n_windows") else 3):
        nd.window(k)
    assert nd.decode_count.max() >= 2             # the redundancy removed


def test_shared_buffer_windows_match_naive(stream):
    _, _, bs, md = stream
    sd, nd = StreamDecoder(CFG), NaiveDecoder(CFG)
    sd.ingest(bs, md)
    nd.ingest(bs, md)
    w_s, _ = sd.window(1)
    w_n, _ = nd.window(1)
    np.testing.assert_allclose(w_s, w_n, atol=1e-5)


def test_compression_ratio(stream):
    _, _, bs, _ = stream
    bits = estimate_bits(bs)
    assert bits["compression_ratio"] > 2.0
    # inter coding beats all-intra (the transmission claim's mechanism)
    frames = decode_stream(bs, CFG.block)
    bs_intra, _ = encode_stream(frames, CodecCfg(gop=1, block=16, search_radius=4))
    intra = estimate_bits(bs_intra)
    assert bits["total_bits"] < intra["total_bits"]


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000))
def test_encode_decode_property(seed):
    """decode(encode(x)) error is quantizer-bounded for arbitrary
    synthetic content."""
    f, _ = generate_video(VideoSpec(n_frames=12, height=48, width=48, seed=seed,
                                    n_objects=3, speed=3.0))
    cfg = CodecCfg(gop=4, block=8, search_radius=2)
    bs, _ = encode_stream(jnp.asarray(f), cfg, quant_step=2.0)
    rec = decode_stream(bs, cfg.block)
    assert float(jnp.max(jnp.abs(rec - f))) <= 1.0 + 1e-4


# ----------------------------------------------------------------------
# motion compensation: static shifts of the padded reference == gather
# ----------------------------------------------------------------------
def _mc_fields(hb: int, wb: int, r: int, rng) -> list:
    """Every uniform (dy, dx) in [-r, r]^2 (so every corner and edge block
    reads every displacement), blocks pointing out of the frame on all
    sides, and random per-block fields."""
    fields = [np.full((hb, wb, 2), (dy, dx), np.int32)
              for dy in range(-r, r + 1) for dx in range(-r, r + 1)]
    out = np.zeros((hb, wb, 2), np.int32)
    out[..., 0] = np.where(np.arange(hb)[:, None] < hb / 2, -r, r)
    out[..., 1] = np.where(np.arange(wb)[None, :] < wb / 2, -r, r)
    fields += [out, -out]
    fields += [rng.integers(-r, r + 1, (hb, wb, 2)).astype(np.int32)
               for _ in range(8)]
    return fields


@pytest.mark.parametrize("radius,block,shape", [
    (1, 8, (40, 64)), (1, 16, (48, 32)),
    (4, 8, (48, 32)), (4, 16, (48, 80)),
    (7, 8, (24, 40)), (7, 16, (64, 32)),
])
def test_motion_compensate_shift_equals_gather(radius, block, shape):
    rng = np.random.default_rng(radius * 100 + block)
    H, W = shape
    # distinct pixel values: a wrong source pixel cannot match by chance
    ref_frame = jnp.asarray(rng.permutation(H * W).reshape(H, W).astype(np.float32))
    shift = jax.jit(lambda f, mv: encoder.motion_compensate(f, mv, block, radius))
    gather = jax.jit(lambda f, mv: encoder.motion_compensate(f, mv, block))
    for mv in _mc_fields(H // block, W // block, radius, rng):
        got = np.asarray(shift(ref_frame, jnp.asarray(mv)))
        want = np.asarray(gather(ref_frame, jnp.asarray(mv)))
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def _gather_mc(ref_frame, mv, block, radius=None):
    return encoder._gather_compensate(ref_frame, mv, block)


def test_encode_decode_bit_identical_to_gather(monkeypatch):
    """The served codec (static shifts) against the same codec built on
    the per-pixel gather: same bitstream, metadata and decoded frames, bit
    for bit, whether the bitstream carries the radius or not."""
    # a textured scene panned by up to the radius per frame, so every
    # block, edge and corner blocks included, moves by up to (4, 4)
    rng = np.random.default_rng(5)
    scene = rng.uniform(0, 255, (112 + 48, 112 + 48)).astype(np.float32)
    offs = np.cumsum([(0, 0)] + [(4, -3), (-2, 4), (3, 1)] * 4, axis=0)[:12] + 24
    frames = np.stack([scene[y:y + 112, x:x + 112] for y, x in offs])
    cfg = CodecCfg(gop=4, block=16, search_radius=4)
    x = jnp.asarray(frames)
    bs, md = encode_stream(x, cfg)
    assert bs.radius == cfg.search_radius
    rec_r = decode_stream(bs, cfg.block)
    rec = decode_stream(bs._replace(radius=None), cfg.block)

    monkeypatch.setattr(encoder, "motion_compensate", _gather_mc)
    monkeypatch.setattr(decoder, "motion_compensate", _gather_mc)
    enc_g = jax.jit(encode_stream.__wrapped__, static_argnames=("cfg", "quant_step"))
    dec_g = jax.jit(decode_stream.__wrapped__, static_argnames=("block",))
    bs_g, md_g = enc_g(x, cfg)
    rec_g = dec_g(bs_g, cfg.block)

    mv = np.asarray(md.mv)
    assert np.abs(mv).max() == cfg.search_radius          # vectors reach the bound
    assert np.abs(mv[:, [0, -1]]).max() > 0                # edge blocks move too
    assert bs_g.radius == bs.radius
    for a, b in zip([*bs[:4], *md, rec_r, rec], [*bs_g[:4], *md_g, rec_g, rec_g]):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


def _stream_shapes(T=92, H=448, W=448, block=16, radius=4):
    f = jax.ShapeDtypeStruct((T, H, W), jnp.float32)
    i = jax.ShapeDtypeStruct((T,), jnp.int32)
    mv = jax.ShapeDtypeStruct((T, H // block, W // block, 2), jnp.int32)
    return f, Bitstream(i, f, mv, f, radius)


@pytest.mark.parametrize("fn", ["encode_stream", "decode_stream"])
def test_codec_hlo_holds_no_gather(fn):
    """One served segment (92 frames, 448 px, radius 4) compiles to a
    program with no gather.  The search runs as the Pallas kernel the chip
    runs (interpret mode); its jnp oracle gathers and is not the served
    path.  A bitstream without the radius is decoded by the gather: the
    check sees one where one is."""
    frames, bs = _stream_shapes()
    cfg = CodecCfg(gop=4, block=16, search_radius=4)
    jax.clear_caches()  # the trace cache does not key on the kernel mode
    with ops.kernel_mode("interpret"):
        if fn == "encode_stream":
            hlo = encode_stream.lower(frames, cfg).compile().as_text()
        else:
            hlo = decode_stream.lower(bs, 16).compile().as_text()
            unbounded = bs._replace(radius=None)
            assert "gather(" in decode_stream.lower(unbounded, 16).compile().as_text()
    jax.clear_caches()
    assert "gather(" not in hlo
