"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis properties on the math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # optional dev dep

from repro.kernels import ops, ref
from repro.kernels.flash_packed import (
    build_pack_map, dense_pack_map, flash_packed_pallas,
)
from repro.kernels.flash_prefill import flash_prefill_pallas
from repro.kernels.flash_refresh import (
    build_block_map, dense_block_map, flash_refresh_pallas, span_block_map,
)
from repro.kernels.mv_sad import mv_sad_pallas
from repro.kernels.rope_shift import rope_shift_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


# ----------------------------------------------------------------------
# mv_sad
# ----------------------------------------------------------------------
@pytest.mark.parametrize("hw,block,radius", [
    ((64, 64), 16, 4), ((64, 96), 16, 2), ((32, 32), 8, 3), ((48, 80), 16, 4),
])
def test_mv_sad_matches_ref(hw, block, radius):
    k = jax.random.PRNGKey(hash((hw, block, radius)) % 2**31)
    cur = jax.random.uniform(k, hw) * 255
    prev = jnp.roll(cur, (1, -2), (0, 1)) + jax.random.normal(k, hw)
    mv_p, sad_p = mv_sad_pallas(cur, prev, block=block, radius=radius, interpret=True)
    mv_r, sad_r = ref.mv_sad_ref(cur, prev, block, radius)
    np.testing.assert_array_equal(np.asarray(mv_p), np.asarray(mv_r))
    np.testing.assert_allclose(np.asarray(sad_p), np.asarray(sad_r), rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(dy=st.integers(-3, 3), dx=st.integers(-3, 3))
def test_mv_sad_recovers_pure_translation(dy, dx):
    """Property: for prev = roll(cur, (dy, dx)), interior blocks must
    report exactly (dy, dx)."""
    k = jax.random.PRNGKey(abs(dy * 7 + dx) + 1)
    cur = jax.random.uniform(k, (64, 64)) * 255
    prev = jnp.roll(cur, (dy, dx), (0, 1))
    mv, sad = ref.mv_sad_ref(cur, prev, 16, 4)
    interior = np.asarray(mv)[1:-1, 1:-1]
    assert (interior[..., 0] == dy).all() and (interior[..., 1] == dx).all()
    assert float(np.asarray(sad)[1:-1, 1:-1].max()) == 0.0


# ----------------------------------------------------------------------
# rope_shift
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 128, 2, 32), (2, 256, 4, 64), (3, 64, 1, 128)])
def test_rope_shift_matches_ref(shape, dtype):
    k = jax.random.PRNGKey(0)
    kk = jax.random.normal(k, shape).astype(dtype)
    d = jax.random.randint(k, shape[:2], -500, 500)
    out_p = rope_shift_pallas(kk, d, seq_tile=min(64, shape[1]), interpret=True)
    out_r = ref.rope_shift_ref(kk, d)
    np.testing.assert_allclose(
        np.asarray(out_p, np.float32), np.asarray(out_r, np.float32),
        atol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
    )


@settings(max_examples=25, deadline=None)
@given(d1=st.integers(-1000, 1000), d2=st.integers(-1000, 1000))
def test_rope_shift_composes(d1, d2):
    """R(d1) . R(d2) == R(d1 + d2) — the property Eq. 5 relies on."""
    k = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 2, 16))
    da = jnp.full((1, 8), d1, jnp.int32)
    db = jnp.full((1, 8), d2, jnp.int32)
    a = ref.rope_shift_ref(ref.rope_shift_ref(k, da), db)
    b = ref.rope_shift_ref(k, da + db)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_rope_shift_zero_is_identity():
    k = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 2, 32))
    out = ref.rope_shift_ref(k, jnp.zeros((2, 16), jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(k), atol=1e-6)


# ----------------------------------------------------------------------
# flash_prefill
# ----------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sq,sk,h,hkv,d", [
    (128, 128, 4, 2, 32), (256, 256, 2, 2, 64), (128, 256, 8, 2, 32),
])
def test_flash_matches_ref(sq, sk, h, hkv, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, sq, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (2, sk, hkv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (2, sk, hkv, d)).astype(dtype)
    off = sk - sq
    o_p = flash_prefill_pallas(q, k, v, q_offset=off, interpret=True)
    o_r = ref.flash_prefill_ref(q, k, v, q_offset=off)
    np.testing.assert_allclose(
        np.asarray(o_p, np.float32), np.asarray(o_r, np.float32),
        atol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
    )


def test_flash_sliding_window():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 32))
    k = jax.random.normal(ks[1], (1, 256, 2, 32))
    v = jax.random.normal(ks[2], (1, 256, 2, 32))
    o_p = flash_prefill_pallas(q, k, v, window=64, interpret=True)
    o_r = ref.flash_prefill_ref(q, k, v, window=64)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r), atol=1e-5)


# ----------------------------------------------------------------------
# flash_refresh (block-sparse masked refresh attention)
# ----------------------------------------------------------------------
def _refresh_case(q_pos, sk, h, hkv, d, *, dtype=jnp.float32, seed=7,
                  kv_valid_p=None, batch=2):
    """Random (q, k, v, kv_valid) for a gathered-query attention case."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    sq = len(q_pos)
    q = jax.random.normal(ks[0], (batch, sq, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (batch, sk, hkv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (batch, sk, hkv, d)).astype(dtype)
    if kv_valid_p is None:
        kv_valid = jnp.ones((batch, sk), bool)
    else:
        kv_valid = jax.random.uniform(ks[3], (batch, sk)) > kv_valid_p
    return q, k, v, kv_valid


def _run_refresh_pallas(bm, q, k, v, kv_valid, window=None):
    """Pad queries per the map and run the kernel in interpret mode."""
    pad = bm.q_pos.shape[0] - q.shape[1]
    qq = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    out = flash_refresh_pallas(
        qq, k, v, jnp.asarray(bm.q_pos), kv_valid,
        jnp.asarray(bm.tile_ids), jnp.asarray(bm.tile_count),
        window=window, tq=bm.tq, tk=bm.tk, interpret=True,
    )
    return out[:, : q.shape[1]]


SCATTER_PATTERNS = {
    # new-window positions of: I-frame anchors only / anchors + the
    # new-stride-and-query tail (the codecflow refresh set) / one token
    "anchors_only": np.arange(0, 32, dtype=np.int32),
    "anchors_tail": np.concatenate([
        np.arange(0, 24, dtype=np.int32),
        np.arange(160, 256, dtype=np.int32),
    ]),
    "single_token": np.asarray([255], np.int32),
}


@pytest.mark.parametrize("pattern", sorted(SCATTER_PATTERNS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_refresh_matches_ref(pattern, dtype):
    q_pos = SCATTER_PATTERNS[pattern]
    sk = 256
    q, k, v, kv_valid = _refresh_case(q_pos, sk, 4, 2, 32, dtype=dtype)
    bm = build_block_map(q_pos, sk, tq=16, tk=32)
    o_p = _run_refresh_pallas(bm, q, k, v, kv_valid)
    qp = jnp.broadcast_to(jnp.asarray(q_pos)[None], (2, len(q_pos)))
    o_r = ref.flash_refresh_ref(q, k, v, qp, kv_valid)
    np.testing.assert_allclose(
        np.asarray(o_p, np.float32), np.asarray(o_r, np.float32),
        atol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
    )


@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (8, 1)])
def test_flash_refresh_gqa_groups(h, hkv):
    q_pos = SCATTER_PATTERNS["anchors_tail"]
    q, k, v, kv_valid = _refresh_case(q_pos, 256, h, hkv, 32, kv_valid_p=0.3)
    bm = build_block_map(q_pos, 256, tq=8, tk=64)
    o_p = _run_refresh_pallas(bm, q, k, v, kv_valid)
    qp = jnp.broadcast_to(jnp.asarray(q_pos)[None], (2, len(q_pos)))
    o_r = ref.flash_refresh_ref(q, k, v, qp, kv_valid)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r), atol=1e-5)


def test_flash_refresh_ragged_kv_valid():
    """Per-batch ragged validity: pruned-slot holes differ across the
    batch; dead queries (all keys invalid or masked) must be zeros."""
    q_pos = np.asarray([0, 3, 97, 130, 131], np.int32)
    q, k, v, _ = _refresh_case(q_pos, 192, 4, 2, 16)
    kv_valid = jnp.zeros((2, 192), bool)
    kv_valid = kv_valid.at[0, 50:120].set(True)      # row 0: mid-cache band
    kv_valid = kv_valid.at[1, ::3].set(True)         # row 1: every 3rd slot
    bm = build_block_map(q_pos, 192, tq=8, tk=32)
    o_p = _run_refresh_pallas(bm, q, k, v, kv_valid)
    qp = jnp.broadcast_to(jnp.asarray(q_pos)[None], (2, len(q_pos)))
    o_r = ref.flash_refresh_ref(q, k, v, qp, kv_valid)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r), atol=1e-5)
    # batch row 0, queries at 0 and 3: no valid key <= qpos -> zeros
    np.testing.assert_array_equal(np.asarray(o_p[0, :2]), 0.0)
    assert float(jnp.abs(o_p[1, :2]).sum()) > 0     # row 1 sees key 0


def test_flash_refresh_sliding_window():
    q_pos = np.concatenate([np.arange(0, 16), np.arange(200, 232)]).astype(np.int32)
    q, k, v, kv_valid = _refresh_case(q_pos, 256, 4, 2, 32, kv_valid_p=0.2)
    bm = build_block_map(q_pos, 256, tq=16, tk=32, window=64)
    assert bm.density < 1.0          # the window must prune tiles
    o_p = _run_refresh_pallas(bm, q, k, v, kv_valid, window=64)
    qp = jnp.broadcast_to(jnp.asarray(q_pos)[None], (2, len(q_pos)))
    o_r = ref.flash_refresh_ref(q, k, v, qp, kv_valid, window=64)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r), atol=1e-5)


def test_flash_refresh_ops_dispatch_uses_map():
    """ops.flash_refresh: interpret mode + matching map -> kernel path;
    mismatched map (different mask config) -> oracle; both agree."""
    q_pos = SCATTER_PATTERNS["anchors_tail"]
    q, k, v, kv_valid = _refresh_case(q_pos, 256, 4, 2, 32, kv_valid_p=0.4)
    qp = jnp.broadcast_to(jnp.asarray(q_pos)[None], (2, len(q_pos)))
    bm = build_block_map(q_pos, 256, tq=16, tk=32)
    with ops.kernel_mode("interpret"):
        o_kernel = ops.flash_refresh(q, k, v, qp, kv_valid, block_map=bm)
        # a map built for a different sliding window must be refused
        o_refused = ops.flash_refresh(
            q, k, v, qp, kv_valid, window=64,
            block_map=build_block_map(q_pos, 256, tq=16, tk=32),
        )
    o_ref = ref.flash_refresh_ref(q, k, v, qp, kv_valid)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_ref), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(o_refused),
        np.asarray(ref.flash_refresh_ref(q, k, v, qp, kv_valid, window=64)),
        atol=1e-6,
    )
    # concrete q_pos that disagrees with the map's positions must route
    # to the oracle (which honors the caller's q_pos), never the kernel
    qp_shift = qp + 1
    with ops.kernel_mode("interpret"):
        o_mismatch = ops.flash_refresh(q, k, v, qp_shift, kv_valid,
                                       block_map=bm)
    np.testing.assert_allclose(
        np.asarray(o_mismatch),
        np.asarray(ref.flash_refresh_ref(q, k, v, qp_shift, kv_valid)),
        atol=1e-6,
    )


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), tail=st.integers(1, 40),
       holes=st.integers(0, 2))
def test_flash_refresh_block_skip_preserves_output(seed, tail, holes):
    """Property: the sparse block map (skipped tiles) computes the SAME
    output as visiting every tile — skipping is purely elision of
    all-masked work, never an approximation."""
    rng = np.random.default_rng(seed)
    sk = 128
    anchors = np.sort(rng.choice(64, size=rng.integers(1, 12), replace=False))
    q_pos = np.unique(np.concatenate(
        [anchors, np.arange(sk - tail, sk)]
    )).astype(np.int32)
    q, k, v, _ = _refresh_case(q_pos, sk, 2, 2, 16, seed=seed)
    kv_valid = jnp.asarray(rng.random((2, sk)) > 0.25 * holes)
    sparse = build_block_map(q_pos, sk, tq=8, tk=16)
    dense = dense_block_map(q_pos, sk, tq=8, tk=16)
    assert dense.tile_count.min() == dense.n_kv_tiles
    o_s = _run_refresh_pallas(sparse, q, k, v, kv_valid)
    o_d = _run_refresh_pallas(dense, q, k, v, kv_valid)
    np.testing.assert_array_equal(np.asarray(o_s), np.asarray(o_d))


# ----------------------------------------------------------------------
# flash_packed (block-diagonal packed-ViT attention)
# ----------------------------------------------------------------------
def _seg_layout(runs, L):
    """(R, L) segment ids from per-row lists of (seg, length) runs."""
    seg = np.full((len(runs), L), -1, np.int32)
    for r, row in enumerate(runs):
        off = 0
        for s, n in row:
            seg[r, off: off + n] = s
            off += n
    return seg


PACK_LAYOUTS = {
    # one frame per row / several variable frames per row / ragged rows
    # with an all-padding row (bucket-quantum slack)
    "single": [[(0, 64)]],
    "multi": [[(0, 20), (1, 32), (2, 8)], [(3, 64)]],
    "ragged_pad": [[(0, 12), (1, 4)], [(2, 40)], []],
}


@pytest.mark.parametrize("layout", sorted(PACK_LAYOUTS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_packed_matches_ref(layout, dtype):
    seg = _seg_layout(PACK_LAYOUTS[layout], 64)
    R = seg.shape[0]
    seed = sorted(PACK_LAYOUTS).index(layout)      # str hash() is salted
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (R, 64, 4, 32)).astype(dtype)
    k = jax.random.normal(ks[1], (R, 64, 4, 32)).astype(dtype)
    v = jax.random.normal(ks[2], (R, 64, 4, 32)).astype(dtype)
    bm = build_pack_map(seg, tq=16, tk=16)
    o_p = flash_packed_pallas(
        q, k, v, jnp.asarray(seg), jnp.asarray(bm.tile_ids),
        jnp.asarray(bm.tile_count), tq=16, tk=16, interpret=True,
    )
    o_r = ref.flash_packed_ref(q, k, v, jnp.asarray(seg))
    np.testing.assert_allclose(
        np.asarray(o_p, np.float32), np.asarray(o_r, np.float32),
        atol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
    )
    # padding slots must be exact zeros
    np.testing.assert_array_equal(np.asarray(o_p)[seg < 0], 0.0)


@pytest.mark.parametrize("h,hkv", [(4, 2), (8, 1)])
def test_flash_packed_gqa_groups(h, hkv):
    seg = _seg_layout(PACK_LAYOUTS["multi"], 64)
    R = seg.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (R, 64, h, 16))
    k = jax.random.normal(ks[1], (R, 64, hkv, 16))
    v = jax.random.normal(ks[2], (R, 64, hkv, 16))
    bm = build_pack_map(seg, tq=8, tk=32)
    o_p = flash_packed_pallas(
        q, k, v, jnp.asarray(seg), jnp.asarray(bm.tile_ids),
        jnp.asarray(bm.tile_count), tq=8, tk=32, interpret=True,
    )
    o_r = ref.flash_packed_ref(q, k, v, jnp.asarray(seg))
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r), atol=1e-5)


def test_flash_packed_ops_dispatch():
    """Kernel path iff a shape-matching visit list is supplied; the
    q-chunked oracle otherwise; both agree."""
    seg = _seg_layout(PACK_LAYOUTS["multi"], 64)
    R = seg.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (R, 64, 4, 16))
    k = jax.random.normal(ks[1], (R, 64, 2, 16))
    v = jax.random.normal(ks[2], (R, 64, 2, 16))
    segj = jnp.asarray(seg)
    bm = build_pack_map(seg, tq=16, tk=16)
    o_ref = ref.flash_packed_ref(q, k, v, segj)
    with ops.kernel_mode("interpret"):
        o_kernel = ops.flash_packed(
            q, k, v, segj, jnp.asarray(bm.tile_ids),
            jnp.asarray(bm.tile_count), tq=16, tk=16,
        )
        # no visit list -> oracle even in kernel mode
        o_nomap = ops.flash_packed(q, k, v, segj, tq=16, tk=16)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_nomap), np.asarray(o_ref),
                               atol=1e-6)
    # chunked oracle == unchunked oracle
    o_chunk = ops.flash_packed(q, k, v, segj, q_chunk=16)
    np.testing.assert_allclose(np.asarray(o_chunk), np.asarray(o_ref),
                               atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), rows=st.integers(1, 3),
       tile=st.sampled_from([8, 16, 32]))
def test_flash_packed_block_skip_preserves_output(seed, rows, tile):
    """Property: skipping cross-segment tiles computes the SAME output
    as visiting every tile — elision of masked work, never an
    approximation."""
    rng = np.random.default_rng(seed)
    L = 64
    runs = []
    for _ in range(rows):
        row, off, s = [], 0, 0
        while off < L and rng.random() > 0.2:
            n = int(rng.integers(1, L - off + 1))
            row.append((s, n))
            off += n
            s += 1
        runs.append(row)
    seg = _seg_layout(runs, L)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (rows, L, 2, 16))
    k = jax.random.normal(ks[1], (rows, L, 2, 16))
    v = jax.random.normal(ks[2], (rows, L, 2, 16))
    sparse = build_pack_map(seg, tq=tile, tk=tile)
    dense = dense_pack_map(seg, tq=tile, tk=tile)
    args = (q, k, v, jnp.asarray(seg))
    o_s = flash_packed_pallas(
        *args, jnp.asarray(sparse.tile_ids), jnp.asarray(sparse.tile_count),
        tq=tile, tk=tile, interpret=True,
    )
    o_d = flash_packed_pallas(
        *args, jnp.asarray(dense.tile_ids), jnp.asarray(dense.tile_count),
        tq=tile, tk=tile, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(o_s), np.asarray(o_d))


# ----------------------------------------------------------------------
# ssd_scan
# ----------------------------------------------------------------------
@pytest.mark.parametrize("L,H,P,N,G,chunk", [
    (128, 4, 16, 8, 1, 32), (256, 4, 8, 16, 2, 64), (64, 2, 32, 8, 2, 16),
])
def test_ssd_matches_exact_recurrence(L, H, P, N, G, chunk):
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    B = 2
    x = jax.random.normal(ks[0], (B, L, H, P))
    la = -jnp.abs(jax.random.normal(ks[1], (B, L, H))) * 0.3
    b = jax.random.normal(ks[2], (B, L, G, N)) * 0.5
    c = jax.random.normal(ks[3], (B, L, G, N)) * 0.5
    init = jax.random.normal(ks[4], (B, H, P, N)) * 0.1
    y_p, s_p = ssd_scan_pallas(x, la, b, c, init, chunk=chunk, n_groups=G,
                               interpret=True)
    bf = jnp.repeat(b, H // G, 2)
    cf = jnp.repeat(c, H // G, 2)
    y_r, s_r = ref.ssd_scan_ref(x, la, bf, cf, init)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r), atol=2e-4)


def test_ssd_decode_consistent_with_scan():
    """Property: running the chunked scan over L steps equals applying
    the single-step decode L times."""
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    B, L, H, P, N = 1, 16, 2, 8, 4
    x = jax.random.normal(ks[0], (B, L, H, P))
    la = -jnp.abs(jax.random.normal(ks[1], (B, L, H))) * 0.3
    b = jax.random.normal(ks[2], (B, L, H, N)) * 0.5
    c = jax.random.normal(ks[3], (B, L, H, N)) * 0.5
    y_scan, s_scan = ref.ssd_chunked_ref(x, la, b, c, 4)
    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(L):
        y, state = ref.ssd_decode_ref(state, x[:, t], la[:, t], b[:, t], c[:, t])
        ys.append(y)
    y_step = jnp.stack(ys, 1)
    np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_step), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_scan), np.asarray(state), atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_ssd_identity_padding_property(seed):
    """Appending identity steps (log_a=0, x=b=0) must not change the
    final state — the property ops.ssd_scan's padding relies on."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    B, L, H, P, N = 1, 12, 2, 4, 4
    x = jax.random.normal(ks[0], (B, L, H, P))
    la = -jnp.abs(jax.random.normal(ks[1], (B, L, H)))
    b = jax.random.normal(ks[2], (B, L, H, N))
    c = jax.random.normal(ks[3], (B, L, H, N))
    _, s1 = ref.ssd_scan_ref(x, la, b, c)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, 4)) + ((0, 0),) * (a.ndim - 2))
    _, s2 = ref.ssd_scan_ref(pad(x), pad(la), pad(b), pad(c))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)


# ----------------------------------------------------------------------
# contract guards (registry-driven dispatch preconditions / eligibility)
# ----------------------------------------------------------------------
from repro.kernels.contracts import KernelContractError  # noqa: E402


def _guard_counts(op):
    return ops.dispatch_counts().get(op, {})


def test_mv_sad_guard_rejects_bad_geometry():
    good = jnp.zeros((64, 64))
    with pytest.raises(KernelContractError, match="block-divisibility"):
        ops.mv_sad(jnp.zeros((60, 64)), jnp.zeros((60, 64)))
    with pytest.raises(KernelContractError, match="shape-match"):
        ops.mv_sad(good, jnp.zeros((64, 32)))
    with pytest.raises(KernelContractError, match="rank"):
        ops.mv_sad(jnp.zeros((1, 64, 64)), jnp.zeros((1, 64, 64)))
    with pytest.raises(KernelContractError, match="radius"):
        ops.mv_sad(good, good, radius=0)
    # raised identically on both backends: the contract is the contract
    with ops.kernel_mode("interpret"):
        with pytest.raises(KernelContractError, match="block-divisibility"):
            ops.mv_sad(jnp.zeros((60, 64)), jnp.zeros((60, 64)))


def test_rope_shift_guard_rejects_bad_geometry():
    k = jnp.zeros((1, 128, 2, 32))
    d = jnp.zeros((1, 128), jnp.int32)
    with pytest.raises(KernelContractError, match="delta-dtype"):
        ops.rope_shift(k, d.astype(jnp.float32))
    with pytest.raises(KernelContractError, match="delta-shape"):
        ops.rope_shift(k, jnp.zeros((1, 64), jnp.int32))
    with pytest.raises(KernelContractError, match="even-head"):
        ops.rope_shift(jnp.zeros((1, 128, 2, 31)), d)
    with pytest.raises(KernelContractError, match="k-dtype"):
        ops.rope_shift(k.astype(jnp.int32), d)


def test_rope_shift_unaligned_seq_falls_back_cleanly():
    """S=192 is not a 128 multiple: formerly a kernel-side assert crash,
    now a counted eligibility fallback that still returns oracle output."""
    kk = jax.random.normal(jax.random.PRNGKey(7), (1, 192, 2, 32))
    d = jax.random.randint(jax.random.PRNGKey(8), (1, 192), -100, 100)
    before = _guard_counts("rope_shift").get("guard:seq-tile", 0)
    with ops.kernel_mode("interpret"):
        out = ops.rope_shift(kk, d)
    assert _guard_counts("rope_shift").get("guard:seq-tile", 0) == before + 1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.rope_shift_ref(kk, d)), atol=1e-5
    )


def test_flash_prefill_guard_rejects_bad_geometry():
    q = jnp.zeros((1, 128, 4, 32))
    k = jnp.zeros((1, 128, 2, 32))
    with pytest.raises(KernelContractError, match="batch"):
        ops.flash_prefill(q, jnp.zeros((2, 128, 2, 32)), jnp.zeros((2, 128, 2, 32)))
    with pytest.raises(KernelContractError, match="gqa"):
        ops.flash_prefill(jnp.zeros((1, 128, 3, 32)), k, k)
    with pytest.raises(KernelContractError, match="head-dim"):
        ops.flash_prefill(jnp.zeros((1, 128, 4, 64)), k, k)
    with pytest.raises(KernelContractError, match="dtype"):
        ops.flash_prefill(q, k.astype(jnp.int32), k.astype(jnp.int32))
    with pytest.raises(KernelContractError, match="window"):
        ops.flash_prefill(q, k, k, window=0)


def test_flash_prefill_unaligned_tile_falls_back_cleanly():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 192, 4, 32))
    k = jax.random.normal(ks[1], (1, 256, 2, 32))
    v = jax.random.normal(ks[2], (1, 256, 2, 32))
    before = _guard_counts("flash_prefill").get("guard:q-tile", 0)
    with ops.kernel_mode("interpret"):
        out = ops.flash_prefill(q, k, v, q_offset=64)
    assert _guard_counts("flash_prefill").get("guard:q-tile", 0) == before + 1
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(ref.flash_prefill_ref(q, k, v, q_offset=64)),
        atol=1e-5,
    )


def test_ssd_scan_guard_rejects_bad_geometry():
    B, L, H, P, G, N = 1, 16, 4, 8, 2, 8
    x = jnp.zeros((B, L, H, P))
    la = jnp.zeros((B, L, H))
    b = jnp.zeros((B, L, G, N))
    with pytest.raises(KernelContractError, match="log-a-shape"):
        ops.ssd_scan(x, jnp.zeros((B, L, H + 1)), b, b)
    with pytest.raises(KernelContractError, match="bc-shape"):
        ops.ssd_scan(x, la, b, jnp.zeros((B, L, G, N + 1)))
    with pytest.raises(KernelContractError, match="gqa"):
        ops.ssd_scan(x, la, jnp.zeros((B, L, 3, N)), jnp.zeros((B, L, 3, N)))
    with pytest.raises(KernelContractError, match="chunk"):
        ops.ssd_scan(x, la, b, b, chunk=0)
    with pytest.raises(KernelContractError, match="dtype"):
        ops.ssd_scan(x.astype(jnp.int32), la, b, b)


def test_guarded_ops_oracle_parity_smoke():
    """Aligned geometries pass validate() and the ops wrapper's kernel
    path (interpret mode) matches its oracle — end-to-end through the
    contract-driven dispatch."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(11))
    cur = jax.random.uniform(k1, (32, 32)) * 255
    prev = jnp.roll(cur, (1, 1), (0, 1))
    with ops.kernel_mode("interpret"):
        mv_k, sad_k = ops.mv_sad(cur, prev, block=8, radius=2)
    mv_r, sad_r = ref.mv_sad_ref(cur, prev, 8, 2)
    np.testing.assert_array_equal(np.asarray(mv_k), np.asarray(mv_r))
    np.testing.assert_allclose(np.asarray(sad_k), np.asarray(sad_r), rtol=1e-5)

    kk = jax.random.normal(k2, (1, 128, 2, 32))
    d = jnp.full((1, 128), 17, jnp.int32)
    with ops.kernel_mode("interpret"):
        out = ops.rope_shift(kk, d)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.rope_shift_ref(kk, d)), atol=1e-5
    )


# ----------------------------------------------------------------------
# paged attention: shared KV slab + per-stream page tables
# ----------------------------------------------------------------------
def _paged_case(n_streams, pages_per, h=4, hkv=2, d=32, *, page=128,
                seed=11, kv_valid_p=0.3):
    """Random slab + shuffled page tables + ragged logical validity.

    Two spare pages stay un-mapped so the slab holds stale rows no
    stream owns — the masks, not the allocator, must hide them."""
    total = n_streams * pages_per + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    slab_k = jax.random.normal(ks[0], (total * page, hkv, d))
    slab_v = jax.random.normal(ks[1], (total * page, hkv, d))
    perm = np.random.default_rng(seed).permutation(total)
    pt = jnp.asarray(
        perm[: n_streams * pages_per]
        .reshape(n_streams, pages_per).astype(np.int32))
    kvv = jax.random.uniform(
        ks[2], (n_streams, pages_per * page)) > kv_valid_p
    return slab_k, slab_v, pt, kvv


def test_paged_gather_matches_manual_indexing():
    """paged_gather_ref is a pure reindexing: logical slot s of stream b
    IS slab row pt[b, s // page] * page + s % page, value-identical."""
    slab_k, _, pt, _ = _paged_case(3, 2)
    g = np.asarray(ref.paged_gather_ref(slab_k, pt, 128))
    slab = np.asarray(slab_k)
    for b in range(3):
        for s in (0, 1, 127, 128, 200, 255):
            phys = int(pt[b, s // 128]) * 128 + s % 128
            np.testing.assert_array_equal(g[b, s], slab[phys])


def _paged_refresh_cases():
    """(pattern, h, hkv, dtype) cases of the grouped paged kernel: query
    groups g = H // Hkv of 1, 2, 5 (InternVL3-14B's 40/8) and 6 (12/2),
    every scatter pattern plus a decode step, f32 and bf16.  The 4/2 f32
    cases keep their original ids."""
    patterns = sorted(SCATTER_PATTERNS) + ["decode"]
    cases = [pytest.param(p, 4, 2, jnp.float32, id=p) for p in patterns]
    for h, hkv in [(4, 4), (10, 2), (12, 2)]:
        cases += [pytest.param(p, h, hkv, jnp.float32, id=f"{p}-h{h}kv{hkv}")
                  for p in patterns]
    for h, hkv in [(4, 4), (4, 2), (10, 2), (12, 2)]:
        cases += [pytest.param(p, h, hkv, jnp.bfloat16,
                               id=f"{p}-h{h}kv{hkv}-bf16")
                  for p in ("anchors_tail", "decode")]
    return cases


@pytest.mark.parametrize("pattern,h,hkv,dtype", _paged_refresh_cases())
def test_flash_refresh_paged_matches_ref(pattern, h, hkv, dtype):
    if pattern == "decode":
        # one query at a static position, mapped as serving decodes
        q_pos = np.asarray([200], np.int32)
        bm = span_block_map(200, 1, 256)
    else:
        q_pos = SCATTER_PATTERNS[pattern]
        bm = build_block_map(q_pos, 256, tq=128, tk=128, causal=True)
    slab_k, slab_v, pt, kvv = _paged_case(2, 2, hkv=hkv)
    slab_k, slab_v = slab_k.astype(dtype), slab_v.astype(dtype)
    q = jax.random.normal(jax.random.PRNGKey(3), (2, len(q_pos), h, 32))
    q = q.astype(dtype)
    qp = jnp.broadcast_to(jnp.asarray(q_pos)[None], (2, len(q_pos)))
    before = _guard_counts("flash_refresh_paged").get("kernel", 0)
    with ops.kernel_mode("interpret"):
        o_k = ops.flash_refresh_paged(
            q, slab_k, slab_v, qp, kvv, pt, block_map=bm, causal=True)
    assert _guard_counts("flash_refresh_paged").get("kernel", 0) == before + 1
    o_r = ref.flash_refresh_paged_ref(
        q, slab_k, slab_v, qp, kvv, pt, causal=True)
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r, np.float32),
        atol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
    )


def test_flash_refresh_paged_oracle_bitwise_vs_dense_gather():
    """The paged oracle path IS the dense path on the gathered logical
    view — bitwise, not approximately: gather preserves value identity
    and ordering, so both runs reduce identical operands in identical
    order."""
    q_pos = SCATTER_PATTERNS["anchors_tail"]
    slab_k, slab_v, pt, kvv = _paged_case(2, 2, kv_valid_p=0.4)
    q = jax.random.normal(jax.random.PRNGKey(5), (2, len(q_pos), 4, 32))
    qp = jnp.broadcast_to(jnp.asarray(q_pos)[None], (2, len(q_pos)))
    o_paged = ops.flash_refresh_paged(
        q, slab_k, slab_v, qp, kvv, pt, causal=True)
    kg = ref.paged_gather_ref(slab_k, pt, 128)
    vg = ref.paged_gather_ref(slab_v, pt, 128)
    o_dense = ops.flash_refresh(q, kg, vg, qp, kvv, causal=True)
    np.testing.assert_array_equal(np.asarray(o_paged), np.asarray(o_dense))


def test_flash_refresh_paged_page_tile_fallback():
    """A 256-slot page cannot map 1:1 onto 128-wide kv tiles: the
    page-tile eligibility rule must route to the oracle, counted."""
    q_pos = np.arange(0, 64, dtype=np.int32)
    slab_k, slab_v, _, _ = _paged_case(1, 2, seed=13)   # 512 rows
    pt = jnp.asarray([[0]], jnp.int32)                  # one 256-slot page
    kvv = jnp.ones((1, 256), bool)
    q = jax.random.normal(jax.random.PRNGKey(7), (1, 64, 4, 32))
    qp = jnp.asarray(q_pos)[None]
    bm = build_block_map(q_pos, 256, tq=128, tk=128, causal=True)
    before = _guard_counts("flash_refresh_paged").get("guard:page-tile", 0)
    with ops.kernel_mode("interpret"):
        out = ops.flash_refresh_paged(
            q, slab_k, slab_v, qp, kvv, pt, page=256, block_map=bm,
            causal=True)
    counts = _guard_counts("flash_refresh_paged")
    assert counts.get("guard:page-tile", 0) == before + 1
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(ref.flash_refresh_paged_ref(
            q, slab_k, slab_v, qp, kvv, pt, page=256, causal=True)),
        atol=1e-6,
    )


def test_flash_refresh_paged_group_vmem_fallback():
    """48 query heads on one kv head need a (6144, D) grouped step, more
    than the scoped VMEM holds: the group-vmem eligibility rule routes
    to the oracle, counted."""
    q_pos = SCATTER_PATTERNS["anchors_tail"]
    slab_k, slab_v, pt, kvv = _paged_case(1, 2, hkv=1, seed=31)
    q = jax.random.normal(jax.random.PRNGKey(37), (1, len(q_pos), 48, 32))
    qp = jnp.asarray(q_pos)[None]
    bm = build_block_map(q_pos, 256, tq=128, tk=128, causal=True)
    before = _guard_counts("flash_refresh_paged").get("guard:group-vmem", 0)
    with ops.kernel_mode("interpret"):
        out = ops.flash_refresh_paged(
            q, slab_k, slab_v, qp, kvv, pt, block_map=bm, causal=True)
    counts = _guard_counts("flash_refresh_paged")
    assert counts.get("guard:group-vmem", 0) == before + 1
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(ref.flash_refresh_paged_ref(
            q, slab_k, slab_v, qp, kvv, pt, causal=True)),
        atol=1e-6,
    )


@pytest.mark.parametrize("window", [None, 64])
def test_flash_prefill_paged_matches_ref(window):
    slab_k, slab_v, pt, _ = _paged_case(2, 2, seed=17)
    q = jax.random.normal(jax.random.PRNGKey(19), (2, 256, 4, 32))
    before = _guard_counts("flash_prefill_paged").get("kernel", 0)
    with ops.kernel_mode("interpret"):
        o_k = ops.flash_prefill_paged(
            q, slab_k, slab_v, pt, window=window)
    assert _guard_counts("flash_prefill_paged").get("kernel", 0) == before + 1
    o_r = ref.flash_prefill_paged_ref(q, slab_k, slab_v, pt, window=window)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5)


def test_flash_prefill_paged_guard_and_fallback():
    slab_k, slab_v, pt, _ = _paged_case(1, 2, seed=23)
    q = jax.random.normal(jax.random.PRNGKey(29), (1, 256, 4, 32))
    # causal masking is what hides stale rows in recycled pages: a
    # non-causal paged prefill is a contract violation, not a fallback
    with pytest.raises(KernelContractError, match="causal"):
        ops.flash_prefill_paged(q, slab_k, slab_v, pt, causal=False)
    # unaligned query count: counted eligibility fallback, oracle output
    q192 = q[:, :192]
    before = _guard_counts("flash_prefill_paged").get("guard:q-tile", 0)
    with ops.kernel_mode("interpret"):
        out = ops.flash_prefill_paged(q192, slab_k, slab_v, pt)
    assert (
        _guard_counts("flash_prefill_paged").get("guard:q-tile", 0)
        == before + 1
    )
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(ref.flash_prefill_paged_ref(q192, slab_k, slab_v, pt)),
        atol=1e-6,
    )


# ----------------------------------------------------------------------
# two-precision paged attention: int8 cold pages + fused dequant
# ----------------------------------------------------------------------
from repro.models.layers import (  # noqa: E402
    dequantize_kv, page_quant_scale, quantize_kv,
)


def _quant_paged_case(n_streams, pages_per, cold_per, hkv=2, d=32, *,
                      page=128, seed=31):
    """Mixed-precision slab: each stream's first ``cold_per`` pages are
    int8 cold pages (unified id space: entry >= n_hot addresses the cold
    slab at entry - n_hot), the tail stays hot bf16.  One page in each
    slab stays unmapped so stale rows exist in both precisions."""
    n_hot = n_streams * (pages_per - cold_per) + 1
    n_cold = n_streams * cold_per + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    hot_k = jax.random.normal(ks[0], (n_hot * page, hkv, d), jnp.bfloat16)
    hot_v = jax.random.normal(ks[1], (n_hot * page, hkv, d), jnp.bfloat16)
    ck = jax.random.normal(ks[2], (n_cold, page, hkv, d))
    cv = jax.random.normal(ks[3], (n_cold, page, hkv, d))
    k_scale = page_quant_scale(ck, (1, 3))
    v_scale = page_quant_scale(cv, (1, 3))
    k8 = quantize_kv(ck, k_scale[:, None]).reshape(n_cold * page, hkv, d)
    v8 = quantize_kv(cv, v_scale[:, None]).reshape(n_cold * page, hkv, d)
    rng = np.random.default_rng(seed)
    hot_ids = rng.permutation(n_hot - 1)
    cold_ids = rng.permutation(n_cold - 1) + n_hot
    pt = np.zeros((n_streams, pages_per), np.int32)
    nh = pages_per - cold_per
    for b in range(n_streams):
        pt[b, :cold_per] = cold_ids[b * cold_per:(b + 1) * cold_per]
        pt[b, cold_per:] = hot_ids[b * nh:(b + 1) * nh]
    kvv = jax.random.uniform(ks[4], (n_streams, pages_per * page)) > 0.3
    return (hot_k, hot_v, (k8, v8, k_scale, v_scale), jnp.asarray(pt),
            kvv)


def test_paged_gather_quant_matches_manual_indexing():
    """Hot slots are slab rows verbatim; cold slots are the int8 row
    dequantized through the storage dtype — value-identical to what the
    fused kernel feeds QK^T."""
    page = 128
    hot_k, _, (k8, _, k_scale, _), pt, _ = _quant_paged_case(2, 3, 2)
    n_hot = hot_k.shape[0] // page
    g = np.asarray(ref.paged_gather_quant_ref(hot_k, k8, k_scale, pt, page))
    hot = np.asarray(hot_k)
    for b in range(2):
        for s in (0, 127, 128, 255, 256, 340, 383):
            entry = int(pt[b, s // page])
            if entry < n_hot:
                want = hot[entry * page + s % page]
            else:
                cpg = entry - n_hot
                row = k8[cpg * page + s % page]
                want = np.asarray(dequantize_kv(
                    row, k_scale[cpg], hot_k.dtype))
            np.testing.assert_array_equal(g[b, s], want)


@pytest.mark.parametrize("pattern", sorted(SCATTER_PATTERNS))
def test_flash_refresh_paged_quant_matches_ref(pattern):
    """Fused-dequant kernel (interpret) vs gather-and-dequant oracle on
    a mixed hot/cold page table — kernel path taken, not a fallback."""
    q_pos = SCATTER_PATTERNS[pattern]
    hot_k, hot_v, cold, pt, kvv = _quant_paged_case(2, 2, 1)
    q = jax.random.normal(
        jax.random.PRNGKey(37), (2, len(q_pos), 4, 32), jnp.bfloat16)
    qp = jnp.broadcast_to(jnp.asarray(q_pos)[None], (2, len(q_pos)))
    bm = build_block_map(q_pos, 256, tq=128, tk=128, causal=True)
    before = _guard_counts("flash_refresh_paged").get("kernel", 0)
    with ops.kernel_mode("interpret"):
        o_k = ops.flash_refresh_paged(
            q, hot_k, hot_v, qp, kvv, pt, block_map=bm, causal=True,
            cold=cold)
    assert _guard_counts("flash_refresh_paged").get("kernel", 0) == before + 1
    o_r = ref.flash_refresh_paged_ref(
        q, hot_k, hot_v, qp, kvv, pt, causal=True, cold=cold)
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r, np.float32),
        atol=3e-2)


def test_flash_refresh_paged_quant_oracle_bitwise_vs_dequantized_dense():
    """The quant oracle == the dense refresh on the manually dequantized
    logical view, bitwise: dequant rounds through the storage dtype, so
    precision routing adds no reduction-order freedom."""
    q_pos = SCATTER_PATTERNS["anchors_tail"]
    hot_k, hot_v, cold, pt, kvv = _quant_paged_case(2, 2, 1, seed=41)
    k8, v8, k_scale, v_scale = cold
    q = jax.random.normal(jax.random.PRNGKey(43), (2, len(q_pos), 4, 32))
    qp = jnp.broadcast_to(jnp.asarray(q_pos)[None], (2, len(q_pos)))
    o_paged = ops.flash_refresh_paged(
        q, hot_k, hot_v, qp, kvv, pt, causal=True, cold=cold)
    kg = ref.paged_gather_quant_ref(hot_k, k8, k_scale, pt, 128)
    vg = ref.paged_gather_quant_ref(hot_v, v8, v_scale, pt, 128)
    o_dense = ops.flash_refresh(q, kg, vg, qp, kvv, causal=True)
    np.testing.assert_array_equal(np.asarray(o_paged), np.asarray(o_dense))


def test_flash_refresh_paged_quant_scale_f32_guard():
    """f16 scales are refused by exactly the scale-f32 eligibility rule
    (counted, oracle output) — never silently mis-dequantized."""
    q_pos = SCATTER_PATTERNS["anchors_only"]
    hot_k, hot_v, (k8, v8, k_scale, v_scale), pt, kvv = _quant_paged_case(
        1, 2, 1, seed=47)
    cold16 = (k8, v8, k_scale.astype(jnp.float16),
              v_scale.astype(jnp.float16))
    q = jax.random.normal(jax.random.PRNGKey(53), (1, len(q_pos), 4, 32))
    qp = jnp.asarray(q_pos)[None]
    bm = build_block_map(q_pos, 256, tq=128, tk=128, causal=True)
    before = _guard_counts("flash_refresh_paged").get("guard:scale-f32", 0)
    with ops.kernel_mode("interpret"):
        out = ops.flash_refresh_paged(
            q, hot_k, hot_v, qp, kvv, pt, block_map=bm, causal=True,
            cold=cold16)
    counts = _guard_counts("flash_refresh_paged")
    assert counts.get("guard:scale-f32", 0) == before + 1
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(ref.flash_refresh_paged_ref(
            q, hot_k, hot_v, qp, kvv, pt, causal=True, cold=cold16)),
        atol=1e-6)


@pytest.mark.parametrize("window", [None, 64])
def test_flash_prefill_paged_quant_matches_ref(window):
    hot_k, hot_v, cold, pt, _ = _quant_paged_case(2, 2, 1, seed=59)
    q = jax.random.normal(
        jax.random.PRNGKey(61), (2, 256, 4, 32), jnp.bfloat16)
    before = _guard_counts("flash_prefill_paged").get("kernel", 0)
    with ops.kernel_mode("interpret"):
        o_k = ops.flash_prefill_paged(
            q, hot_k, hot_v, pt, window=window, cold=cold)
    assert _guard_counts("flash_prefill_paged").get("kernel", 0) == before + 1
    o_r = ref.flash_prefill_paged_ref(
        q, hot_k, hot_v, pt, window=window, cold=cold)
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r, np.float32),
        atol=3e-2)
