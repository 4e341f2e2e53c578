"""Ahead-of-time TPU v5e compiles of every Pallas kernel the serving path
can dispatch to, at real widths.

Nothing runs: each test lowers the kernel for a described (not attached)
v5e chip and asserts the Mosaic kernel is in the compiled program.  This
catches what interpret mode cannot — blocks the TPU tiling refuses,
vector layouts Mosaic cannot build — at no chip cost.  Widths: the
InternVL3-14B LM (40 query / 8 KV heads of 128) with its 2-stream paged
slab, the InternViT-300M encoder (16 heads of 64), 448-px codec frames,
and the mamba2-2.7b SSD mixer (80 heads of 64, d_state 128).  The codec's
segment programs (``encode_stream``, ``decode_stream``) are compiled too,
and must hold no gather.

The topology is described inside a module fixture, never at import:
only the worker that runs this file may load the TPU compiler library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.codec import decode_stream, encode_stream
from repro.codec.metadata import Bitstream
from repro.configs.base import CodecCfg
from repro.kernels import ops
from repro.kernels.flash_packed import flash_packed_pallas
from repro.kernels.flash_prefill import (
    flash_prefill_paged_pallas, flash_prefill_pallas,
)
from repro.kernels.flash_refresh import (
    flash_refresh_paged_pallas, flash_refresh_pallas,
)
from repro.kernels.mv_sad import mv_sad_pallas
from repro.kernels.rope_shift import rope_shift_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
H, HKV, D = 40, 8, 128            # InternVL3-14B LM attention
PAGE = 128
PAGES = 21                        # per stream: 2568-token window + decode
PHYS = 2 * PAGES * PAGE           # slab rows for 2 streams
COLD = 16 * PAGE                  # int8 cold slab rows
NQT, TMAX = 4, PAGES              # refresh visit list: q tiles x kv tiles


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles cannot be read back from a persistent
    # cache without the chip, so keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topology
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cold():
    return [((COLD, HKV, D), I8), ((COLD, HKV, D), I8),
            ((COLD // PAGE, HKV), F32), ((COLD // PAGE, HKV), F32)]


def _refresh_operands(paged: bool, nqt: int = NQT, h: int = H,
                      hkv: int = HKV):
    kv = (PHYS, hkv, D) if paged else (2, PAGES * PAGE, hkv, D)
    ops = [((2, nqt * 128, h, D), BF16), (kv, BF16), (kv, BF16),
           ((nqt * 128,), I32), ((2, PAGES * PAGE), jnp.bool_)]
    if paged:
        ops.append(((2, PAGES), I32))
    return ops + [((nqt, TMAX), I32), ((nqt,), I32)]


def _refresh_paged_cold(*a):
    *args, k8, v8, ks, vs = a
    return flash_refresh_paged_pallas(*args, cold=(k8, v8, ks, vs))


def _prefill_paged_cold(q, k, v, pt, k8, v8, ks, vs):
    return flash_prefill_paged_pallas(q, k, v, pt, cold=(k8, v8, ks, vs))


_PREFILL = [((2, 1024, H, D), BF16)]
_PAGED_KV = [((PHYS, HKV, D), BF16), ((PHYS, HKV, D), BF16),
             ((2, PAGES), I32)]
_PACKED = [((4, 2048, 16, 64), BF16)] * 3 + [
    ((4, 2048), I32), ((4, 16, 4), I32), ((4, 16), I32)]

CASES = {
    "mv_sad-448": (
        lambda cur, prev: mv_sad_pallas(cur, prev, block=16, radius=4),
        [((448, 448), F32), ((448, 448), F32)],
    ),
    # overlap keys of 16 layers x 2 streams, and a single row (B == 1)
    "rope_shift": (rope_shift_pallas,
                   [((32, 1920, HKV, D), BF16), ((32, 1920), I32)]),
    "rope_shift-b1": (rope_shift_pallas,
                      [((1, 1920, HKV, D), BF16), ((1, 1920), I32)]),
    "flash_prefill": (
        flash_prefill_pallas,
        _PREFILL + [((2, 1024, HKV, D), BF16)] * 2,
    ),
    "flash_prefill_paged": (flash_prefill_paged_pallas,
                            _PREFILL + _PAGED_KV),
    "flash_prefill_paged-int8": (_prefill_paged_cold,
                                 _PREFILL + _PAGED_KV + _cold()),
    "flash_refresh": (flash_refresh_pallas, _refresh_operands(False)),
    "flash_refresh_paged": (flash_refresh_paged_pallas,
                            _refresh_operands(True)),
    # the decode step: one query tile against the whole window
    "flash_refresh_paged-decode": (flash_refresh_paged_pallas,
                                   _refresh_operands(True, nqt=1)),
    # InternVL3-2B's LM (Qwen2.5-1.5B): 12 query heads on 2 kv heads
    "flash_refresh_paged-g6": (flash_refresh_paged_pallas,
                               _refresh_operands(True, h=12, hkv=2)),
    "flash_refresh_paged-int8": (_refresh_paged_cold,
                                 _refresh_operands(True) + _cold()),
    "flash_packed-internvit": (flash_packed_pallas, _PACKED),
    "ssd_scan-mamba2": (
        lambda x, a, b, c: ssd_scan_pallas(x, a, b, c, None, chunk=256,
                                           n_groups=1),
        [((1, 1024, 80, 64), BF16), ((1, 1024, 80), F32),
         ((1, 1024, 1, 128), BF16), ((1, 1024, 1, 128), BF16)],
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, operands = CASES[case]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in operands]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fn", ["encode_stream", "decode_stream"])
def test_codec_compiles_for_v5e_without_gather(fn, one_chip):
    """One served segment (92 frames of 448 px, radius 4) with the search
    on the ``mv_sad`` kernel: the v5e program holds no gather, so motion
    compensation stays static shifts of the padded reference."""
    f = jax.ShapeDtypeStruct((92, 448, 448), F32, sharding=one_chip)
    i = jax.ShapeDtypeStruct((92,), I32, sharding=one_chip)
    mv = jax.ShapeDtypeStruct((92, 28, 28, 2), I32, sharding=one_chip)
    jax.clear_caches()  # the trace cache does not key on the kernel mode
    with ops.kernel_mode("pallas"):
        if fn == "encode_stream":
            lowered = encode_stream.lower(
                f, CodecCfg(gop=4, block=16, search_radius=4))
        else:
            lowered = decode_stream.lower(Bitstream(i, f, mv, f, 4), 16)
        hlo = lowered.compile().as_text()
    jax.clear_caches()
    assert "gather(" not in hlo
    if fn == "encode_stream":
        assert "tpu_custom_call" in hlo
