"""Paged refresh kernel on a TPU, against other source trees' kernels.

    PYTHONPATH=src python3 -m benchmarks.refresh_paged_variants \
        [--baseline-src OTHER_TREE/src ...] [--out FILE]

Times ``flash_refresh_paged_pallas`` at the serving shapes of the
InternVL3-14B camera cell (8 streams, heads of 128, bf16 slab; the
16-frame GOP-4 stride-4 window: 12 refresh query tiles over 21 kv
pages, the 1-token decode step, the 21-tile fresh prefill) under two
head layouts: the cell's 40 query heads on 8 kv heads (g = 5), and 40
on 40 (g = 1, an MHA model's grid).  Each ``--baseline-src``
(repeatable) times that tree's ``repro/kernels/flash_refresh.py`` on the
same operands, so every variant moves the same bytes; variants that
differ from the tree in one respect (grid or MXU precision) are built as
such trees.  Each output is compared with the oracle
(``ref.flash_refresh_paged_ref``).  Times are milliseconds per call
from the host clock around back-to-back calls ended by
``block_until_ready``; a call includes the wrapper's slab transposes.
Exits 1 on a backend other than TPU.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kvc import WindowLayout, refresh_block_map
from repro.kernels import ref
from repro.kernels.flash_refresh import (
    build_block_map, flash_refresh_paged_pallas, span_block_map,
)

from .bench_kernels import _timeit

PAGE = 128
STREAMS = 8
HEAD_DIM = 128
LAYOUTS = ((40, 8), (40, 40))          # (query heads, kv heads)
ITERS = 20


def _load_kernel(src: str, name: str):
    """``flash_refresh_paged_pallas`` of another source tree."""
    path = f"{src}/repro/kernels/flash_refresh.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod.flash_refresh_paged_pallas


def _maps(layout: WindowLayout, kv_len: int):
    total = layout.total_len
    return {
        "refresh": refresh_block_map(layout, kv_len=kv_len),
        "decode": span_block_map(total, 1, kv_len),
        "fresh": build_block_map(np.arange(total, dtype=np.int32), kv_len),
    }


def run(kernels: dict) -> list[dict]:
    """One row per (head layout, map, kernel): ms per call and the
    largest deviation from the oracle."""
    layout = WindowLayout(window=16, stride=4, gop=4, g_tokens=256,
                          k_tokens=128, query_len=8)
    kv_len = -(-(layout.total_len + 1) // PAGE) * PAGE
    n_pages = kv_len // PAGE
    B, D = STREAMS, HEAD_DIM
    rng = np.random.default_rng(0)
    phys = (B * n_pages + 2) * PAGE
    pt = jnp.asarray(rng.permutation(B * n_pages + 2)[: B * n_pages]
                     .reshape(B, n_pages).astype(np.int32))
    kvv = jnp.asarray(rng.random((B, kv_len)) > 0.05)
    rows = []
    for H, Hkv in LAYOUTS:
        ks = jax.random.split(jax.random.PRNGKey(Hkv), 3)
        k = jax.random.normal(ks[0], (phys, Hkv, D), jnp.bfloat16)
        v = jax.random.normal(ks[1], (phys, Hkv, D), jnp.bfloat16)
        for phase, bm in _maps(layout, kv_len).items():
            n_q = bm.q_pos.shape[0]
            q = jax.random.normal(ks[2], (B, n_q, H, D), jnp.bfloat16)
            qp = jnp.asarray(bm.q_pos)
            ids, cnt = jnp.asarray(bm.tile_ids), jnp.asarray(bm.tile_count)
            # one stream at a time: the oracle's logits of all would not fit
            oracle = np.concatenate([np.asarray(ref.flash_refresh_paged_ref(
                q[b:b + 1, : bm.n_q], k, v, qp[None, : bm.n_q], kvv[b:b + 1],
                pt[b:b + 1]), np.float32) for b in range(B)])
            for name, f in kernels.items():
                def call(f=f):
                    return f(q, k, v, qp, kvv, pt, ids, cnt)
                ms = _timeit(call, ITERS) / 1e3
                out = np.asarray(call(), np.float32)[:, : bm.n_q]
                row = {
                    "heads": H, "kv_heads": Hkv, "phase": phase,
                    "variant": name, "ms_per_call": ms,
                    "q_tiles": bm.n_q_tiles, "t_max": bm.t_max,
                    "live_tiles": int(bm.tile_count.sum()),
                    "max_abs_err_vs_oracle": float(np.abs(out - oracle).max()),
                }
                rows.append(row)
                print(json.dumps(row), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline-src", action="append", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU (found {dev.platform})", file=sys.stderr)
        return 1
    kernels = {"tree": flash_refresh_paged_pallas}
    for i, src in enumerate(args.baseline_src):
        kernels[src] = _load_kernel(src, f"baseline_flash_refresh_{i}")
    rows = run(kernels)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"device": dev.device_kind, "streams": STREAMS,
                       "rows": rows}, f, indent=1)
    print(json.dumps({"device": dev.device_kind, "n_rows": len(rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
