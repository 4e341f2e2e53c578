"""Multi-stream scaling: fused-window throughput, KV staging overhead,
and the async-vs-lockstep scheduler A/B as the concurrent fleet grows.

Serves the same eval corpus at increasing ``max_concurrent`` with the
paged slab (page-table staging, ``docs/paged_kv.md``) and with the
legacy per-stream concat/split path — the t_overhead gap is the KV
bytes the scheduler no longer moves per fused window.

On the paged leg each fleet size also runs the stage-pipelined async
scheduler (``docs/async_scheduler.md``) against the lockstep baseline:
identical per-window answers are ASSERTED (the pipelining is a
scheduling change, not a numerics change), and at fleet >= 4 the async
aggregate windows/s must be at least the lockstep scheduler's.  The
latency distribution (p50/p99 window latency, TTFT) and per-stage
occupancy of both engines land in the artifact for the nightly upload.

Fleet sizes come from ``STREAM_FLEETS`` (comma-separated, default
``1,2,4``); the nightly workflow raises it to stress higher stream
counts than the PR-gating smoke can afford.

The int8 cold-page capacity A/B (``docs/paged_kv.md`` §Quantized cold
pages) runs at a long-window geometry where the demotable overlap is
15/16 pages: at a fixed slab byte budget, the two-precision pool must
admit >= 1.7x the streams of the all-bf16 pool while every common
stream produces identical per-window answers (max abs logit error is
reported, and gated upward in ``report.py`` as
``streams/quant_capacity_ratio``).  Set ``QUANT_CAPACITY=0`` to skip.
"""
from __future__ import annotations

import os

import numpy as np

from .common import csv_row, eval_videos, run_mode


def _fleets() -> tuple:
    raw = os.environ.get("STREAM_FLEETS", "1,2,4")
    return tuple(int(x) for x in raw.split(",") if x.strip())


def _admit_all(pipe, cap: int, videos) -> tuple:
    """Admit streams one at a time until the pool refuses the next one,
    serving every window of each stream before the next admission (the
    scheduler's staggered-admission order) so overlap pages actually
    demote and free hot capacity.  Streams stay resident — capacity is
    the question, not throughput.  Returns (states, per-stream stats).
    """
    resident, served = [], []
    while pipe.can_admit(1) and len(resident) < min(cap, len(videos)):
        cs = pipe.frontend.open(np.asarray(videos[len(resident)]))
        state, stats_w = None, []
        for k in range(cs.n_windows):
            wf, wm, _ = pipe.frontend.window(cs, k)
            stats, state = pipe.serve_batch(wf[None], [wm], state)
            stats_w.append(stats[0])
        resident.append(state)
        served.append(stats_w)
    return resident, served


def _quant_capacity(emit) -> dict:
    """Tentpole A/B: stream admission at a fixed KV slab byte budget,
    int8 cold pages vs all-bf16 (docs/paged_kv.md §Quantized cold
    pages).  Long-window geometry (W=124, stride=4, keep_ratio=1.0)
    puts 15 of each stream's 16 pages inside the reused overlap, so the
    steady-state footprint is 1 hot page + 15 demoted int8 pages."""
    from repro.configs.base import CodecCfg
    from repro.data.video import VideoSpec, generate_video

    from .common import VIT, make_pipeline

    codec = CodecCfg(gop=4, block=16, search_radius=4, window_frames=124,
                     stride_frames=4, keep_ratio=1.0)
    N_CAP = 14
    # seed base chosen so every window's yes/no decision margin (>= 2.9
    # logits across this set) dwarfs the int8 round-trip error budget
    # (~0.06 logits at this depth) — the answer-equality assert below
    # tests quantization, not coin-flip windows of the tiny bench model
    videos = [
        generate_video(VideoSpec(n_frames=128, height=VIT.image,
                                 width=VIT.image, anomaly=bool(i % 2),
                                 seed=201 + i))[0]
        for i in range(N_CAP)
    ]

    pq = make_pipeline("codecflow", codec, stale_dtype="int8")
    pq.ensure_capacity(N_CAP)
    pool_q = pq.backend.pool
    D = pq.backend.cold_per_stream
    P = pq.backend.pages_per_stream
    assert D > 0, "no demotable overlap page at the capacity geometry"
    budget = pool_q.slab_bytes

    q_states, q_stats = _admit_all(pq, N_CAP, videos)
    n_q = len(q_states)
    assert not pq.can_admit(1), "quant pool not exhausted at N_CAP"

    # all-bf16 control: as many 16-hot-page streams as fit in <= the
    # SAME slab byte budget
    n_b = int(budget // (P * pool_q.page_bytes()))
    pb = make_pipeline("codecflow", codec, stale_dtype="bf16",
                       pool_streams=n_b)
    pb.ensure_capacity(n_b)
    assert pb.backend.pool.slab_bytes <= budget
    b_states, b_stats = _admit_all(pb, n_b, videos)
    assert len(b_states) == n_b and not pb.can_admit(1)

    # precision is a storage decision, not an answer decision: every
    # stream served by BOTH pools must answer identically per window
    common = min(n_q, n_b)
    answers_equal = all(
        [s.answer for s in q_stats[i]] == [s.answer for s in b_stats[i]]
        for i in range(common)
    )
    err = max(
        abs(ql - bl)
        for i in range(common)
        for sq, sb in zip(q_stats[i], b_stats[i])
        for ql, bl in zip(sq.logits_yes_no, sb.logits_yes_no)
    )
    assert answers_equal, "int8 cold pages changed a per-window answer"

    out = {
        "quant_streams": n_q,
        "bf16_streams": n_b,
        "quant_capacity_ratio": n_q / max(n_b, 1),
        "quant_slab_budget_bytes": int(budget),
        "quant_bytes_per_stream": pq.kv_bytes_per_stream(),
        "bf16_bytes_per_stream": pb.kv_bytes_per_stream(),
        "quant_answers_equal": answers_equal,
        "quant_max_logit_err": float(err),
        "quant_cold_pages_per_stream": D,
        "quant_pages_per_stream": P,
    }
    emit(csv_row(
        "streams/quant_capacity", 0.0,
        f"int8 {n_q} vs bf16 {n_b} streams at {budget:,}B slab "
        f"({out['quant_capacity_ratio']:.2f}x, gate >= 1.7x) "
        f"max|dlogit|={err:.4f}"))
    # acceptance: >= 1.7x admission at fixed bytes, answers identical
    assert out["quant_capacity_ratio"] >= 1.7, out["quant_capacity_ratio"]

    for pipe, states in ((pq, q_states), (pb, b_states)):
        for st in states:
            pipe.release_state(st)
    assert pool_q.free_pages == pool_q.n_pages
    assert pool_q.free_cold_pages == pool_q.n_cold
    return out


def run(emit) -> dict:
    out = {"fleets": list(_fleets())}
    if os.environ.get("QUANT_CAPACITY", "1") != "0":
        out.update(_quant_capacity(emit))
    for n in _fleets():
        # at least as many streams as slots, so the fleet actually fills
        videos = eval_videos(max(2 * n, 6))
        for paged in (True, False):
            tag = "paged" if paged else "concat"
            r = run_mode("codecflow", videos=videos, concurrent=n,
                         paged=paged)
            out[f"s{n}_{tag}_windows_per_s"] = r["windows_per_s"]
            out[f"s{n}_{tag}_t_overhead"] = r["t_overhead"]
            out[f"s{n}_{tag}_f1"] = r["f1"]
            emit(csv_row(
                f"streams/c{n}_{tag}",
                1e6 / max(r["windows_per_s"], 1e-9),
                f"windows/s={r['windows_per_s']:.2f} "
                f"t_overhead={r['t_overhead'] * 1e3:.2f}ms",
            ))
            if paged:
                lockstep = r
        # paged and concat must agree on every answer: the slab is an
        # allocation strategy, not an approximation
        assert out[f"s{n}_paged_f1"] == out[f"s{n}_concat_f1"], n

        out[f"s{n}_staging_reduction_x"] = (
            out[f"s{n}_concat_t_overhead"]
            / max(out[f"s{n}_paged_t_overhead"], 1e-9)
        )

        # ---- async-vs-lockstep scheduler A/B (paged leg) -------------
        r_async = run_mode("codecflow", videos=videos, concurrent=n,
                           paged=True, pipelined=True)
        # the async engine reorders/fuses WORK, never math: every
        # stream must produce the identical per-window answer sequence
        assert r_async["window_answers"] == lockstep["window_answers"], (
            n, r_async["window_answers"], lockstep["window_answers"])
        out[f"s{n}_async_windows_per_s"] = r_async["windows_per_s"]
        out[f"s{n}_lockstep_windows_per_s"] = lockstep["windows_per_s"]
        for eng, rr in (("async", r_async), ("lockstep", lockstep)):
            out[f"s{n}_{eng}_ttft_p50"] = rr["ttft_p50"]
            out[f"s{n}_{eng}_ttft_p99"] = rr["ttft_p99"]
            out[f"s{n}_{eng}_occupancy"] = rr["stage_occupancy"]
        speedup = (r_async["windows_per_s"]
                   / max(lockstep["windows_per_s"], 1e-9))
        out[f"s{n}_async_speedup_x"] = speedup
        emit(csv_row(
            f"streams/c{n}_async",
            1e6 / max(r_async["windows_per_s"], 1e-9),
            f"windows/s={r_async['windows_per_s']:.2f} "
            f"vs_lockstep={speedup:.2f}x "
            f"ttft_p99={r_async['ttft_p99'] * 1e3:.0f}ms",
        ))
        if n >= 4:
            # acceptance: stage overlap must not LOSE throughput once
            # the fleet is large enough to keep every stage busy
            assert speedup >= 1.0, (
                f"async scheduler slower than lockstep at fleet {n}: "
                f"{r_async['windows_per_s']:.2f} vs "
                f"{lockstep['windows_per_s']:.2f} windows/s"
            )
    return out
