"""Render benchmark JSON as markdown tables.

    PYTHONPATH=src python -m benchmarks.report [bench.json]
        EXPERIMENTS.md §Reproduction table (paper claim vs measured).

    PYTHONPATH=src python -m benchmarks.report --ci-summary [bench.json]
        Compact kernel/serving table for $GITHUB_STEP_SUMMARY: windows/s
        from the serve smoke probe plus the refresh-attention FLOPs
        ledger of the block-sparse kernel path.

    PYTHONPATH=src python -m benchmarks.report --compare base.json cur.json
        Bench-regression gate: delta table (markdown) of the current
        run against a baseline artifact (latest main).  Exits non-zero
        when a FLOP-ledger metric regresses by more than 10% — those
        are deterministic counts, so any drift is a real code change.
        Wall-clock rows (windows/s, t_overhead, kernel microbench us)
        are informational only: shared CI runners are too noisy to
        gate on.
"""
import json
import sys


def _get(r, *keys, default="—"):
    cur = r
    for k in keys:
        if not isinstance(cur, dict) or k not in cur:
            return default
        cur = cur[k]
    return cur


def reproduction_table(r) -> str:
    def g(*keys, default="—"):
        return _get(r, *keys, default=default)

    rows = [
        ("E2E speedup (Fig. 11)", "up to 2.97x (InternVL3)",
         f"wall {g('latency','codecflow','speedup_vs_fullcomp'):.2f}x / "
         f"FLOP-bound {g('latency','codecflow','speedup_flop_bound'):.2f}x"
         if isinstance(g("latency","codecflow","speedup_vs_fullcomp"), float) else "—"),
        ("Transmission reduction (Fig. 11)", "2.12x",
         f"{g('latency','transmission','reduction_x'):.2f}x vs all-intra"
         if isinstance(g("latency","transmission","reduction_x"), float) else "—"),
        ("F1 drop (Fig. 12)", "0 ~ 0.08",
         f"{g('accuracy','f1_drop_codecflow'):+.3f}"
         if isinstance(g("accuracy","f1_drop_codecflow"), float) else "—"),
        ("Token reduction (Fig. 13a)", "~85% vs Full-Comp",
         f"{g('resources','codecflow','token_reduction')*100:.0f}%"
         if isinstance(g("resources","codecflow","token_reduction"), float) else "—"),
        ("FLOP reduction (Fig. 13b)", "~87%",
         f"{g('resources','codecflow','flop_reduction')*100:.0f}%"
         if isinstance(g("resources","codecflow","flop_reduction"), float) else "—"),
        ("Pruning falls with motion (Fig. 14)", "50/27/13% low/med/high",
         f"{g('motion','low','pruned_frac')*100:.0f}/"
         f"{g('motion','medium','pruned_frac')*100:.0f}/"
         f"{g('motion','high','pruned_frac')*100:.0f}% "
         f"(monotone={g('motion','pruning_monotone')})"
         if isinstance(g("motion","low","pruned_frac"), float) else "—"),
        ("Combined ablation saves most (Fig. 15)", "3.87x combined",
         f"combined_saves_most={g('ablation','combined_saves_most')}, "
         f"flops -{g('ablation','codecflow','flop_reduction')*100:.0f}% vs "
         f"prune-only -{g('ablation','prune_only','flop_reduction')*100:.0f}% / "
         f"refresh-only -{g('ablation','refresh_only','flop_reduction')*100:.0f}%"
         if isinstance(g("ablation","codecflow","flop_reduction"), float) else "—"),
        ("Smaller stride -> better F1 (Fig. 16)", "F1 0.84->0.89 at 20%",
         " / ".join(f"s{k}: F1={v['f1']:.2f}"
                    for k, v in sorted(g("sensitivity","stride",
                                         default={}).items(),
                                       key=lambda kv: int(kv[0])))
         or "—"),
        ("Higher tau -> fewer tokens, lower F1 (Fig. 17)", "F1 0.81->0.73",
         " / ".join(f"tau{k}: F1={v['f1']:.2f},tok={v['tokens']:.0f}"
                    for k, v in sorted(g("sensitivity","mv", default={}).items(),
                                       key=lambda kv: float(kv[0])))
         or "—"),
        ("Larger GOP -> fewer refreshes (Fig. 18)", "F1 .77/.79/.81, latency falls",
         " / ".join(f"g{k}: F1={v['f1']:.2f},refresh={v['refreshed']:.0f}"
                    for k, v in sorted(g("sensitivity","gop", default={}).items(),
                                       key=lambda kv: int(kv[0])))
         or "—"),
        ("Decision overhead (Fig. 19)", "~4% of latency",
         f"{g('overhead','share_of_window')*100:.1f}%"
         if isinstance(g("overhead","share_of_window"), float) else "—"),
    ]
    out = ["| claim | paper | this repo |", "|---|---|---|"]
    out += [f"| {name} | {paper} | {ours} |" for name, paper, ours in rows]
    return "\n".join(out)


def ci_summary(r) -> str:
    """Kernel CI step summary: throughput + refresh-attention FLOPs."""
    k = r.get("kernels", {})
    host = k.get("host_platform", "unknown")
    out = ["## Kernel bench smoke", ""]
    if host != "tpu":
        out += [f"wall-clock rows measured on **{host}** — the Pallas "
                "kernels run their jnp oracles here, so wall numbers "
                "track the oracle, not device wins; the FLOP/byte "
                "ledgers below are hardware-independent", ""]
    else:
        out += [f"wall-clock rows measured on **{host}**", ""]
    out += ["| metric | value |", "|---|---|"]
    for label, key, fmt in [
        ("mv_sad oracle", "mv_sad", "{:.0f} us"),
        ("rope_shift oracle", "rope_shift", "{:.0f} us"),
        ("ssd_scan oracle", "ssd_scan", "{:.0f} us"),
        ("prefill attention oracle", "attention", "{:.0f} us"),
        ("refresh attn, dense-mask path", "refresh_dense_us", "{:.0f} us"),
        ("refresh attn, flash_refresh dispatch", "refresh_dispatch_us",
         "{:.0f} us"),
        (f"refresh dense/sparse wall speedup ({host})",
         "refresh_wall_speedup_x", "{:.2f}x"),
        ("codecflow windows/s (smoke)", "smoke_codecflow_windows_per_s",
         "{:.2f}"),
        ("fullcomp windows/s (smoke)", "smoke_fullcomp_windows_per_s",
         "{:.2f}"),
        ("codecflow TTFT p50 (smoke)", "smoke_codecflow_ttft_p50",
         "{:.3f} s"),
        ("codecflow TTFT p99 (smoke)", "smoke_codecflow_ttft_p99",
         "{:.3f} s"),
        ("codecflow KV bytes/stream (smoke)",
         "smoke_codecflow_kv_bytes_per_stream", "{:,.0f} B"),
    ]:
        v = k.get(key)
        out.append(f"| {label} | {fmt.format(v) if v is not None else '—'} |")
    ok_n = k.get("dispatch_kernel_decisions")
    fb_n = k.get("dispatch_fallback_decisions")
    if ok_n is not None:
        flag = " ⚠️ silent oracle fallback" if fb_n else ""
        out.append(
            f"| kernel dispatch coverage | {ok_n} kernel-eligible / "
            f"{fb_n} fallback{flag} |"
        )
    out += ["", "### Packed ViT encode (padded vs packed pruned path)", ""]
    out += [f"| keep_ratio | padded patches/s | packed patches/s | "
            f"wall speedup ({host}) | FLOPs saved | buffer fill |",
            "|---|---|---|---|---|---|"]
    any_pack = False
    for tag in ("0.5", "0.25"):
        pps_pad = k.get(f"vitpack_{tag}_padded_patches_s")
        pps_pack = k.get(f"vitpack_{tag}_packed_patches_s")
        fd = k.get(f"vitpack_{tag}_flops_padded")
        fp = k.get(f"vitpack_{tag}_flops_packed")
        fill = k.get(f"vitpack_{tag}_fill")
        if None in (pps_pad, pps_pack, fd, fp, fill):
            continue
        any_pack = True
        wall = k.get(f"vitpack_{tag}_wall_speedup_x")
        out.append(
            f"| {tag} | {pps_pad:,.0f} | {pps_pack:,.0f} | "
            f"{'—' if wall is None else f'{wall:.2f}x'} | "
            f"**{100 * (1 - fp / fd):.0f}%** ({fd / fp:.2f}x) | "
            f"{100 * fill:.0f}% |"
        )
    if any_pack:
        ms = k.get("vitpack_min_flop_speedup")
        util = k.get("smoke_codecflow_pack_util")
        out.append("")
        out.append(
            f"min FLOP-ledger speedup "
            f"{'—' if ms is None else f'{ms:.2f}x'} (gate: >= 1.5x at "
            f"keep_ratio <= 0.5); serve-smoke ViT lane utilization "
            f"{'—' if util is None else f'{100 * util:.0f}%'} "
            f"(`docs/vit_packing.md`)"
        )
    else:
        out.append("| (vit packing section missing from JSON) | | | | | |")
    out += ["", "### Refresh-attention block sparsity", ""]
    out += ["| | dense | block-sparse |", "|---|---|---|"]
    tiles_t, tiles_v = k.get("refresh_tiles_total"), k.get("refresh_tiles_visited")
    fd, fs = k.get("refresh_flops_dense"), k.get("refresh_flops_sparse")
    if None not in (tiles_t, tiles_v, fd, fs):
        out.append(f"| (q, kv) tiles | {tiles_t} | {tiles_v} |")
        out.append(f"| attention MFLOPs/layer | {fd / 1e6:.1f} | {fs / 1e6:.1f} |")
        out.append(
            f"| | | **{100 * (1 - tiles_v / max(tiles_t, 1)):.0f}% skipped** |"
        )
        out.append("")
        out.append(
            f"layout: n_refresh={k.get('refresh_n_q', '—')} gathered queries "
            f"vs kv_len={k.get('refresh_kv_len', '—')} cache slots "
            f"(`WindowLayout`-static map, `kernels/flash_refresh.py`)"
        )
    else:
        out.append("| (refresh section missing from JSON) | | |")
    st = r.get("streams", {})
    if isinstance(st, dict) and "quant_capacity_ratio" in st:
        out += ["", "### Int8 cold-page KV capacity (fixed slab bytes)", ""]
        out += ["| | bf16 | int8 cold pages |", "|---|---|---|"]
        out.append(f"| streams admitted | {st.get('bf16_streams', '—')} | "
                   f"{st.get('quant_streams', '—')} |")
        out.append(f"| bytes/stream | {st.get('bf16_bytes_per_stream', 0):,} "
                   f"| {st.get('quant_bytes_per_stream', 0):,} |")
        out.append(
            f"| | | **{st['quant_capacity_ratio']:.2f}x** (gate: >= 1.7x) |")
        err = st.get("quant_max_logit_err")
        out.append("")
        out.append(
            f"answers identical across precisions: "
            f"{st.get('quant_answers_equal', '—')}; max abs logit error "
            f"{'—' if err is None else f'{err:.4f}'} (`docs/paged_kv.md`)")
    return "\n".join(out)


# ----------------------------------------------------------------------
# bench-regression gate (CI --compare mode)
# ----------------------------------------------------------------------
#: Deterministic FLOP/byte-ledger metrics: any >10% regression fails the
#: job.  Direction "down" = smaller is better.  Keys default to the
#: ``["kernels"]`` section; a ``section/key`` form reads another bench's
#: output (e.g. the stream-capacity ratio under ``["streams"]``).
GATED_METRICS = (
    ("smoke_codecflow_flops_prefill", "down", "codecflow prefill FLOPs"),
    ("smoke_fullcomp_flops_prefill", "down", "fullcomp prefill FLOPs"),
    ("smoke_codecflow_refreshed_per_window", "down",
     "refreshed tokens / window"),
    ("smoke_codecflow_kv_bytes_per_stream", "down",
     "codecflow KV bytes/stream"),
    ("refresh_flops_sparse", "down", "refresh attn FLOPs (block-sparse)"),
    ("refresh_tiles_visited", "down", "refresh kv tiles visited"),
    ("vitpack_min_flop_speedup", "up", "ViT packing FLOP speedup"),
    ("dispatch_fallback_decisions", "down", "silent kernel fallbacks"),
    ("streams/quant_capacity_ratio", "up",
     "int8 cold-page stream capacity ratio"),
)

#: Wall-clock metrics: reported in the delta table, never gated (CI
#: runner noise).  Direction only orients the arrow rendering.  The
#: latency-quantile / TTFT rows come from the scheduler's own samples
#: (docs/async_scheduler.md) and stay informational for the same
#: reason windows/s does.
INFO_METRICS = (
    ("refresh_wall_speedup_x", "up", "refresh dense/sparse wall speedup"),
    ("vitpack_0.5_wall_speedup_x", "up", "ViT pack wall speedup (keep 0.5)"),
    ("vitpack_0.25_wall_speedup_x", "up", "ViT pack wall speedup (keep 0.25)"),
    ("smoke_codecflow_windows_per_s", "up", "codecflow windows/s"),
    ("smoke_fullcomp_windows_per_s", "up", "fullcomp windows/s"),
    ("smoke_codecflow_ttft_p50", "down", "codecflow TTFT p50"),
    ("smoke_codecflow_ttft_p99", "down", "codecflow TTFT p99"),
    ("smoke_codecflow_t_overhead", "down", "codecflow t_overhead/window"),
    ("smoke_fullcomp_t_overhead", "down", "fullcomp t_overhead/window"),
    ("refresh_dispatch_us", "down", "flash_refresh dispatch us"),
    ("mv_sad", "down", "mv_sad us"),
    ("rope_shift", "down", "rope_shift us"),
    ("ssd_scan", "down", "ssd_scan us"),
)

REGRESSION_THRESHOLD = 0.10


def _rel_regression(base: float, cur: float, direction: str) -> float:
    """Regression fraction (positive = worse) in the gated direction."""
    if base == 0:
        return float("inf") if (cur > 0 and direction == "down") else 0.0
    d = (cur - base) / abs(base)
    return d if direction == "down" else -d


def _metric(r: dict, key: str):
    """Gate-key lookup: bare keys read ``["kernels"]``; ``section/key``
    reads another bench section of the results JSON."""
    section, _, name = key.rpartition("/")
    sec = r.get(section or "kernels")
    return sec.get(name) if isinstance(sec, dict) else None


def compare(base: dict, cur: dict,
            threshold: float = REGRESSION_THRESHOLD):
    """Returns (markdown report, list of gate-failure strings)."""
    failures = []
    host_b = _metric(base, "host_platform")
    host_c = _metric(cur, "host_platform")
    out = ["## Bench regression vs baseline", "",
           f"wall-clock rows: baseline on **{host_b or 'unknown'}**, "
           f"current on **{host_c or 'unknown'}** — never gated", "",
           "| metric | baseline | current | delta | gate |",
           "|---|---|---|---|---|"]

    def fmt(v):
        if v is None:
            return "—"
        return f"{v:.4g}" if isinstance(v, float) else str(v)

    for key, direction, label in GATED_METRICS + INFO_METRICS:
        gated = (key, direction, label) in GATED_METRICS
        b, c = _metric(base, key), _metric(cur, key)
        if b is None or c is None:
            out.append(f"| {label} | {fmt(b)} | {fmt(c)} | — | "
                       f"{'skipped (missing)' if gated else 'info'} |")
            continue
        reg = _rel_regression(float(b), float(c), direction)
        delta = "n/a" if b == 0 else f"{(float(c) - float(b)) / abs(float(b)):+.1%}"
        if not gated:
            verdict = "info"
        elif reg > threshold:
            verdict = f"**FAIL** (> {threshold:.0%})"
            failures.append(
                f"{label}: {fmt(b)} -> {fmt(c)} "
                f"({delta}, allowed {threshold:.0%})"
            )
        else:
            verdict = "ok"
        out.append(f"| {label} | {fmt(b)} | {fmt(c)} | {delta} | {verdict} |")

    out.append("")
    if failures:
        out.append(f"**{len(failures)} FLOP-ledger regression(s)** — "
                   "deterministic counts moved; this is a code change, "
                   "not runner noise:")
        out += [f"- {f}" for f in failures]
    else:
        out.append("No FLOP-ledger regressions; wall-clock rows are "
                   "informational.")
    return "\n".join(out), failures


def main() -> None:
    args = [a for a in sys.argv[1:]]
    mode = "repro"
    if "--ci-summary" in args:
        mode = "ci"
        args.remove("--ci-summary")
    if "--compare" in args:
        args.remove("--compare")
        assert len(args) == 2, "--compare needs: baseline.json current.json"
        base = json.load(open(args[0]))
        cur = json.load(open(args[1]))
        report, failures = compare(base, cur)
        print(report)
        sys.exit(1 if failures else 0)
    path = args[0] if args else "experiments/bench_results.json"
    r = json.load(open(path))
    print(ci_summary(r) if mode == "ci" else reproduction_table(r))


if __name__ == "__main__":
    main()
