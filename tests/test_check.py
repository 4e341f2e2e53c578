"""Tests for the tools.check static analyzer.

Two halves: (1) every seeded fixture violation under
``tests/fixtures/check/`` is flagged (and the deliberately-clean
constructs in the same files are not); (2) the real tree lints clean
and both audits pass — the same bar the CI static-analysis job gates
on.
"""

import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from tools.check import lints  # noqa: E402
from tools.check.lints import (  # noqa: E402
    RULE_DONATION,
    RULE_DTYPE,
    RULE_EVENTS,
    RULE_HOST_SYNC,
    RULE_RECOMPILE,
    RULE_SHARED,
    RULE_STALE,
)

FIXTURES = ROOT / "tests" / "fixtures" / "check"


def _lint(rel: str):
    path = FIXTURES / rel
    return lints.lint_source(path.read_text(), str(path))


# ----------------------------------------------------------------------
# seeded fixtures: one per rule
# ----------------------------------------------------------------------
def test_host_sync_strict_fixture():
    fs = _lint("host_sync_strict.py")
    assert [f.rule for f in fs] == [RULE_HOST_SYNC] * 3
    msgs = " | ".join(f.message for f in fs)
    assert "np.asarray" in msgs
    assert "float()" in msgs
    assert ".item()" in msgs and "'_helper'" in msgs  # strict via callee
    # the module-level asarray (outside any jit scope) is not flagged
    src = (FIXTURES / "host_sync_strict.py").read_text()
    clean_line = next(
        i for i, l in enumerate(src.splitlines(), 1) if "CLEAN" in l
    )
    assert all(f.line != clean_line for f in fs)


def test_host_sync_adjacent_fixture():
    fs = _lint("serving/host_sync_adjacent.py")
    assert len(fs) == 1 and fs[0].rule == RULE_HOST_SYNC
    assert "dispatch path" in fs[0].message and "'run'" in fs[0].message
    # float() is permitted in adjacent (non-strict) scopes: 'tail' clean


def test_host_sync_adjacent_needs_serving_path():
    # same source outside a serving/ path: the adjacent rule stays off
    src = (FIXTURES / "serving" / "host_sync_adjacent.py").read_text()
    assert lints.lint_source(src, "tests/fixtures/check/elsewhere.py") == []


def test_recompile_loop_fixture():
    fs = _lint("recompile_loop.py")
    assert [f.rule for f in fs] == [RULE_RECOMPILE]
    assert "inside a loop" in fs[0].message


def test_recompile_closure_fixture():
    fs = _lint("recompile_closure.py")
    assert [f.rule for f in fs] == [RULE_RECOMPILE]
    assert "mutable container 'table'" in fs[0].message


def test_recompile_static_fixture():
    fs = _lint("recompile_static.py")
    assert [f.rule for f in fs] == [RULE_RECOMPILE] * 2
    assert all("static argument 'n'" in f.message for f in fs)
    # the bucketed caller routes through a bucket table: not flagged
    src = (FIXTURES / "recompile_static.py").read_text()
    bucketed_line = next(
        i for i, l in enumerate(src.splitlines(), 1)
        if "padded(x, n=n)" in l
    )
    assert all(f.line != bucketed_line for f in fs)


def test_dtype_fixture():
    fs = _lint("kernels/dtype_mix.py")
    assert [f.rule for f in fs] == [RULE_DTYPE] * 2
    msgs = " | ".join(f.message for f in fs)
    assert "mixes explicit float32 and bfloat16" in msgs
    assert "preferred_element_type" in msgs
    # accum_ok (pinned accumulator) contributes nothing: only 2 findings


def test_dtype_needs_kernel_path():
    src = (FIXTURES / "kernels" / "dtype_mix.py").read_text()
    assert lints.lint_source(src, "tests/fixtures/check/elsewhere.py") == []


def test_waiver_suppresses_finding():
    assert _lint("waived_ok.py") == []


def test_stale_waiver_reported():
    fs = _lint("stale_waiver.py")
    assert [f.rule for f in fs] == [RULE_STALE]
    assert "suppresses nothing" in fs[0].message
    assert "left over after a refactor" in fs[0].message


# ----------------------------------------------------------------------
# concurrency-era passes: donation / shared-state / event-protocol
# ----------------------------------------------------------------------
def test_donation_use_after_fixture():
    fs = _lint("donation_use_after.py")
    assert [f.rule for f in fs] == [RULE_DONATION] * 3
    msgs = [f.message for f in fs]
    assert any("read of donated buffer 'pool.slab'" in m for m in msgs)
    assert any("never rebound" in m for m in msgs)
    assert any("alias 'keep'" in m and "survives" in m for m in msgs)
    # linear_ok (rebind then hands off) contributes nothing
    src = (FIXTURES / "donation_use_after.py").read_text()
    ok_line = next(
        i for i, l in enumerate(src.splitlines(), 1)
        if "def linear_ok" in l
    )
    assert all(f.line < ok_line for f in fs)


def test_donation_captured_fixture():
    fs = _lint("donation_captured.py")
    assert [f.rule for f in fs] == [RULE_DONATION]
    assert "captured by nested closure 'debug'" in fs[0].message


def test_shared_state_unguarded_fixture():
    fs = _lint("shared_state_unguarded.py")
    assert [f.rule for f in fs] == [RULE_SHARED] * 2
    msgs = " | ".join(f.message for f in fs)
    assert "worker-thread mutation" in msgs
    assert "main-loop read" in msgs
    assert "'MiniSched.count'" in msgs
    # the lock-guarded twin (busy) and immutable cfg are not flagged
    assert "busy" not in msgs and "cfg" not in msgs


def test_shared_state_waiver_suppresses():
    assert _lint("shared_state_waived.py") == []


def test_shared_state_inventory_rows():
    import ast

    from tools.check import concurrency

    src = (FIXTURES / "shared_state_unguarded.py").read_text()
    _, rows = concurrency.analyze(ast.parse(src), "fixture")
    by_attr = {r.attr: r for r in rows}
    assert by_attr["count"].label == "VIOLATION"
    assert by_attr["count"].thread_rw == "-W"
    assert by_attr["count"].main_rw == "R-"
    assert by_attr["busy"].label == "lock-guarded"
    assert by_attr["cfg"].label == "immutable-after-init"


def test_events_order_fixture():
    fs = _lint("events_order.py")
    assert [f.rule for f in fs] == [RULE_EVENTS] * 2
    msgs = " | ".join(f.message for f in fs)
    assert "no preceding WindowDone" in msgs
    assert "after StreamDone" in msgs
    # good_emit and the n_windows=0 zero-window form are not flagged
    assert all("bad_emit" in f.message for f in fs)


def test_stale_waivers_cover_new_rules():
    fs = _lint("stale_waiver_new.py")
    assert [f.rule for f in fs] == [RULE_STALE] * 3
    msgs = " | ".join(f.message for f in fs)
    for rule in (RULE_DONATION, RULE_SHARED, RULE_EVENTS):
        assert f"allow-{rule}" in msgs


def test_donation_sites_tracked_on_real_tree():
    """The pass must actually *see* the serving donation sites — an
    empty site table would mean the registry regressed, and linearity
    was vacuously true."""
    import ast

    from tools.check import donation

    src = (ROOT / "src/repro/serving/api.py").read_text()
    findings, sites = donation.analyze(ast.parse(src), "api.py")
    assert findings == []
    callees = {s.callee for s in sites}
    assert {"_jit_paged_fresh", "_jit_paged_reuse", "_jit_demote",
            "_jit_decode_paged", "jit_selective"} <= callees
    assert all(s.status == "linear" for s in sites)


def test_scheduler_inventory_on_real_tree():
    """stage_busy (the one attr both ingest workers and the main loop
    write) must classify lock-guarded; the metrics accumulators the
    issue asked to audit must be main-thread-only, not violations."""
    import ast

    from tools.check import concurrency

    src = (ROOT / "src/repro/serving/scheduler.py").read_text()
    findings, rows = concurrency.analyze(ast.parse(src), "scheduler.py")
    assert findings == []
    by_attr = {r.attr: r for r in rows if r.cls == "Scheduler"}
    assert by_attr["stage_busy"].label == "lock-guarded"
    for attr in ("kernel_fallbacks", "ttft",
                 "windows_served", "vit_patches", "vit_slots"):
        assert by_attr[attr].label == "main-thread-only", attr
    assert by_attr["pipeline"].label == "immutable-after-init"


# ----------------------------------------------------------------------
# the real tree: the bar CI gates on
# ----------------------------------------------------------------------
def test_repo_lints_clean():
    findings = lints.lint_paths(
        [str(ROOT / "src"), str(ROOT / "benchmarks")]
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_dispatch_audit_no_silent_fallbacks():
    from tools.check import dispatch_audit

    rows, failures = dispatch_audit.run_audit()
    assert failures == [], "\n".join(failures)
    # every geometry the registry promises to the kernel actually
    # dispatched to it (no silent oracle fallback)
    for r in rows:
        if r.expect == "kernel":
            assert r.observed == "kernel", (r.op, r.geometry, r.observed)
    table = dispatch_audit.coverage_table(rows)
    assert "| kernel | geometry |" in table


def test_recompile_audit_within_budget():
    from tools.check import recompile_audit

    results, failures = recompile_audit.run_audit()
    assert failures == [], "\n".join(failures)
    by_op = {r.op: r for r in results}
    assert by_op["flash_packed"].distinct_keys <= by_op["flash_packed"].budget
    assert by_op["flash_refresh"].distinct_keys <= 20  # one per (layout, fleet)


# ----------------------------------------------------------------------
# CLI exit codes (what the CI job actually invokes)
# ----------------------------------------------------------------------
@pytest.mark.parametrize(
    "paths,expect_rc",
    [
        (["src", "benchmarks"], 0),
        (["tests/fixtures/check"], 1),
    ],
)
def test_cli_exit_codes(paths, expect_rc, tmp_path):
    import os

    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    summary = tmp_path / "summary.md"
    proc = subprocess.run(
        [
            sys.executable, "-m", "tools.check", *paths,
            "--no-audit", "--summary", str(summary),
        ],
        cwd=ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == expect_rc, proc.stdout + proc.stderr
    assert summary.exists()
    if expect_rc == 0:
        assert "clean" in proc.stdout
    else:
        assert "FAILED" in proc.stdout
