"""Tier-1 wiring for scripts/obs_lint.py — since PR 6 a compatibility
shim over graftlint's host-sync rule (scripts/graftlint/rules/
host_sync.py): the package must stay free of per-step host-sync smells
(.item(), time.time() for durations, float(<call>) in step-cadence
paths) modulo the documented allowlist — a regression here silently
kills async-dispatch overlap, which no functional test can see.

These tests deliberately keep loading obs_lint.py BY PATH with its
historical surface (scan/_Finder/HOT_PATHS/allowed/load_allowlist):
they are the contract the shim exists to honor. The full multi-rule
analyzer is covered by tests/test_graftlint.py."""
from __future__ import annotations

import importlib.util
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "obs_lint", REPO / "scripts" / "obs_lint.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_package_has_no_unallowlisted_host_sync_smells():
    # in-process: scan() is the same entry main() wraps
    findings = _load_lint().scan()
    pretty = "\n".join(f"{r}:{n}: {s}\n    {ln}"
                       for r, n, s, ln in findings)
    assert not findings, f"obs_lint found host-sync smells:\n{pretty}"


def test_lint_detects_each_smell(tmp_path):
    """The lint's teeth: each smell class is actually caught (a lint
    that silently stops matching is worse than none)."""
    lint = _load_lint()
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import time\n"
        "def hot(metrics, loss_fn, x):\n"
        "    a = metrics['loss'].item()\n"
        "    t = time.time()\n"
        "    b = float(loss_fn(x))\n"
        "    return a, t, b\n"
        "# .item() in a comment must NOT trip the AST lint\n")
    finder = lint._Finder("torchbooster_tpu/utils.py",
                          bad.read_text().splitlines(), hot=True)
    import ast

    finder.visit(ast.parse(bad.read_text()))
    smells = [s for _, _, s, _ in finder.findings]
    assert len(smells) == 3
    assert any(".item()" in s for s in smells)
    assert any("time.time()" in s for s in smells)
    assert any("float(<call>)" in s for s in smells)


def test_hot_paths_cover_step_cadence_serving_files():
    """HOT_PATHS must keep covering the serving hot loop — including
    speculative.py, whose host-side drafting runs between every verify
    dispatch. The prefix rule covers new files automatically; this
    pins it so a HOT_PATHS refactor to per-file entries cannot
    silently drop one."""
    lint = _load_lint()
    # the PR 13 fork/tree decoding paths (CoW parallel sampling in
    # kv_pages/engine/batcher, tree drafting + accept walk in
    # speculative.py) all run at step cadence inside these files —
    # the pins below are what keeps them under the host-sync rule
    for rel in ("torchbooster_tpu/serving/engine.py",
                "torchbooster_tpu/serving/batcher.py",
                "torchbooster_tpu/serving/speculative.py",
                "torchbooster_tpu/serving/kv_pages.py",
                # the front door's async scheduler loop pumps step()
                # between dispatches — a host sync there stalls the
                # decode pipeline exactly like one in the batcher
                "torchbooster_tpu/serving/frontend/server.py",
                "torchbooster_tpu/serving/frontend/scheduler.py",
                # the loadgen replay driver pumps step() on the
                # decode loop's own thread and the capture hook runs
                # per submit — step-cadence both (PR 11); the pacer's
                # wall-clock timestamps are reasoned allowlist
                # entries, never durations
                "torchbooster_tpu/serving/loadgen/replay.py",
                "torchbooster_tpu/serving/loadgen/workload.py",
                "torchbooster_tpu/serving/loadgen/report.py",
                # the tensor-parallel sharded decode driver (PR 12):
                # its wrappers run on the step cadence around every
                # compiled decode/verify dispatch
                "torchbooster_tpu/serving/tp.py",
                # the fleet router (PR 14): routing decisions, the
                # fleet step loop, and readmission all run between
                # every replica's decode dispatches — as step-cadence
                # as the batcher loop they pump
                "torchbooster_tpu/serving/router/fleet.py",
                "torchbooster_tpu/serving/router/routing.py",
                "torchbooster_tpu/serving/router/replica.py",
                # the fleet signal plane (PR 17): health observation
                # runs inside the fleet step loop, audit records land
                # per routing decision, and the burn engine ticks on
                # the exporter thread next to the serving loop — all
                # must stay under the host-sync rule
                "torchbooster_tpu/serving/router/health.py",
                "torchbooster_tpu/serving/router/audit.py",
                "torchbooster_tpu/observability/slo.py",
                # the paged flash-decode kernel wrapper runs inside
                # the compiled decode/verify steps (PR 8)
                "torchbooster_tpu/ops/paged_attention.py",
                # PR 19: the adapter registry's lane bookkeeping runs
                # at every admit/retire, and the in-kernel dequant
                # wrappers run inside every compiled matmul — both
                # step-cadence
                "torchbooster_tpu/serving/adapters.py",
                "torchbooster_tpu/models/quant.py"):
        assert (REPO / rel).exists(), f"{rel} moved without this test"
        assert any(rel.startswith(h) for h in lint.HOT_PATHS), (
            f"{rel} fell out of obs_lint HOT_PATHS")


def test_allowlist_matches_by_path_and_substring():
    lint = _load_lint()
    entries = [("torchbooster_tpu/metrics.py", "float(jax.device_get")]
    assert lint.allowed("torchbooster_tpu/metrics.py",
                        "x = float(jax.device_get(v))", entries)
    assert not lint.allowed("torchbooster_tpu/utils.py",
                            "x = float(jax.device_get(v))", entries)
    assert not lint.allowed("torchbooster_tpu/metrics.py",
                            "x = v.item()", entries)


def test_allowlist_entries_still_match_something():
    """Stale allowlist entries (code moved on) must be pruned, or the
    allowlist rots into a blanket waiver."""
    lint = _load_lint()
    entries = lint.load_allowlist()
    assert entries, "allowlist unexpectedly empty"
    for path, pattern in entries:
        source = (REPO / path).read_text()
        assert pattern in source, (
            f"stale allowlist entry: {path}:{pattern}")


def test_shim_agrees_with_graftlint_host_sync_rule():
    """The shim and the re-homed rule are ONE implementation: the
    legacy scan()'s findings must equal graftlint's unsuppressed
    host-sync findings over the package (same files, same allowlist
    semantics). If the rule and the shim ever fork, this fails."""
    import sys

    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    from scripts.graftlint import run_scan
    from scripts.graftlint.rules import RULES_BY_ID

    legacy = {(r, n, ln) for r, n, _, ln in _load_lint().scan()}
    result = run_scan(rules=[RULES_BY_ID["host-sync"]])
    unified = {(f.path, f.line, f.source)
               for f in result.findings if f.rule == "host-sync"}
    assert legacy == unified

    # tree-level equality alone is vacuous while the package is clean
    # (set() == set() tells us nothing about a forked detector) — the
    # two surfaces must also agree on a SEEDED fixture with known
    # smells, non-emptily
    import ast

    from scripts.graftlint.core import FileContext

    source = ("import time\n"
              "def hot(m, loss_fn, x):\n"
              "    return m.item(), time.time(), float(loss_fn(x))\n")
    rel = "torchbooster_tpu/utils.py"   # a HOT path
    ctx = FileContext(rel, source, ast.parse(source))
    via_rule = {(f.line, f.message)
                for f in RULES_BY_ID["host-sync"].check_file(ctx)}
    finder = _load_lint()._Finder(rel, source.splitlines(), hot=True)
    finder.visit(ast.parse(source))
    via_shim = {(ln, smell) for _, ln, smell, _ in finder.findings}
    assert via_rule == via_shim
    assert len(via_rule) == 3
