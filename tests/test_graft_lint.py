"""graft-lint framework + passes (ISSUE 4).

Covers: the full-repo clean gate (THE tier-1 regression guard: new
findings can't merge), per-pass positive/negative fixtures, the
suppression syntax, baseline semantics (within / grown / shrunk), the
--changed git scoping, shim CLI compatibility, and the flags registry
contract. Fixture snippets live in tests/fixtures/graft_lint/ and are
parsed, never imported.
"""
from __future__ import annotations

import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "graft_lint"
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.graft_lint import (  # noqa: E402
    core, get_passes, load_baseline, run_collect,
)
from tools.graft_lint.passes.collective_order import (  # noqa: E402
    CollectiveOrderPass,
)
from tools.graft_lint.passes.fault_points import (  # noqa: E402
    FaultPointsPass,
)
from tools.graft_lint.passes.flags_hygiene import (  # noqa: E402
    FlagsHygienePass,
)
from tools.graft_lint.passes.host_sync import HostSyncPass  # noqa: E402
from tools.graft_lint.passes.trace_safety import (  # noqa: E402
    TraceSafetyPass,
)


def _run(passes, paths=None, **kw):
    return run_collect(passes, paths=paths, repo=REPO, **kw)


# -- the tier-1 gate ---------------------------------------------------------

@pytest.fixture(scope="module")
def full_run():
    """One whole-repo run shared by the gate tests (it's the expensive
    part: every pass over every in-scope file)."""
    return _run(get_passes(), baseline=load_baseline())


def test_full_repo_clean_under_baseline(full_run):
    """`python -m tools.graft_lint` exits 0 on the repo: every finding
    is fixed, suppressed with a rationale, or baselined (ISSUE 4
    acceptance criterion). New violations of ANY pass fail here."""
    assert full_run.active == [], \
        "\n".join(f.render() for f in full_run.active)


def test_baseline_counts_are_exact(full_run):
    """The baseline may only SHRINK: once a grandfathered finding is
    fixed, `python -m tools.graft_lint --write-baseline` must be run so
    the debt count ratchets down (stale entries fail here)."""
    assert full_run.stale_baseline == [], (
        f"baseline overcounts {full_run.stale_baseline} — a fix "
        f"landed; regenerate with "
        f"`python -m tools.graft_lint --write-baseline`")


# -- trace-safety ------------------------------------------------------------

def test_trace_safety_catches_bug_classes():
    res = _run([TraceSafetyPass()],
               paths=[FIXTURES / "trace_safety_bad.py"])
    msgs = [f.message for f in res.active]
    assert len(msgs) == 9
    assert sum("global" in m for m in msgs) == 1
    assert sum("print()" in m for m in msgs) == 2   # incl. nested def
    assert sum("time.*" in m for m in msgs) == 1
    assert sum("host RNG" in m for m in msgs) == 2  # random + np.random
    assert sum("float() on a tensor" in m for m in msgs) == 1
    assert sum(".numpy()" in m for m in msgs) == 1
    assert sum(".item()" in m for m in msgs) == 1


def test_trace_safety_negative():
    res = _run([TraceSafetyPass()],
               paths=[FIXTURES / "trace_safety_ok.py"])
    assert res.active == [], "\n".join(f.render() for f in res.active)


# -- host-sync ---------------------------------------------------------------

def test_host_sync_catches_and_spares_host_code():
    res = _run([HostSyncPass()], paths=[FIXTURES / "host_sync_bad.py"])
    assert len(res.active) == 2
    assert all(f.severity == "warning" for f in res.active)
    lines = sorted(f.line for f in res.active)
    # float(arr[i]) in the loop and t.mean().item(); fine_host's
    # float(np_array.sum()) must NOT fire
    assert "float" in res.active[0].message or \
        "item" in res.active[0].message
    assert len(lines) == 2


# -- collective-order --------------------------------------------------------

def test_collective_order_catches_divergence():
    res = _run([CollectiveOrderPass()],
               paths=[FIXTURES / "collective_order_bad.py"])
    msgs = [f.message for f in res.active]
    assert len(msgs) == 3
    assert sum("inside a rank-conditional branch" in m for m in msgs) == 2
    assert sum("after the rank-conditional early return" in m
               for m in msgs) == 1
    assert any("lax.psum" in m for m in msgs)


def test_collective_order_negative():
    res = _run([CollectiveOrderPass()],
               paths=[FIXTURES / "collective_order_ok.py"])
    assert res.active == [], "\n".join(f.render() for f in res.active)


def test_collective_order_group_subsets_legal():
    """ISSUE 6 / MPMD prereq: a collective gated on `rank in
    group.ranks` (or `.process_ids`, or past a non-member early return)
    is legal FOR THAT GROUP — subgroup recovery barriers and
    degraded-world re-formation take exactly this shape."""
    res = _run([CollectiveOrderPass()],
               paths=[FIXTURES / "collective_order_subset_ok.py"])
    assert res.active == [], "\n".join(f.render() for f in res.active)


def test_collective_order_group_subsets_still_catch_misuse():
    """The subset exemption is exact: a different group, no group, a
    plain rank gate in between, a member early return, or another
    group's guard all stay flagged."""
    res = _run([CollectiveOrderPass()],
               paths=[FIXTURES / "collective_order_subset_bad.py"])
    msgs = [f.message for f in res.active]
    assert len(msgs) == 5, "\n".join(msgs)
    assert sum("inside a rank-conditional branch" in m for m in msgs) == 3
    assert sum("after the rank-conditional early return" in m
               for m in msgs) == 2


def test_collective_order_covers_quantized_collectives():
    """ISSUE 8: the quantized chain's call names (quantized_all_reduce /
    quantized_reduce_scatter + the lax phase-2 all_gather) are flagged
    inside rank-conditional code — no blind spot for the new ops."""
    res = _run([CollectiveOrderPass()],
               paths=[FIXTURES / "collective_order_quant_bad.py"])
    msgs = [f.message for f in res.active]
    assert len(msgs) == 3, "\n".join(msgs)
    assert any("quantized_reduce_scatter" in m for m in msgs)
    assert any("lax.all_gather" in m for m in msgs)
    assert any("quantized_all_reduce" in m and
               "after the rank-conditional early return" in m
               for m in msgs)


def test_collective_order_covers_zero_sequence():
    """ISSUE 16: the ZeRO rs -> update -> ag call names
    (zero_grad_reduce_scatter / zero_param_all_gather) are flagged
    inside rank-conditional code — the new sharded-update sequence
    stays deadlock-checked."""
    res = _run([CollectiveOrderPass()],
               paths=[FIXTURES / "collective_order_zero_bad.py"])
    msgs = [f.message for f in res.active]
    assert len(msgs) == 2, "\n".join(msgs)
    assert any("zero_param_all_gather" in m and
               "inside a rank-conditional branch" in m for m in msgs)
    assert any("zero_grad_reduce_scatter" in m and
               "after the rank-conditional early return" in m
               for m in msgs)


# -- flags-hygiene -----------------------------------------------------------

def test_flags_hygiene_catches_typo():
    res = _run([FlagsHygienePass()],
               paths=[FIXTURES / "flags_hygiene_bad.py"])
    assert len(res.active) == 1
    assert "FLAGS_bennchmark_typo" in res.active[0].message


def test_flags_hygiene_dead_flag_detection(tmp_path):
    """A registered flag nobody reads is reported dead (full-scope runs
    only); reads keep flags alive; unknown reads are errors."""
    pkg = tmp_path / "paddle_tpu"
    (pkg / "framework").mkdir(parents=True)
    (pkg / "framework" / "core.py").write_text(
        '_flags: dict = {\n'
        '    "FLAGS_used": True,\n'
        '    "FLAGS_dead": 0,\n'
        '}\n')
    (pkg / "consumer.py").write_text(
        'def f(core):\n'
        '    a = core.get_flag("FLAGS_used")\n'
        '    b = core.get_flag("FLAGS_typo")\n'
        '    return a, b\n')
    res = run_collect([FlagsHygienePass()], repo=tmp_path)
    by_sev = {}
    for f in res.active:
        by_sev.setdefault(f.severity, []).append(f.message)
    assert any("FLAGS_typo" in m for m in by_sev.get("error", []))
    assert any("FLAGS_dead" in m for m in by_sev.get("warning", []))
    assert not any("FLAGS_used" in m for m in by_sev.get("warning", []))


def test_flags_registry_parse_matches_runtime():
    """The pass's static view of the registry equals the live dict —
    if the registry literal moves/changes shape, this fails before the
    lint silently goes blind."""
    from tools.graft_lint.passes.flags_hygiene import parse_registry
    static_keys = set(parse_registry(
        REPO / "paddle_tpu" / "framework" / "core.py"))
    from paddle_tpu.framework import core as runtime_core
    assert static_keys == set(runtime_core._flags.keys())


# -- fault-point-hygiene -----------------------------------------------------

def test_fault_point_hygiene_catches_bug_classes():
    res = _run([FaultPointsPass()],
               paths=[FIXTURES / "fault_points_bad.py"])
    msgs = [f.message for f in res.active]
    assert sum("LITERAL" in m for m in msgs) == 1
    assert sum("snake_case" in m for m in msgs) == 2
    # the direct undocumented literal AND the fault_name= default
    assert sum("not listed in the fault-point table" in m
               for m in msgs) == 2
    assert len(msgs) == 5


def test_fault_point_one_module_rule(tmp_path):
    """The same point name in two FILES is an error (ambiguous @N hit
    counts); several sites in one file stay legal (elastic.restore
    fires from two branches of one operation)."""
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text('fault_point("serving.tick")\n'
                 'fault_point("serving.tick")\n')      # same-file: fine
    b.write_text('fault_point("serving.tick")\n')      # cross-file: not
    res = _run([FaultPointsPass()], paths=[a, b])
    assert len(res.active) == 1
    assert "already lives in" in res.active[0].message
    assert res.active[0].path.endswith("b.py")


def test_fault_point_serving_sites_documented_and_clean():
    """The new serving.* chaos levers exist, are documented, and the
    serving module passes the hygiene bar."""
    from tools.graft_lint.passes.fault_points import parse_runbook_table
    table = parse_runbook_table(
        REPO / "tools" / "FAULT_POINTS.md")
    assert {"serving.tick", "serving.admit",
            "serving.page_alloc"} <= table
    res = _run([FaultPointsPass()],
               paths=[REPO / "paddle_tpu" / "inference" / "serving.py"])
    assert res.active == [], "\n".join(f.render() for f in res.active)


def test_fault_point_table_vs_live_sites_round_trip():
    """Full-scope inverse check: every documented point has a live
    site TODAY (a dead row would warn through the tier-1 full-repo
    gate, so catch it here with a readable message)."""
    res = _run([FaultPointsPass()],
               paths=[REPO / "paddle_tpu"])
    dead = [f.message for f in res.active
            if "has no live" in f.message]
    assert dead == [], dead


# -- lock-discipline ---------------------------------------------------------

def test_lock_discipline_catches_bug_classes():
    from tools.graft_lint.passes.lock_discipline import LockDisciplinePass
    res = _run([LockDisciplinePass()],
               paths=[FIXTURES / "lock_discipline_bad.py"])
    msgs = [f.message for f in res.active]
    assert len(msgs) == 13, "\n".join(msgs)
    assert sum("time.sleep()" in m for m in msgs) == 1
    assert sum("untimed queue .get()" in m for m in msgs) == 2
    assert sum("untimed queue .put()" in m for m in msgs) == 1
    assert sum("untimed .join()" in m for m in msgs) == 1
    assert sum("untimed .wait()" in m for m in msgs) == 1
    assert sum(".accept()" in m for m in msgs) == 1
    assert sum("untimed .communicate()" in m for m in msgs) == 1
    assert sum("subprocess.run() without timeout=" in m
               for m in msgs) == 1
    assert sum("float() on a device value" in m for m in msgs) == 1
    assert sum(".numpy()" in m for m in msgs) == 1
    # acquire()/release() straight-line tracking: the recv between the
    # calls fires; the recv after release() does not
    assert sum(".recv()" in m for m in msgs) == 1
    # every blocking-call message names the held lock
    assert all("while holding" in m for m in msgs
               if "lock-order cycle" not in m)


def test_lock_discipline_cycle_is_an_error():
    from tools.graft_lint.passes.lock_discipline import LockDisciplinePass
    res = _run([LockDisciplinePass()],
               paths=[FIXTURES / "lock_discipline_bad.py"])
    errors = [f for f in res.active if f.severity == "error"]
    assert len(errors) == 1
    assert "lock-order cycle" in errors[0].message
    assert "Inverted.self.lock_a" in errors[0].message
    assert "Inverted.self.lock_b" in errors[0].message


def test_lock_discipline_negative():
    from tools.graft_lint.passes.lock_discipline import LockDisciplinePass
    res = _run([LockDisciplinePass()],
               paths=[FIXTURES / "lock_discipline_ok.py"])
    assert res.active == [], "\n".join(f.render() for f in res.active)


# -- thread-hygiene ----------------------------------------------------------

def test_thread_hygiene_catches_bug_classes():
    from tools.graft_lint.passes.thread_hygiene import ThreadHygienePass
    res = _run([ThreadHygienePass()],
               paths=[FIXTURES / "thread_hygiene_bad.py"])
    msgs = [f.message for f in res.active]
    assert len(msgs) == 6, "\n".join(msgs)
    assert sum("without name=" in m for m in msgs) == 2
    assert sum("explicit daemon=" in m for m in msgs) == 1
    assert sum("never joined, stored or returned" in m
               for m in msgs) == 2
    assert sum("bare except:" in m for m in msgs) == 1


def test_thread_hygiene_negative():
    from tools.graft_lint.passes.thread_hygiene import ThreadHygienePass
    res = _run([ThreadHygienePass()],
               paths=[FIXTURES / "thread_hygiene_ok.py"])
    assert res.active == [], "\n".join(f.render() for f in res.active)


# -- --fix mode --------------------------------------------------------------

def _fix_sandbox(tmp_path):
    """Copies of the positive fixtures, since --fix rewrites in place."""
    import shutil
    paths = []
    for name in ("lock_discipline_bad.py", "thread_hygiene_bad.py"):
        dst = tmp_path / name
        shutil.copy(FIXTURES / name, dst)
        paths.append(dst)
    return paths


def test_fix_dry_run_prints_diff_and_leaves_files_alone(tmp_path):
    from tools.graft_lint.core import run
    paths = _fix_sandbox(tmp_path)
    before = [p.read_text() for p in paths]
    out = tmp_path / "out.txt"
    run(pass_names=["lock-discipline", "thread-hygiene"],
        paths=[str(p) for p in paths],
        fix=True, fix_dry_run=True, out=open(out, "w"))
    text = out.read_text()
    assert "+                return _jobs_q.get(timeout=0.1)" in text
    assert '+    threading.Thread(target=_worker, daemon=True, ' \
           'name="paddle-worker").start()' in text
    assert [p.read_text() for p in paths] == before   # dry: untouched


def test_fix_applies_and_resolves_findings(tmp_path):
    from tools.graft_lint.core import run
    from tools.graft_lint.passes.lock_discipline import LockDisciplinePass
    from tools.graft_lint.passes.thread_hygiene import ThreadHygienePass
    paths = _fix_sandbox(tmp_path)
    passes = [LockDisciplinePass(), ThreadHygienePass()]
    before = len(_run(passes, paths=paths).active)
    out = tmp_path / "out.txt"
    rc = run(pass_names=["lock-discipline", "thread-hygiene"],
             paths=[str(p) for p in paths],
             fix=True, out=open(out, "w"))
    assert rc == 0
    assert "3 fix(es) applied" in out.read_text()
    # exactly the three mechanical findings are gone; judgement calls
    # (daemon choice, ownership, cycles) remain for a human
    after = _run([LockDisciplinePass(), ThreadHygienePass()],
                 paths=paths)
    assert len(after.active) == before - 3
    fixed = (tmp_path / "lock_discipline_bad.py").read_text()
    assert "_jobs_q.get(timeout=0.1)" in fixed
    assert 'name="paddle-worker"' in \
        (tmp_path / "thread_hygiene_bad.py").read_text()


def test_fix_inserts_daemon_when_statically_known(tmp_path):
    """--fix writes daemon=K only where the CREATING thread's
    daemon-ness is statically known: the enclosing function is a
    target= of threads unanimously constructed with constant daemon=K.
    Unknown creators and conflicting creators keep findings un-fixed."""
    import shutil
    from tools.graft_lint.core import run
    from tools.graft_lint.passes.thread_hygiene import ThreadHygienePass
    dst = tmp_path / "thread_hygiene_daemon_fix.py"
    shutil.copy(FIXTURES / "thread_hygiene_daemon_fix.py", dst)

    res = _run([ThreadHygienePass()], paths=[dst])
    msgs = [f.message for f in res.active]
    assert len(msgs) == 3, "\n".join(msgs)
    assert all("explicit daemon=" in m for m in msgs)
    assert sum(1 for f in res.active if f.fix) == 1

    out = tmp_path / "out.txt"
    rc = run(pass_names=["thread-hygiene"], paths=[str(dst)],
             fix=True, out=open(out, "w"))
    assert rc == 0
    assert "1 fix(es) applied" in out.read_text()
    assert 'target=_tick, name="paddle-ticker", daemon=True)' in \
        dst.read_text()
    after = _run([ThreadHygienePass()], paths=[dst])
    assert sum("explicit daemon=" in f.message
               for f in after.active) == 2


def test_fix_skips_stale_lines(tmp_path):
    """A fix whose recorded line drifted (file edited between collect
    and apply) is skipped, never misapplied."""
    from tools.graft_lint.core import apply_fixes, run_collect
    from tools.graft_lint.passes.thread_hygiene import ThreadHygienePass
    paths = _fix_sandbox(tmp_path)
    res = run_collect([ThreadHygienePass()], paths=paths, repo=REPO)
    target = tmp_path / "thread_hygiene_bad.py"
    target.write_text(target.read_text().replace(
        "target=_worker, daemon=True", "target=_worker,  daemon=True"))
    out = tmp_path / "out.txt"
    applied = apply_fixes(res.findings, REPO, out=open(out, "w"))
    assert "line no longer matches" in out.read_text()
    assert applied < sum(1 for f in res.findings if f.fix)


# -- suppressions ------------------------------------------------------------

def test_suppressions_inline_and_standalone():
    res = _run([TraceSafetyPass()],
               paths=[FIXTURES / "suppression_demo.py"])
    assert len(res.active) == 1          # t1 only
    assert res.suppressed == 2           # t0 (inline) + t2 (standalone)
    assert res.active[0].line == 11


# -- baseline mechanics ------------------------------------------------------

def test_baseline_within_grown_shrunk():
    fixture = FIXTURES / "host_sync_bad.py"
    key = "host-sync:tests/fixtures/graft_lint/host_sync_bad.py"

    within = _run([HostSyncPass()], paths=[fixture], baseline={key: 2})
    assert within.active == [] and len(within.baselined) == 2
    assert within.stale_baseline == []

    grown = _run([HostSyncPass()], paths=[fixture], baseline={key: 1})
    assert len(grown.active) == 2        # whole group reported

    shrunk = _run([HostSyncPass()], paths=[fixture], baseline={key: 3})
    assert shrunk.active == [] and shrunk.stale_baseline == [key]


def test_baseline_roundtrip(tmp_path):
    res = _run([HostSyncPass()], paths=[FIXTURES / "host_sync_bad.py"])
    bpath = tmp_path / "baseline.json"
    counts = core.write_baseline(res.findings, bpath)
    assert core.load_baseline(bpath) == counts
    assert sum(counts.values()) == 2


def test_baseline_ignores_entries_for_passes_not_run():
    """A --pass subset run must not call the rest of the baseline
    stale."""
    res = _run([TraceSafetyPass()],
               baseline={"host-sync:paddle_tpu/geometric/__init__.py": 5})
    assert res.stale_baseline == []


def test_write_baseline_subset_run_preserves_other_entries(tmp_path):
    """`--changed --write-baseline` (or any subset regeneration) must
    not wipe grandfathered entries outside the run's scope."""
    from tools.graft_lint.core import run
    bpath = tmp_path / "baseline.json"
    bpath.write_text(json.dumps({
        "host-sync:paddle_tpu/geometric/__init__.py": 5,
        "host-sync:tests/fixtures/graft_lint/host_sync_bad.py": 2,
    }))
    rc = run(pass_names=["host-sync"],
             paths=[str(FIXTURES / "host_sync_bad.py")],
             baseline_path=bpath, regen_baseline=True,
             out=open(tmp_path / "out.txt", "w"))
    assert rc == 0
    regen = json.loads(bpath.read_text())
    # the re-judged (pass, file) group was rewritten; the geometric
    # entry (outside this run's scope) survived
    assert regen == {
        "host-sync:paddle_tpu/geometric/__init__.py": 5,
        "host-sync:tests/fixtures/graft_lint/host_sync_bad.py": 2,
    }


def test_write_baseline_refuses_error_findings(tmp_path):
    """Errors are never baseline-eligible — silently grandfathering a
    deadlock signature or typo'd flag would green-light it through the
    tier-1 gates with no rationale in the code."""
    from tools.graft_lint.core import run
    bpath = tmp_path / "baseline.json"
    out = tmp_path / "out.txt"
    rc = run(pass_names=["trace-safety"],
             paths=[str(FIXTURES / "trace_safety_bad.py")],
             baseline_path=bpath, regen_baseline=True,
             out=open(out, "w"))
    assert rc == 1
    assert not bpath.exists()
    assert "refusing to baseline" in out.read_text()


def test_baseline_entry_for_deleted_file_is_stale(tmp_path):
    """Debt rows must die with their file: an entry whose path no
    longer exists is reported stale, and --write-baseline drops it."""
    from tools.graft_lint.core import run
    ghost = "host-sync:paddle_tpu/no_such_module_anymore.py"
    res = _run([HostSyncPass()], baseline={ghost: 3})
    assert ghost in res.stale_baseline
    bpath = tmp_path / "baseline.json"
    bpath.write_text(json.dumps({
        ghost: 3,
        "host-sync:tests/fixtures/graft_lint/host_sync_bad.py": 2}))
    rc = run(pass_names=["host-sync"],
             paths=[str(FIXTURES / "host_sync_bad.py")],
             baseline_path=bpath, regen_baseline=True,
             out=open(tmp_path / "out.txt", "w"))
    assert rc == 0
    assert ghost not in json.loads(bpath.read_text())


def test_unreadable_file_is_a_finding_not_a_crash(tmp_path):
    """Non-UTF-8 bytes (or null bytes) in a scanned file must produce a
    'syntax' finding, not an unhandled exception."""
    probe = tmp_path / "latin.py"
    probe.write_bytes(b"# -*- coding: latin-1 -*-\n# caf\xe9\nx = 1\n")
    res = _run([TraceSafetyPass()], paths=[probe])
    assert len(res.active) == 1
    assert res.active[0].pass_name == "syntax"


def test_metric_names_shim_threads_seen_across_files(tmp_path):
    """Old-API callers pass one `seen` dict across files; a duplicate
    creation site in a SECOND file must still be caught."""
    shim = _load_tool("check_metric_names")
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("from x import metrics\n"
                 "c = metrics.counter('sub.dup')\n")
    b.write_text("from x import metrics\n"
                 "d = metrics.counter('sub.dup')\n")
    seen = {}
    first = shim.check_file(a, seen)
    second = shim.check_file(b, seen)
    assert first == []
    assert len(second) == 1 and "duplicate" in second[0][2]
    # span home-module state rides the same seen dict: a cross-file
    # span fork is caught through the legacy API too
    sa = tmp_path / "sa.py"
    sb = tmp_path / "sb.py"
    sa.write_text("from x import span\n"
                  "def f():\n"
                  "    with span('subspan.phase'):\n"
                  "        pass\n")
    sb.write_text("from x import span\n"
                  "def g():\n"
                  "    with span('subspan.phase'):\n"
                  "        pass\n")
    assert shim.check_file(sa, seen) == []
    forked = shim.check_file(sb, seen)
    assert len(forked) == 1 and "one span name" in forked[0][2]


def test_metric_names_covers_span_literals():
    """ISSUE 11 satellite: span("...") names ride the same
    snake_case/uniqueness discipline as metric ids — the fixture's
    dynamic name, bad shape and bad concatenation prefix are each
    caught; the literal + literal-prefix forms pass."""
    from tools.graft_lint.passes.metric_names import MetricNamesPass
    fixture = FIXTURES / "span_names_bad.py"
    res = _run([MetricNamesPass()], paths=[fixture])
    msgs = [f.message for f in res.active]
    assert len(msgs) == 3, msgs
    assert any("string literal" in m for m in msgs)          # dynamic
    assert any("snake_case" in m for m in msgs)              # bad shape
    assert any("prefix" in m for m in msgs)                  # bad concat


def test_metric_names_span_home_module_uniqueness(tmp_path):
    """One span name, one home module: the same literal from two
    different files is flagged; repeats within one file are fine (a
    retry loop spans the same name at several sites)."""
    from tools.graft_lint.passes.metric_names import MetricNamesPass
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("from x import span\n"
                 "def f():\n"
                 "    with span('sub.phase'):\n"
                 "        pass\n"
                 "    with span('sub.phase'):\n"    # same file: OK
                 "        pass\n")
    b.write_text("from x import span\n"
                 "def g():\n"
                 "    with span('sub.phase'):\n"    # other file: forked
                 "        pass\n")
    p = MetricNamesPass()
    res = _run([p], paths=[a, b])
    assert len(res.active) == 1
    assert "one span name, one home module" in res.active[0].message


# -- --changed mode ----------------------------------------------------------

def _git(repo, *args):
    subprocess.run(["git", "-C", str(repo), "-c", "user.email=t@t",
                    "-c", "user.name=t", *args],
                   check=True, capture_output=True)


def test_changed_mode_scopes_to_git_diff(tmp_path):
    pkg = tmp_path / "paddle_tpu"
    pkg.mkdir()
    clean = "def f(x):\n    return x\n"
    bad = ("from paddle_tpu.jit import to_static\n"
           "@to_static\n"
           "def f(x):\n"
           "    print(x)\n"
           "    return x\n")
    (pkg / "touched.py").write_text(clean)
    (pkg / "untouched_bad.py").write_text(bad)
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-qm", "seed")
    # modify ONE file to be bad; the committed-bad file must not scan
    (pkg / "touched.py").write_text(bad)
    res = run_collect([TraceSafetyPass()], changed=True, repo=tmp_path)
    assert res.files_scanned == 1
    assert len(res.active) == 1
    assert res.active[0].path == "paddle_tpu/touched.py"


# -- shims + CLI -------------------------------------------------------------

def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_shim_clis_share_the_framework():
    """The three historical checkers still work as CLIs but carry no
    duplicated walker logic — no `import ast` outside graft_lint."""
    for name in ("check_apply_op_closures", "check_atomic_writes",
                 "check_metric_names"):
        text = (REPO / "tools" / f"{name}.py").read_text()
        assert "import ast" not in text, f"{name} regrew its own walker"
        mod = _load_tool(name)
        assert mod.main([]) == 0
    # coverage grown per the ROADMAP open item (ISSUE 2/3 follow-on)
    shim = _load_tool("check_atomic_writes")
    covered = "\n".join(shim.CHECKED_MODULES)
    assert "static/__init__.py" in covered
    assert "onnx/__init__.py" in covered


def test_shim_still_catches_probe_violation(tmp_path):
    shim = _load_tool("check_atomic_writes")
    probe = tmp_path / "probe.py"
    probe.write_text("def save(path, b):\n"
                     "    with open(path, 'wb') as f:\n"
                     "        f.write(b)\n")
    assert shim.main([str(probe)]) == 1


def test_cli_json_and_pass_selection(capsys):
    from tools.graft_lint.__main__ import main
    rc = main(["--pass", "trace-safety", "--format", "json",
               str(FIXTURES / "trace_safety_bad.py")])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["exit_code"] == 1
    assert len(out["findings"]) == 9
    assert all(f["pass_name"] == "trace-safety"
               for f in out["findings"])


def test_cli_rejects_unknown_pass():
    from tools.graft_lint.__main__ import main
    with pytest.raises(SystemExit):
        main(["--pass", "no-such-pass"])


def test_cli_list_passes(capsys):
    from tools.graft_lint.__main__ import main
    assert main(["--list-passes"]) == 0
    out = capsys.readouterr().out
    for name in ("trace-safety", "host-sync", "collective-order",
                 "flags-hygiene", "apply-op-closures", "atomic-writes",
                 "metric-names", "lock-discipline", "thread-hygiene"):
        assert name in out
