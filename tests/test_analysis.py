"""Static analyzer + lock witness (tier-1 gate for docs/static-analysis.md).

Three contracts:
- the seed-regression fixtures (tests/fixtures/analysis/) reproduce the
  repo's historical bug shapes and each BAD form is caught by its rule
  while the FIXED form passes — the rules can never silently stop
  understanding the bugs they were built from;
- the repo itself is clean: ``python -m kubedl_tpu.analysis`` exits 0
  against this checkout with the committed baseline (run in-process here
  the same way check_readme_numbers.py is gated);
- the lock witness finds an ABBA ordering cycle, stays quiet on
  consistent ordering, and its disarmed path costs nothing (chaos-style
  budget). The full-suite zero-cycle gate lives in conftest.py and runs
  when KUBEDL_LOCKWITNESS=1.
"""

import json
import os
import shutil
import threading
import time
from pathlib import Path

import pytest

from kubedl_tpu.analysis import lockwitness
from kubedl_tpu.analysis.engine import (
    analyze_file,
    apply_baseline,
    load_baseline,
    run,
    write_baseline,
)

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "analysis"

#: true when THIS suite run is the witnessed one (conftest armed it) —
#: the arming/overhead assertions below only make sense disarmed
_WITNESSED_RUN = os.environ.get(lockwitness.ENV_VAR, "") == "1"


def _rules(path: Path):
    return [f.rule for f in analyze_file(path)]


# --------------------------------------------------------------------------
# Seed-regression fixtures
# --------------------------------------------------------------------------


class TestSeedRegressions:
    CASES = [
        ("donated_restore", "KTL001"),  # PR 6: frombuffer -> donated step
        ("asarray_mirror", "KTL001"),   # PR 8: self._bt_host borrow
        ("runner_mirror", "KTL001"),    # PR 30: the same borrow, stored by the runner
        ("env_race", "KTL003"),         # PR 6: environ rewrite on re-entry
        ("lock_blocking", "KTL002"),    # PR 11: harvest under the cv
        ("fsync_loop", "KTL010"),
        ("fenced_actuation", "KTL011"),       # PR 19: fsync-per-append at scale
    ]

    @pytest.mark.parametrize("name,rule", CASES)
    def test_bad_form_caught(self, name, rule):
        found = _rules(FIXTURES / f"{name}_bad.py")
        assert rule in found, f"{name}_bad.py: expected {rule}, got {found}"

    @pytest.mark.parametrize("name,rule", CASES)
    def test_fixed_form_passes(self, name, rule):
        found = _rules(FIXTURES / f"{name}_fixed.py")
        assert rule not in found, (
            f"{name}_fixed.py: {rule} still fires on the fixed form: {found}"
        )

    @pytest.mark.parametrize("name,rule", CASES)
    def test_cli_nonzero_on_seeded_tree(self, name, rule, tmp_path, capsys):
        """The CLI exits non-zero on a tree seeded with each bad fixture,
        and the expected rule is among the findings."""
        pkg = tmp_path / "kubedl_tpu"
        pkg.mkdir()
        shutil.copy(FIXTURES / f"{name}_bad.py", pkg / "seeded.py")
        rc = run(["--root", str(tmp_path), "--no-baseline", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert any(
            f["rule"] == rule and f["path"].endswith("seeded.py")
            for f in out["findings"]
        ), out["findings"]

    def test_inline_pragma_suppresses(self, tmp_path):
        src = FIXTURES / "env_race_bad.py"
        suppressed = tmp_path / "pragma.py"
        suppressed.write_text(
            src.read_text().replace(
                "os.environ[k] = v",
                "os.environ[k] = v  # ktl: disable=KTL003 -- fixture",
            )
        )
        assert "KTL003" in _rules(src)
        assert "KTL003" not in _rules(suppressed)

    def test_baseline_roundtrip(self, tmp_path):
        """Accepted findings stop failing; anything new still does."""
        findings = analyze_file(FIXTURES / "env_race_bad.py")
        assert findings
        bl_path = tmp_path / "baseline.json"
        write_baseline(findings, bl_path)
        new, stale = apply_baseline(findings, load_baseline(bl_path))
        assert new == [] and stale == []
        extra = analyze_file(FIXTURES / "lock_blocking_bad.py")
        new, _ = apply_baseline(findings + extra, load_baseline(bl_path))
        assert new == extra


# --------------------------------------------------------------------------
# The repo itself is clean (the tier-1 gate)
# --------------------------------------------------------------------------


class TestRepoClean:
    def test_analyzer_exits_zero_on_repo(self, capsys):
        """`python -m kubedl_tpu.analysis` against this checkout with the
        committed baseline: zero new findings, zero stale entries."""
        rc = run(["--root", str(REPO)])
        out = capsys.readouterr().out
        assert rc == 0, f"static analysis regressed:\n{out}"
        assert "stale baseline" not in out, out


# --------------------------------------------------------------------------
# Lock witness
# --------------------------------------------------------------------------


class TestLockWitness:
    def test_abba_cycle_detected(self):
        """Two threads taking the same pair of lock classes in opposite
        orders — the classic ABBA potential deadlock — must close a cycle
        even though this run never actually deadlocks."""
        w = lockwitness.Witness()
        lock_a = w.Lock()
        lock_b = w.Lock()  # separate line: a distinct lock class

        def path_ab():
            with lock_a:
                with lock_b:
                    pass

        def path_ba():
            with lock_b:
                with lock_a:
                    pass

        for target in (path_ab, path_ba):
            t = threading.Thread(target=target)
            t.start()
            t.join()
        cycles = w.cycles()
        assert len(cycles) == 1
        assert set(cycles[0].sites) == {lock_a.site, lock_b.site}

    def test_consistent_order_no_cycle(self):
        w = lockwitness.Witness()
        lock_a = w.Lock()
        lock_b = w.Lock()
        for _ in range(2):
            with lock_a:
                with lock_b:
                    pass
        assert w.cycles() == []
        assert (lock_a.site, lock_b.site) in w.edges

    def test_condition_protocol_compat(self):
        """A witnessed Condition must survive the wait/notify protocol
        (_release_save/_acquire_restore) with depth bookkeeping intact."""
        w = lockwitness.Witness()
        cv = w.Condition()
        done = []

        def waiter():
            with cv:
                while not done:
                    cv.wait(timeout=1.0)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cv:
            done.append(True)
            cv.notify_all()
        t.join(timeout=5.0)
        assert not t.is_alive()
        assert w.cycles() == []

    def test_blocking_call_under_lock_flagged(self):
        """Armed witness flags time.sleep while a witnessed lock is held
        (runtime twin of static KTL002) — report-only by default."""
        if _WITNESSED_RUN:
            pytest.skip("global witness already armed; private-arm test")
        w = lockwitness.install(force=True)
        try:
            lock = threading.Lock()  # patched: witnessed, created here
            with lock:
                time.sleep(0.001)
            flagged = [
                b for b in w.blocking_findings()
                if "test_analysis.py" in b.caller
            ]
            assert flagged and lock.site in flagged[0].held
            assert lockwitness.check() == []  # report-only class
        finally:
            lockwitness.uninstall()

    @pytest.mark.skipif(_WITNESSED_RUN, reason="armed run: overhead expected")
    def test_install_is_noop_when_unarmed(self):
        assert os.environ.get(lockwitness.ENV_VAR, "") != "1"
        before = threading.Lock
        assert lockwitness.install() is None
        assert threading.Lock is before
        assert not lockwitness.armed()
        assert lockwitness.check() == []

    @pytest.mark.skipif(_WITNESSED_RUN, reason="armed run: overhead expected")
    def test_disarmed_overhead_unmeasurable(self):
        """Disarmed, the factory route is one global load + None test over
        a bare threading.Lock — same budget style as the chaos layer's
        disarmed-check test (generous absolute bound for slow CI)."""
        n = 200_000
        lock = lockwitness.Lock()
        assert type(lock) is type(threading.Lock())  # bare primitive
        acquire, release = lock.acquire, lock.release
        t0 = time.perf_counter()
        for _ in range(n):
            acquire()
            release()
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 5e-6, (
            f"disarmed witnessed lock costs {per_call * 1e9:.0f}ns/cycle"
        )
