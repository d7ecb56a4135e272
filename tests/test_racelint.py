"""racelint + lock-order-sanitizer tests (ISSUE 18 acceptance criteria).

Same contract shape as test_analysis.py pins for jaxlint: the rule
corpus under ``tests/fixtures/racelint/`` carries true-positive lines
marked ``# expect: RLxxx`` AND must-not-flag snippets of the
neighbouring legal idiom, and the parametrized test asserts EXACT
agreement — a rule that goes quiet or starts flagging the serve tier's
own idioms fails tier-1 either way. Plus: the shared-lintcore
suppression contract, JSON/CLI/exit codes, cross-module cycle
detection, the repo-clean gate, and the ``guards`` runtime lock-order
sanitizer validated against the statically exported graph.

All AST-only and pure-Python — no jax, no device.
"""

import json
import re
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.analysis import racelint

pytestmark = pytest.mark.analysis

FIXTURES = Path(__file__).parent / "fixtures" / "racelint"
RULE_FILES = sorted(FIXTURES.glob("rl0*.py"))
_EXPECT_RE = re.compile(r"#\s*expect:\s*(RL\d{3}(?:\s*,\s*RL\d{3})*)")


def expected_findings(path: Path):
    """(line, rule) pairs declared by `# expect: RLxxx` markers."""
    out = set()
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        m = _EXPECT_RE.search(line)
        if m:
            for rule in m.group(1).split(","):
                out.add((i, rule.strip()))
    return out


class TestRuleCorpus:
    @pytest.mark.parametrize(
        "path", RULE_FILES, ids=[p.stem for p in RULE_FILES])
    def test_rule_fixture_exact_agreement(self, path):
        expected = expected_findings(path)
        assert expected, f"{path.name} has no # expect markers"
        actual = {(f.line, f.rule) for f in racelint.lint_file(path)}
        missed = expected - actual
        spurious = actual - expected
        assert not missed, f"rule went quiet, missed: {sorted(missed)}"
        assert not spurious, \
            f"flagged legal idiom lines: {sorted(spurious)}"

    def test_corpus_covers_every_rule(self):
        covered = set()
        for path in RULE_FILES:
            covered |= {rule for _, rule in expected_findings(path)}
        # RL002's cycle half needs two modules; the cross pair below
        # covers it too, but the solo corpus must already hit each rule
        assert covered == set(racelint.RULES), \
            f"rules without a true-positive fixture: " \
            f"{sorted(set(racelint.RULES) - covered)}"

    def test_seeded_violation_fixture_is_dirty(self):
        """The CI gate lints this fixture expecting a nonzero exit; if
        someone 'fixes' it the gate stops proving anything."""
        findings = racelint.lint_file(FIXTURES / "seeded_violation.py")
        assert {f.rule for f in findings} >= {"RL003", "RL006"}


class TestSuppression:
    def test_suppressed_corpus_is_clean(self):
        """Every waiver form (trailing, line-above, slug, comma list,
        `all`) silences its finding."""
        assert racelint.lint_file(FIXTURES / "suppressed.py") == []

    def test_unwaived_sibling_still_flagged(self):
        """A waiver is line-scoped: the same violation one line later
        without a comment still fires."""
        src = (
            "import time\n"
            "def f(t):\n"
            "    a = time.time() + t  # racelint: disable=RL006 — ok\n"
            "    b = time.time() + t\n"
            "    return a, b\n"
        )
        findings = racelint.lint_source(src)
        assert [(f.line, f.rule) for f in findings] == [(4, "RL006")]

    def test_unknown_rule_in_waiver_ignored(self):
        src = ("import time\n"
               "def f(t):\n"
               "    return time.time() + t  # racelint: disable=RL999\n")
        assert [f.rule for f in racelint.lint_source(src)] == ["RL006"]

    def test_jaxlint_waiver_does_not_silence_racelint(self):
        """The two tools share one parser but each only honors its own
        tool name — a jaxlint waiver on a racelint finding is inert."""
        src = ("import time\n"
               "def f(t):\n"
               "    return time.time() + t  # jaxlint: disable=JL007\n")
        assert [f.rule for f in racelint.lint_source(src)] == ["RL006"]


class TestCLI:
    def test_json_output_and_exit_code(self, capsys):
        rc = racelint.main(
            ["--json", "--no-default-excludes",
             str(FIXTURES / "seeded_violation.py")])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert out["files"] == 1
        rules = {f["rule"] for f in out["findings"]}
        assert "RL003" in rules and "RL006" in rules
        for f in out["findings"]:
            assert set(f) == {"rule", "slug", "path", "line", "col",
                              "message"}

    def test_clean_file_exits_zero(self, tmp_path, capsys):
        p = tmp_path / "clean.py"
        p.write_text("import time\nt0 = time.monotonic()\n")
        assert racelint.main([str(p)]) == 0

    def test_default_excludes_skip_own_corpus(self, capsys):
        """`racelint tests` must exit 0 on the merged tree even though
        the true-positive corpus lives under tests/ — the corpus is
        excluded by default and reachable via --no-default-excludes."""
        files = racelint.iter_py_files([str(FIXTURES)])
        assert files == []
        files = racelint.iter_py_files([str(FIXTURES)], excludes=())
        assert len(files) >= 10

    def test_select_and_ignore(self, capsys):
        rc = racelint.main(["--json", "--select", "RL006",
                            "--no-default-excludes",
                            str(FIXTURES / "seeded_violation.py")])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert {f["rule"] for f in out["findings"]} == {"RL006"}
        rc = racelint.main(["--ignore", "RL003,RL006",
                            "--no-default-excludes",
                            str(FIXTURES / "seeded_violation.py")])
        capsys.readouterr()
        assert rc == 0

    def test_unknown_rule_is_usage_error(self, capsys):
        assert racelint.main(["--select", "RL999", "x.py"]) == 2

    def test_list_rules(self, capsys):
        assert racelint.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in racelint.RULES:
            assert rid in out

    @pytest.mark.slow
    def test_module_entrypoint_subprocess(self):
        """The form Makefile/CI invoke: python -m ... exits 1 on the
        seeded fixture, 0 with it excluded by default."""
        proc = subprocess.run(
            [sys.executable, "-m", "dalle_pytorch_tpu.analysis.racelint",
             "--no-default-excludes", str(FIXTURES / "seeded_violation.py")],
            capture_output=True, text=True, cwd=Path(__file__).parents[1])
        assert proc.returncode == 1, proc.stderr


class TestCrossModule:
    """Project mode (``racelint.lint_files`` — what the CLI and the
    repo-clean test run): the lock-order cycle spans two modules, each
    half clean alone because the peer class resolves only when both
    files are in one run. The propagation, not a rule change, is what
    fires the finding."""

    PAIR = [FIXTURES / "cross_order_a.py",
            FIXTURES / "cross_order_b.py"]
    _CROSS_RE = re.compile(r"#\s*cross-expect:\s*(RL\d{3})")

    def _expected(self):
        out = set()
        for p in self.PAIR:
            for i, line in enumerate(p.read_text().splitlines(),
                                     start=1):
                m = self._CROSS_RE.search(line)
                if m:
                    out.add((p.name, i, m.group(1)))
        return out

    def test_solo_mode_is_blind_to_the_pair(self):
        for p in self.PAIR:
            assert racelint.lint_file(p) == [], p.name

    def test_project_mode_exact_agreement(self):
        expected = self._expected()
        assert expected, "pair has no # cross-expect markers"
        assert {"RL002"} == {r for _, _, r in expected}
        actual = {(Path(f.path).name, f.line, f.rule)
                  for f in racelint.lint_files(self.PAIR)}
        missed = expected - actual
        spurious = actual - expected
        assert not missed, f"cross-module cycle went quiet: " \
                           f"{sorted(missed)}"
        assert not spurious, f"flagged legal cross-module idiom: " \
                             f"{sorted(spurious)}"

    def test_pair_edges_exported(self):
        edges = racelint.lock_order_edges(self.PAIR)
        assert ("PeerA._la", "PeerB._lb") in edges
        assert ("PeerB._lb", "PeerA._la") in edges


class TestRepoIsClean:
    def test_package_and_tests_lint_clean(self):
        """The merged-tree acceptance criterion, as a tier-1 test: every
        concurrency finding in the package, tests and scripts —
        including whole-program lock-order and blocking propagation —
        is fixed or carries an in-line reasoned waiver."""
        root = Path(__file__).parents[1]
        files = racelint.iter_py_files(
            [str(root / "dalle_pytorch_tpu"), str(root / "tests"),
             str(root / "scripts")])
        findings = racelint.lint_files(files)
        assert findings == [], "\n".join(x.render() for x in findings)


class TestSanitizer:
    """guards.py's LockOrderRecorder/TrackedLock — racelint RL002's
    runtime twin."""

    def test_inverted_order_raises(self):
        rec = guards.LockOrderRecorder()
        a = guards.TrackedLock("A._la", rec)
        b = guards.TrackedLock("B._lb", rec)
        with a:
            with b:
                pass
        with pytest.raises(guards.LockOrderError) as ei:
            with b:
                with a:
                    pass
        assert ei.value.first == "B._lb"
        assert ei.value.second == "A._la"

    def test_transitive_inversion_caught(self):
        """A->B and B->C observed; C->A closes a 3-cycle even though
        the pair (C, A) was never seen directly."""
        rec = guards.LockOrderRecorder()
        la = guards.TrackedLock("A", rec)
        lb = guards.TrackedLock("B", rec)
        lc = guards.TrackedLock("C", rec)
        with la:
            with lb:
                pass
        with lb:
            with lc:
                pass
        with pytest.raises(guards.LockOrderError) as ei:
            with lc:
                with la:
                    pass
        assert ei.value.chain == ["A", "B", "C"]

    def test_consistent_order_is_silent(self):
        rec = guards.LockOrderRecorder()
        a = guards.TrackedLock("A", rec)
        b = guards.TrackedLock("B", rec)
        for _ in range(3):
            with a:
                with b:
                    pass
        assert rec.edges() == {("A", "B")}

    def test_tracked_lock_passthrough(self):
        rec = guards.LockOrderRecorder()
        lk = guards.TrackedLock("X", rec)
        assert lk.acquire(True, 0.1)
        assert lk.locked()
        # contended timed acquire fails without recording
        assert not lk.acquire(False)
        lk.release()
        assert not lk.locked()
        assert rec.edges() == set()

    def test_instrument_locks_names_and_wraps(self):
        class Thing:
            def __init__(self):
                self._lock = threading.Lock()
                self.data = []
        t = Thing()
        rec = guards.LockOrderRecorder()
        names = guards.instrument_locks(t, rec)
        assert names == ["Thing._lock"]
        assert isinstance(t._lock, guards.TrackedLock)
        with t._lock:
            pass
        # cls_name override: racelint names locks after the DEFINING
        # class, so a subclass instance must be instrumentable under
        # its base's name
        t2 = Thing()
        assert guards.instrument_locks(t2, rec, cls_name="Base") \
            == ["Base._lock"]

    def test_assert_consistent_with(self):
        rec = guards.LockOrderRecorder()
        with guards.TrackedLock("A", rec):
            with guards.TrackedLock("B", rec):
                pass
        rec.assert_consistent_with({("A", "B"), ("B", "C")})
        with pytest.raises(AssertionError, match="A -> B"):
            rec.assert_consistent_with({("B", "C")})

    def test_serve_drive_matches_static_graph(self):
        """The acceptance check: instrument real serve objects, drive a
        requeue-after-drain (which fulfils the handle and summarizes
        its trace UNDER the queue lock), and assert every runtime edge
        was predicted by ``racelint.lock_order_edges`` over the
        package. A hole in the static call-graph resolution — or a new
        nested acquire racelint cannot see — fails here, not in
        production."""
        from dalle_pytorch_tpu.serve import scheduler
        rec = guards.LockOrderRecorder()
        q = scheduler.RequestQueue(max_depth=4)
        guards.instrument_locks(q, rec)
        h = q.submit(scheduler.Request(codes=(1, 2, 3)))
        guards.instrument_locks(h, rec)
        assert h.trace is not None
        guards.instrument_locks(h.trace, rec)
        q.close()
        q.drain()
        q.requeue(h)          # post-drain: fulfils under RequestQueue._lock
        assert h.done()
        observed = rec.edges()
        assert ("RequestQueue._lock", "RequestHandle._fulfill_lock") \
            in observed
        root = Path(__file__).parents[1]
        files = racelint.iter_py_files([str(root / "dalle_pytorch_tpu")])
        rec.assert_consistent_with(racelint.lock_order_edges(files))

    def test_sanitizer_catches_seeded_inversion_against_static(self):
        """An edge the static graph does NOT predict fails the
        consistency check — the gate half of the contract."""
        rec = guards.LockOrderRecorder()
        with guards.TrackedLock("RequestHandle._fulfill_lock", rec):
            with guards.TrackedLock("RequestQueue._lock", rec):
                pass
        root = Path(__file__).parents[1]
        files = racelint.iter_py_files([str(root / "dalle_pytorch_tpu")])
        with pytest.raises(AssertionError, match="not predicted"):
            rec.assert_consistent_with(racelint.lock_order_edges(files))
