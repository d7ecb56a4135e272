"""jaxlint + runtime-guard tests (ISSUE 3 acceptance criteria).

The lint rules are pinned by a fixtures corpus under
``tests/fixtures/jaxlint/``: each ``jl00N_*.py`` file carries
true-positive lines marked ``# expect: JLxxx`` AND must-not-flag
snippets of the neighbouring legal idiom — the parametrized test asserts
EXACT agreement (every expected finding found, nothing else flagged), so
a rule that goes quiet or starts flagging the codebase's own idioms
fails tier-1 either way. Plus: the suppression-comment contract, JSON
output, exit codes, and the ``analysis.guards`` runtime twins.

All CPU and AST-only except the guard tests (tiny jit programs).
"""

import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from dalle_pytorch_tpu.analysis import guards
from dalle_pytorch_tpu.analysis import jaxlint

pytestmark = pytest.mark.analysis

FIXTURES = Path(__file__).parent / "fixtures" / "jaxlint"
RULE_FILES = sorted(FIXTURES.glob("jl0*.py"))
_EXPECT_RE = re.compile(r"#\s*expect:\s*(JL\d{3}(?:\s*,\s*JL\d{3})*)")


def expected_findings(path: Path):
    """(line, rule) pairs declared by `# expect: JLxxx` markers."""
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
        actual = {(f.line, f.rule) for f in jaxlint.lint_file(path)}
        missed = expected - actual
        spurious = actual - expected
        assert not missed, f"rule went quiet, missed: {sorted(missed)}"
        assert not spurious, \
            f"flagged legal idiom lines: {sorted(spurious)}"

    def test_corpus_covers_every_rule(self):
        covered = set()
        for path in RULE_FILES:
            covered |= {rule for _, rule in expected_findings(path)}
        assert covered == set(jaxlint.RULES), \
            f"rules without a true-positive fixture: " \
            f"{sorted(set(jaxlint.RULES) - covered)}"

    def test_seeded_violation_fixture_is_dirty(self):
        """The CI gate greps this fixture for a nonzero exit; if someone
        'fixes' it the gate stops proving anything."""
        findings = jaxlint.lint_file(FIXTURES / "seeded_violation.py")
        assert {f.rule for f in findings} >= {"JL001", "JL007"}


class TestSuppression:
    def test_suppressed_corpus_is_clean(self):
        """Every waiver form (trailing, line-above, slug, comma list,
        `all`) silences its finding."""
        assert jaxlint.lint_file(FIXTURES / "suppressed.py") == []

    def test_unwaived_sibling_still_flagged(self):
        """A waiver is line-scoped: the same violation one line later
        without a comment still fires."""
        src = (
            "import time\n"
            "a = time.time()  # jaxlint: disable=JL007 — stamp\n"
            "b = time.time()\n"
        )
        findings = jaxlint.lint_source(src)
        assert [(f.line, f.rule) for f in findings] == [(3, "JL007")]

    def test_unknown_rule_in_waiver_ignored(self):
        src = "import time\nt = time.time()  # jaxlint: disable=JL999\n"
        assert [f.rule for f in jaxlint.lint_source(src)] == ["JL007"]


class TestCLI:
    def test_json_output_and_exit_code(self, capsys):
        rc = jaxlint.main(
            ["--json", "--no-default-excludes",
             str(FIXTURES / "seeded_violation.py")])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert out["files"] == 1
        rules = {f["rule"] for f in out["findings"]}
        assert "JL001" in rules and "JL007" in rules
        for f in out["findings"]:
            assert set(f) == {"rule", "slug", "path", "line", "col",
                              "message"}

    def test_clean_file_exits_zero(self, tmp_path, capsys):
        p = tmp_path / "clean.py"
        p.write_text("import time\nt0 = time.perf_counter()\n")
        assert jaxlint.main([str(p)]) == 0

    def test_default_excludes_skip_own_corpus(self, capsys):
        """`jaxlint tests` must exit 0 on the merged tree even though
        the true-positive corpus lives under tests/ — the corpus is
        excluded by default and reachable via --no-default-excludes."""
        files = jaxlint.iter_py_files([str(FIXTURES)])
        assert files == []
        files = jaxlint.iter_py_files([str(FIXTURES)], excludes=())
        assert len(files) >= 10

    def test_select_and_ignore(self, capsys):
        rc = jaxlint.main(["--json", "--select", "JL007",
                           "--no-default-excludes",
                           str(FIXTURES / "seeded_violation.py")])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert {f["rule"] for f in out["findings"]} == {"JL007"}
        rc = jaxlint.main(["--ignore", "JL001,JL007",
                           "--no-default-excludes",
                           str(FIXTURES / "seeded_violation.py")])
        capsys.readouterr()
        assert rc == 0

    def test_unknown_rule_is_usage_error(self, capsys):
        assert jaxlint.main(["--select", "JL999", "x.py"]) == 2

    @pytest.mark.slow
    def test_module_entrypoint_subprocess(self):
        """The form Makefile/CI invoke: python -m ... exits 1 on the
        seeded fixture, 0 with it excluded by default."""
        proc = subprocess.run(
            [sys.executable, "-m", "dalle_pytorch_tpu.analysis.jaxlint",
             "--no-default-excludes", str(FIXTURES / "seeded_violation.py")],
            capture_output=True, text=True, cwd=Path(__file__).parents[1])
        assert proc.returncode == 1, proc.stderr


class TestCrossModule:
    """Project mode (``jaxlint.lint_files`` — what the CLI and the
    repo-clean test run): JL001/JL009 traced reachability across module
    boundaries. The fixture pair proves both directions — a host sync
    on an IMPORTED module-level jitted program's output, and a host
    sync inside a function that only becomes traced because the SIBLING
    module jits it — and that per-file mode stays blind to both (the
    propagation, not a rule change, is what fires them)."""

    PAIR = [FIXTURES / "cross_module_def.py",
            FIXTURES / "cross_module_use.py"]
    _CROSS_RE = re.compile(r"#\s*cross-expect:\s*(JL\d{3})")

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
        """Each half lints CLEAN alone — the findings exist only in the
        cross-module view, so this pair must stay out of the solo
        fixture corpus loop."""
        for p in self.PAIR:
            assert jaxlint.lint_file(p) == [], p.name

    def test_project_mode_exact_agreement(self):
        expected = self._expected()
        assert expected, "pair has no # cross-expect markers"
        assert {"JL001", "JL009"} <= {r for _, _, r in expected}
        actual = {(Path(f.path).name, f.line, f.rule)
                  for f in jaxlint.lint_files(self.PAIR)}
        missed = expected - actual
        spurious = actual - expected
        assert not missed, f"cross-module propagation went quiet: " \
                           f"{sorted(missed)}"
        assert not spurious, f"flagged legal cross-module idiom: " \
                             f"{sorted(spurious)}"


class TestRepoIsClean:
    def test_package_and_tests_lint_clean(self):
        """The merged-tree acceptance criterion, as a tier-1 test: every
        finding in the package, tests and scripts — INCLUDING
        project-mode cross-module propagation — is fixed or carries an
        in-line waiver."""
        root = Path(__file__).parents[1]
        files = jaxlint.iter_py_files(
            [str(root / "dalle_pytorch_tpu"), str(root / "tests"),
             str(root / "scripts")])
        findings = jaxlint.lint_files(files)
        assert findings == [], "\n".join(x.render() for x in findings)


class TestGuards:
    def test_compile_count_passes_on_cached_calls(self):
        import jax
        import jax.numpy as jnp
        traced = guards.counting(lambda x: x * 2)
        fn = jax.jit(traced)
        with guards.compile_count(lambda: traced.traces, expect=1):
            for i in range(4):
                fn(jnp.float32(i)).block_until_ready()

    def test_compile_count_raises_on_recompile(self):
        import jax
        import jax.numpy as jnp
        traced = guards.counting(lambda x: x + 1)
        fn = jax.jit(traced)
        with pytest.raises(guards.CompileCountError) as ei:
            with guards.compile_count(lambda: traced.traces, expect=1,
                                      label="shape-poly probe"):
                fn(jnp.zeros((2,)))
                fn(jnp.zeros((3,)))      # new shape -> retrace
        assert ei.value.actual == 2
        assert "shape-poly probe" in str(ei.value)

    def test_compile_count_at_most(self):
        box = {"n": 0}
        with guards.compile_count(lambda: box["n"], at_most=2):
            box["n"] += 2
        with pytest.raises(ValueError):
            with guards.compile_count(lambda: box["n"]):
                pass

    def test_compile_count_body_exception_wins(self):
        box = {"n": 0}
        with pytest.raises(RuntimeError, match="body"):
            with guards.compile_count(lambda: box["n"], expect=0):
                box["n"] += 1
                raise RuntimeError("body")

    def test_no_transfers_allows_explicit(self):
        import jax
        import numpy as np
        fn = jax.jit(lambda x: x + 1)
        fn(jax.device_put(np.zeros((2,), np.float32)))   # compile outside
        with guards.no_transfers():
            x = jax.device_put(np.ones((2,), np.float32))
            y = jax.device_get(fn(x))
        np.testing.assert_array_equal(y, [2.0, 2.0])
