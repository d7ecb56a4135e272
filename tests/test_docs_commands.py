"""What a document tells its reader to run exists.

One case a document. In each: every program named after ``python``,
``python3``, ``bash`` or ``pytest`` is a file at that path from the
repository's root, every ``python -m <module>`` is a module of the tree
(or a known outside tool), every ``make <target>`` in backticks or in a
code block is a target of the Makefile, and the document holds at least
one such command, so no case passes empty. Bare file names in prose are
not checked: module names and the reference's files trip that.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = [
    "README.md",
    "PERF.md",
    "ROADMAP.md",
    "docs/SERVING.md",
    "docs/STATIC_ANALYSIS.md",
    "docs/PERFORMANCE.md",
    "Makefile",
    ".github/workflows/ci.yml",
    ".claude/skills/verify/SKILL.md",
]

# ``python -m`` modules that are not this tree's
OUTSIDE_MODULES = {"pytest", "pip", "compileall", "build", "venv"}
# a pytest option that takes a value (the value is not a path)
PYTEST_VALUE_OPTIONS = {"-k", "-m", "-p", "-n", "--dist", "-o", "-c"}
STOP = {"|", "||", "&&", ";", ">", ">>", "2>&1", "#"}


def logical_lines(path: str):
    """-> [(line, is_code)]: the document's lines with ``\\``
    continuations joined and, in markdown prose, a backticked span that
    wraps over a line break brought onto one line. ``is_code`` is true
    for a fenced block's lines and for every line of a file that is no
    markdown."""
    with open(os.path.join(REPO, path)) as f:
        text = f.read().replace("\\\n", " ")
    if not path.endswith(".md"):
        return [(line, True) for line in text.split("\n")]
    out = []
    for i, part in enumerate(re.split(r"^```.*$", text, flags=re.M)):
        fenced = i % 2 == 1
        if not fenced:
            part = re.sub(r"`[^`]{1,400}?`",
                          lambda m: re.sub(r"\s*\n\s*", " ", m.group(0)),
                          part)
        out.extend((line, fenced) for line in part.split("\n"))
    return out


def clean(token: str) -> str:
    return token.strip("`'\"()[],;:").rstrip(".")


def commands(path: str):
    """-> [(kind, name)]: ("file", path), ("module", dotted) and
    ("make", target) for everything ``path`` tells its reader to run."""
    found = []
    for line, is_code in logical_lines(path):
        tokens = line.split()
        for i, raw in enumerate(tokens):
            word = clean(raw)
            rest = tokens[i + 1:]
            ends_span = raw.endswith("`") and len(raw) > 1
            if ends_span or not rest:
                continue
            if word in ("python", "python3", "bash"):
                first = clean(rest[0])
                if first == "-m" and word != "bash" and len(rest) > 1:
                    module = clean(rest[1])
                    if "$" in module:
                        continue
                    if module == "pytest":
                        found.extend(pytest_paths(rest[2:]))
                    elif module.split(".")[0] not in OUTSIDE_MODULES:
                        found.append(("module", module))
                elif re.fullmatch(r"[\w./-]+\.(py|sh)", first):
                    found.append(("file", first))
            elif word == "pytest" and clean(tokens[i - 1]) != "-m":
                found.extend(pytest_paths(rest))
            elif word == "make" and (raw.startswith("`")
                                     or (is_code and i == 0)):
                for target in make_targets(rest):
                    found.append(("make", target))
    return found


def pytest_paths(tokens):
    out, skip = [], False
    for raw in tokens:
        if skip:
            skip = False
        elif raw in STOP:
            break
        elif raw in PYTEST_VALUE_OPTIONS:
            skip = True
        else:
            word = clean(raw).split("::")[0]
            if word.startswith("tests/") and "$" not in word:
                out.append(("file", word))
        if raw.endswith("`"):
            break
    return out


def make_targets(tokens):
    """``make a / b / c`` names three targets; the list ends with the
    backticked span or at the first word that is no target name."""
    out = []
    for raw in tokens:
        word = clean(raw)
        if word == "/":
            continue
        if not re.fullmatch(r"[a-z][a-z0-9-]*", word):
            break
        out.append(word)
        if raw.endswith("`"):
            break
    return out


def makefile_targets():
    with open(os.path.join(REPO, "Makefile")) as f:
        return set(re.findall(r"^([a-z][a-z0-9-]*):", f.read(), flags=re.M))


def exists(kind: str, name: str, targets) -> bool:
    if kind == "make":
        return name in targets
    if kind == "file":
        return os.path.exists(os.path.join(REPO, name))
    base = os.path.join(REPO, *name.split("."))          # a module
    return os.path.isfile(base + ".py") \
        or os.path.isfile(os.path.join(base, "__init__.py"))


@pytest.mark.parametrize("path", DOCUMENTS)
def test_every_command_a_document_names_exists(path):
    found = commands(path)
    assert found, f"{path} tells its reader to run nothing this test reads"
    targets = makefile_targets()
    missing = [f"{kind} {name}" for kind, name in found
               if not exists(kind, name, targets)]
    assert not missing, f"{path} names what is not in the tree: {missing}"


def test_the_documents_name_the_same_tier_1_run():
    """What follows ROADMAP.md's "Tier-1 verify" line and the verify skill
    both say how the driver runs the suite: the same worker count and the
    same ``--dist`` mode (a string comparison; the run itself is
    ``/root/TESTS_LAST_RUN.json``'s)."""
    told = {}
    for path in ("ROADMAP.md", ".claude/skills/verify/SKILL.md"):
        with open(os.path.join(REPO, path)) as f:
            text = f.read()
        if path == "ROADMAP.md":
            text = text[text.index("**Tier-1 verify:**"):]
        run = re.search(r"-p xdist -n (\d+) --dist (\w+)",
                        re.sub(r"\s+", " ", text))
        assert run, f"{path} names no worker count and --dist mode"
        told[path] = run.groups()
    assert len(set(told.values())) == 1, told
