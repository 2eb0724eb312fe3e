"""A document names only paths that exist.

One case a document, and one for the comments and docstrings of the
package, ``tools/`` and ``examples/``. A path is a word of a backticked
or quoted span, or of a fenced block, that ends in ``.py``, ``.md``, ``.json``, ``.jsonl``, ``.cpp`` or
``/`` and starts with an entry of the checkout's root (or of the
package's root: ``utils/native.py``); a source or document without a
slash is looked up by its file name anywhere in the checkout
(``chip_smoke.py``, ``async_train.py``), while a bare ``.json`` /
``.jsonl`` is what a run writes (``trace.json``) and is skipped. A ``:line`` or ``::name`` suffix is cut; globs and
``<...>`` placeholders are skipped. ``ROADMAP.md``, ``CHANGES.md`` and
``PERF.md`` are history and are not cases.
"""

import ast
import glob
import io
import os
import re
import tokenize

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "pytorch_ps_mpi_tpu")

DOCS = (
    "README.md", "Makefile", "PARITY.md", "docs/ARCHITECTURE.md",
    "docs/OPERATIONS.md", "docs/RESULTS.md", "docs/CODEC_ECONOMICS.md",
    ".claude/skills/verify/SKILL.md",
)
SOURCES = ("pytorch_ps_mpi_tpu/**/*.py", "tools/*.py", "examples/*.py")

#: files of the reference implementation (SURVEY.md), which the parity
#: notes cite by name beside this repo's own, and the user's own script
REFERENCE_FILES = frozenset({
    "mpi_comms.py", "comms.py", "codings.py", "svd.py", "qsgd.py",
    "train.py", "setup.py", "test_mpi.py", "script.py",
})

_FENCED = re.compile(r"^[ \t]*```.*?^[ \t]*```", re.S | re.M)
_QUOTED = re.compile(r"`+([^`\n]+?)`+|\"([^\"\n]+?)\"|'([^'\n]+?)'")
_PATH = re.compile(r"^[\w.\-]+(/[\w.\-]+)*/?$")
_ENDS = (".py", ".md", ".json", ".jsonl", ".cpp", "/")
_PRUNED = {"__pycache__", "chiprun_out", "_build"}


def _file_names():
    names = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _PRUNED
                   and (not d.startswith(".") or d == ".claude")]
        names.update(files)
    return names


FILE_NAMES = _file_names()


def _tokens(text, words=False):
    if words:
        return text.split()
    spans = _FENCED.findall(text)
    spans += [next(g for g in m.groups() if g)
              for m in _QUOTED.finditer(_FENCED.sub("", text))]
    return [word for span in spans for word in span.split()]


def _exists(tok):
    """Whether the path token names something, or None where it is not
    a path of this checkout at all."""
    if "/" not in tok.rstrip("/"):
        if tok.endswith("/"):  # a bare directory that is not ours is prose
            return (os.path.isdir(os.path.join(REPO, tok))
                    or os.path.isdir(os.path.join(PACKAGE, tok)) or None)
        if tok.endswith((".json", ".jsonl")):  # what a run writes
            return None
        return tok in FILE_NAMES or tok in REFERENCE_FILES
    head = tok.split("/", 1)[0]
    roots = [r for r in (REPO, PACKAGE)
             if os.path.exists(os.path.join(r, head))]
    if not roots:
        return None
    return any(os.path.exists(os.path.join(r, tok)) for r in roots)


def missing_paths(text, words=False):
    """The path tokens of ``text`` that name nothing in the checkout."""
    missing = set()
    for tok in _tokens(text, words):
        tok = re.sub(r"(::|:\d).*$", "", tok.strip("`\"'(),;"))
        if (tok.endswith(_ENDS) and _PATH.match(tok)
                and (tok.startswith(".claude/") or tok[0] not in "/.")
                and _exists(tok) is False):
            missing.add(tok)
    return sorted(missing)


def _comments_and_docstrings(path):
    with open(path, encoding="utf-8") as f:
        src = f.read()
    out = [t.string for t in tokenize.generate_tokens(io.StringIO(src).readline)
           if t.type == tokenize.COMMENT]
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            out.append(ast.get_docstring(node, clean=False) or "")
    return "\n".join(out)


@pytest.mark.parametrize("doc", DOCS + ("sources",))
def test_a_document_names_only_paths_that_exist(doc):
    if doc == "sources":
        missing = {}
        for pattern in SOURCES:
            for path in sorted(glob.glob(os.path.join(REPO, pattern),
                                         recursive=True)):
                gone = missing_paths(_comments_and_docstrings(path))
                if gone:
                    missing[os.path.relpath(path, REPO)] = gone
    else:
        with open(os.path.join(REPO, doc), encoding="utf-8") as f:
            missing = missing_paths(f.read(), words=doc == "Makefile")
    assert not missing, f"{doc} names paths that do not exist: {missing}"


def test_the_check_sees_a_retired_path():
    """The rule bites: the names this repository retired are found
    missing, bare or under a directory that is still there."""
    text = ("run `bench.py`, then ``benchmarks/tree_bench.py --quick``; "
            "see `utils/tracing.py:55` and \"benchmarks/agg_bench.py\"")
    assert missing_paths(text) == [
        "bench.py", "benchmarks/agg_bench.py", "benchmarks/tree_bench.py",
        "utils/tracing.py"]
    assert missing_paths("`chip_smoke.py`, `tests/test_ps.py::test_x`, "
                         "`chipbench/`, `<name>.jsonl`, `BENCH_r*.json`") == []
