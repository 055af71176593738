"""The documents name files that exist.

One case per document: every path in it that begins with one of this
repository's top-level directories, and every bare ``*.py`` / ``*.json``
name in backticks, resolves in the tree. A path with another root
(``/root/reference/docs/api.rst``, ``horovod/common/basics.py``,
``$HVD_HISTORY_DIR/run-manifest.json``) is not this repository's and is
not looked at; neither is a name with a placeholder in it
(``<script>.py``, ``step-<n>/``) or a glob's tail (``configs/*.json``
is held to ``configs/``).
"""

import glob
import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_DIRS = ("horovod_tpu", "tools", "examples", "tests", "benchmarks",
            "ci", "docs", "bin")
DOCUMENTS = sorted(
    ["README.md", "examples/README.md", "Makefile", "ci/run_tests.sh",
     ".github/workflows/ci.yml", ".claude/skills/verify/SKILL.md"]
    + [os.path.relpath(p, ROOT)
       for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))])

# a top-level directory at the start of a path (not in the middle of a
# longer one), then what a path is made of, ending on a name or a slash
_PATH = re.compile(r"(?<![\w/.-])((?:%s)/[\w./-]*[\w/])"
                   % "|".join(TOP_DIRS))
_BARE = re.compile(r"`([\w.-]+\.(?:py|json))`")


def _basenames():
    """Names of the files git tracks or would add (a bare
    ``engine.py`` may mean ``serving/engine.py``)."""
    out = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
        cwd=ROOT, capture_output=True, text=True)
    if out.returncode == 0 and out.stdout:
        files = out.stdout.split("\n")
    else:  # not a checkout: the files on disk
        files = [os.path.join(d, f) for d, _, fs in os.walk(ROOT)
                 for f in fs]
    return {os.path.basename(f) for f in files}


def dangling(text, basenames):
    missing = {m.group(1) for m in _PATH.finditer(text)
               if not os.path.exists(os.path.join(ROOT, m.group(1)))}
    missing |= {m.group(1) for m in _BARE.finditer(text)
                if m.group(1) not in basenames}
    return sorted(missing)


@pytest.fixture(scope="module")
def basenames():
    return _basenames()


def test_the_rule_sees_what_it_should(basenames):
    text = ("run `bench_gone.py`, then tools/no_such_tool.py:12 and "
            "`docs/missing.md`; tests/test_docs_refs.py::test_x, "
            "`chip_smoke.py`, `engine.py`, benchmarks/configs/*.json, "
            "/root/reference/docs/api.rst, horovod/common/basics.py, "
            "`$TMPDIR/run-manifest.json` and examples/<script>.py stay.")
    assert dangling(text, basenames) == [
        "bench_gone.py", "docs/missing.md", "tools/no_such_tool.py"]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_files_that_exist(document, basenames):
    with open(os.path.join(ROOT, document)) as f:
        assert dangling(f.read(), basenames) == []
