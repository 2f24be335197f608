"""The markdown docs are checked like code: README.md and docs/*.md.

Each file's fenced ``python`` blocks run through ``check_docs.py``'s
per-file runner in a subprocess of their own: README.md registers a
backend ``before="fused_causal"``, which in-process would change ``auto``
resolution for every later test file on the same worker.  Each file also
names only what exists: repo paths in backticks, ``python -m`` modules
and ``python <file>.py`` scripts.
"""
import os
import pathlib
import re
import subprocess
import sys
from importlib.machinery import PathFinder

import pytest

from check_docs import DEFAULT_FILES

ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
CHECK_DOCS = ROOT / "tests" / "check_docs.py"

#: backticked repo paths; run-time paths (``.jax_cache/``) and paths relative
#: to ``src/repro/`` (``kernels/fused_ce``) are not meant to exist from the root
PATH = re.compile(r"`((?:src|tests|examples|docs|bench)/[^`\s]*)[^`]*`")
MODULE = re.compile(r"\bpython3? -m ([A-Za-z_][\w.]*\w)")
SCRIPT = re.compile(r"\bpython3? ([\w./-]+\.py)\b")


@pytest.mark.parametrize("doc", DEFAULT_FILES)
def test_doc_examples_run(doc):
    env = dict(os.environ, PYTHONPATH=str(SRC))
    env.pop("XLA_FLAGS", None)  # the examples are written for one device
    out = subprocess.run([sys.executable, str(CHECK_DOCS), doc], cwd=ROOT,
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout + out.stderr)[-4000:]


def _module_exists(name: str) -> bool:
    """Whether ``python -m name`` would find ``name``, importing nothing."""
    path = [str(SRC), str(ROOT), *sys.path]
    for part in name.split("."):
        spec = PathFinder.find_spec(part, path)
        if spec is None:
            return False
        path = list(spec.submodule_search_locations or [])
    return True


@pytest.mark.parametrize("doc", DEFAULT_FILES)
def test_doc_names_only_what_exists(doc):
    text = (ROOT / doc).read_text()
    missing = [p for p in PATH.findall(text)
               if not (ROOT / p.split(":")[0]).exists()]
    missing += [f"python -m {m}" for m in MODULE.findall(text)
                if not _module_exists(m)]
    missing += [f"python {s}" for s in SCRIPT.findall(text)
                if not (ROOT / s).exists()]
    assert not missing, f"{doc} names what does not exist: {missing}"
