"""Execute every fenced ``python`` block in the repo's markdown docs.

``tests/test_docs.py`` runs this once per file, so README / docs examples
cannot rot: each markdown file's ```` ```python ```` blocks run
top-to-bottom in ONE shared namespace per file (so a later block may use
names an earlier block defined), with assertions live.  A block whose last preceding non-blank
line is the marker comment

    <!-- notest -->

is skipped (examples needing hardware — a TPU mesh, 8 devices — or that
are intentionally illustrative fragments).  ``bash``/``text``/unlabeled
fences are never executed.

    PYTHONPATH=src python tests/check_docs.py            # README + docs/
    PYTHONPATH=src python tests/check_docs.py docs/serving.md
"""
from __future__ import annotations

import re
import sys
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
DEFAULT_FILES = ["README.md", *sorted(
    str(p.relative_to(ROOT)) for p in (ROOT / "docs").glob("*.md"))]
FENCE = re.compile(r"^```(\w*)\s*$")
MARKER = "<!-- notest -->"


def extract_blocks(text: str):
    """Yield (start_line, code, skipped) for each fenced python block."""
    lines = text.splitlines()
    i = 0
    last_nonblank = ""
    while i < len(lines):
        m = FENCE.match(lines[i])
        if not m:
            if lines[i].strip():
                last_nonblank = lines[i].strip()
            i += 1
            continue
        lang, start = m.group(1), i + 1
        body = []
        i += 1
        while i < len(lines) and not lines[i].strip().startswith("```"):
            body.append(lines[i])
            i += 1
        i += 1  # closing fence
        if lang == "python":
            yield start, "\n".join(body), last_nonblank == MARKER
        last_nonblank = ""  # a fence resets the marker either way
    return


def run_file(path: Path) -> tuple[int, int, list[str]]:
    """Run one markdown file's python blocks; return (ran, skipped, errors)."""
    ns: dict = {"__name__": f"docs:{path.name}"}
    ran = skipped = 0
    errors: list[str] = []
    for start, code, skip in extract_blocks(path.read_text()):
        if skip:
            skipped += 1
            continue
        try:
            exec(compile(code, f"{path}:{start}", "exec"), ns)  # noqa: S102
            ran += 1
        except Exception:
            errors.append(
                f"{path}:{start}: block failed\n{traceback.format_exc()}")
    return ran, skipped, errors


def main(argv: list[str]) -> int:
    files = argv or DEFAULT_FILES
    failures: list[str] = []
    for rel in files:
        path = ROOT / rel
        if not path.exists():
            failures.append(f"{rel}: no such file")
            continue
        ran, skipped, errors = run_file(path)
        status = "FAIL" if errors else "ok"
        print(f"[docs] {rel}: {ran} blocks ran, {skipped} skipped [{status}]")
        failures.extend(errors)
    for f in failures:
        print(f, file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
