#!/usr/bin/env python
"""Docs checks, run by the CI ``docs`` job:

1. every intra-repo markdown link in README.md / ROADMAP.md / docs/*.md
   resolves to an existing file (http/mailto/anchor links are skipped,
   fenced code blocks and inline code spans are ignored);
2. every fenced ```python block in docs/*.md that contains doctest
   prompts (``>>>``) runs clean under doctest — blocks within one file
   share a namespace, so examples can build on each other;
3. stale-reference check: every `module.py` / `function()` inline-code
   reference in docs/*.md resolves to a real file / a real ``def`` or
   ``class`` somewhere in the repo's python sources, so renames can't
   silently strand the documentation.

    python tools/check_docs.py          # exits nonzero on any failure
"""

from __future__ import annotations

import builtins
import doctest
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
FENCE_RE = re.compile(r"```python\n(.*?)```", re.DOTALL)
_SKIP_SCHEMES = ("http://", "https://", "mailto:", "#")

# stale-reference patterns over inline code spans (see check_code_refs):
# a `path/to/module.py` file reference, or a `name(...)` call reference
# (no nested parens — those are full expressions, not references).
INLINE_CODE_RE = re.compile(r"`([^`\n]+)`")
FILE_REF_RE = re.compile(r"^[\w./-]+\.py$")
CALL_REF_RE = re.compile(r"^[A-Za-z_][\w.]*\([^()]*\)$")
_PY_DIRS = ("src", "bench", "benchmarks", "tools", "examples", "tests")


def _md_files() -> list[pathlib.Path]:
    files = [ROOT / "README.md", ROOT / "ROADMAP.md"]
    files += sorted((ROOT / "docs").glob("*.md"))
    return [f for f in files if f.exists()]


def _strip_code(text: str) -> str:
    """Remove fenced blocks and inline code spans so bracket/paren
    patterns inside code never read as markdown links."""
    text = re.sub(r"```.*?```", "", text, flags=re.DOTALL)
    return re.sub(r"`[^`]*`", "", text)


def check_links(files) -> list[str]:
    errors = []
    for md in files:
        for target in LINK_RE.findall(_strip_code(md.read_text())):
            if target.startswith(_SKIP_SCHEMES):
                continue
            rel = target.split("#", 1)[0]
            if not rel:
                continue
            if not (md.parent / rel).resolve().exists():
                errors.append(f"{md.relative_to(ROOT)}: broken link -> "
                              f"{target}")
    return errors


def _py_files() -> list[pathlib.Path]:
    out: list[pathlib.Path] = []
    for sub in _PY_DIRS:
        out += sorted((ROOT / sub).rglob("*.py"))
    return out


def check_code_refs(files) -> tuple[list[str], int]:
    """Stale-reference check over docs/*.md: a `module.py` span must name
    a file that exists in the repo (matched by path suffix, so both
    `core/obs.py` and `src/repro/core/obs.py` work), and a `name(...)`
    span must name a ``def``/``class`` defined somewhere in the python
    sources (dotted spans check the last component, so
    `CounterTimeline.load()` checks ``load``).  Returns
    ``(errors, refs_checked)``."""
    py = _py_files()
    paths = {str(p.relative_to(ROOT)) for p in py}
    source = "\n".join(p.read_text() for p in py)
    errors: list[str] = []
    checked = 0
    for md in files:
        text = re.sub(r"```.*?```", "", md.read_text(), flags=re.DOTALL)
        for span in INLINE_CODE_RE.findall(text):
            span = span.strip()
            if FILE_REF_RE.match(span):
                checked += 1
                if not any(p == span or p.endswith("/" + span)
                           for p in paths):
                    errors.append(f"{md.relative_to(ROOT)}: stale file "
                                  f"reference `{span}`")
            elif CALL_REF_RE.match(span):
                name = span.split("(", 1)[0].rsplit(".", 1)[-1]
                if hasattr(builtins, name):
                    continue       # `len(samples)` isn't a repo reference
                checked += 1
                if not re.search(rf"^\s*(?:def|class)\s+{re.escape(name)}\b",
                                 source, re.MULTILINE):
                    errors.append(f"{md.relative_to(ROOT)}: stale function "
                                  f"reference `{span}`")
    return errors, checked


def run_doctests(files) -> tuple[list[str], int]:
    errors, n_examples = [], 0
    parser = doctest.DocTestParser()
    runner = doctest.DocTestRunner(verbose=False,
                                   optionflags=doctest.ELLIPSIS)
    for md in files:
        blocks = [b for b in FENCE_RE.findall(md.read_text()) if ">>>" in b]
        if not blocks:
            continue
        # one shared namespace per file: later blocks may use earlier names
        test = parser.get_doctest("\n".join(blocks), {},
                                  str(md.relative_to(ROOT)), str(md), 0)
        n_examples += len(test.examples)
        out: list[str] = []
        result = runner.run(test, out=out.append)
        if result.failed:
            errors.append(f"{md.relative_to(ROOT)}: {result.failed} doctest "
                          f"failure(s)\n" + "".join(out))
    return errors, n_examples


def main() -> int:
    files = _md_files()
    docs = [f for f in files if f.parent.name == "docs"]
    link_errors = check_links(files)
    doc_errors, n_examples = run_doctests(docs)
    ref_errors, n_refs = check_code_refs(docs)
    for e in link_errors + doc_errors + ref_errors:
        print(f"FAIL {e}", file=sys.stderr)
    n_links = sum(len(LINK_RE.findall(_strip_code(f.read_text())))
                  for f in files)
    n_fail = len(link_errors) + len(doc_errors) + len(ref_errors)
    print(f"checked {len(files)} markdown files: {n_links} links, "
          f"{n_examples} doctest examples, {n_refs} code references; "
          f"{n_fail} failure(s)")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
