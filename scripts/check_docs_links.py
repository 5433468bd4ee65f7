#!/usr/bin/env python
"""Fail if any `DESIGN.md §N` citation in source docstrings/comments does
not resolve to an actual section heading in DESIGN.md (the `docs-links`
Makefile target).

A citation is any occurrence of ``DESIGN.md §N`` (or ``DESIGN.md §N,``
etc.) under src/, tests/ or examples/.  A section heading is
a markdown heading line in DESIGN.md containing the same §N token.
"""

from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
CITE = re.compile(r"DESIGN\.md[^§\n]{0,20}§(\d+)")
SCAN_DIRS = ("src", "tests", "examples")


def main() -> int:
    design = ROOT / "DESIGN.md"
    if not design.exists():
        print("docs-links: DESIGN.md is missing")
        return 1
    headings = set()
    for line in design.read_text().splitlines():
        if line.lstrip().startswith("#"):
            headings.update(re.findall(r"§(\d+)", line))

    failures = []
    n_cites = 0
    for d in SCAN_DIRS:
        for py in (ROOT / d).rglob("*.py"):
            text = py.read_text()
            for m in CITE.finditer(text):
                n_cites += 1
                sec = m.group(1)
                if sec not in headings:
                    line_no = text.count("\n", 0, m.start()) + 1
                    failures.append(f"{py.relative_to(ROOT)}:{line_no}: cites DESIGN.md §{sec}, no such heading")

    if failures:
        print("\n".join(failures))
        print(f"docs-links: {len(failures)} dangling citation(s) out of {n_cites}")
        return 1
    print(f"docs-links: OK — {n_cites} citations, all resolve (headings: {sorted(headings, key=int)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
