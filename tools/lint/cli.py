"""Command-line entry point: ``python -m tools.lint src tests scripts``.

Exit codes:
  0  clean (no findings beyond the committed baseline)
  1  new findings
  2  usage error, unparsable file, or baseline drift (stale entries)
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

from tools.lint import baseline as baseline_mod
from tools.lint.framework import FileContext, Finding, iter_py_files
from tools.lint.rules import all_rules

DEFAULT_BASELINE = "tools/lint/baseline.txt"


@dataclass
class LintResult:
    new: list[Finding] = field(default_factory=list)
    grandfathered: list[Finding] = field(default_factory=list)
    stale: list[baseline_mod.BaselineEntry] = field(default_factory=list)
    errors: list[str] = field(default_factory=list)
    n_files: int = 0
    n_legacy: int = 0

    @property
    def exit_code(self) -> int:
        if self.errors or self.stale:
            return 2
        return 1 if self.new else 0


def lint_paths(
    paths: list[str | Path],
    *,
    root: str | Path = ".",
    baseline_path: str | Path | None = DEFAULT_BASELINE,
    update_baseline: bool = False,
    select: set[str] | None = None,
) -> LintResult:
    root = Path(root)
    rules = all_rules()
    if select:
        rules = [r for r in rules if r.code in select]
    result = LintResult()
    findings: list[Finding] = []
    ctxs: list[FileContext] = []

    for f, rel in iter_py_files([Path(p) for p in paths], root):
        result.n_files += 1
        try:
            ctx = FileContext(f, rel, f.read_text())
        except SyntaxError as e:
            result.errors.append(f"{rel}:{e.lineno or 0}: syntax error: {e.msg}")
            continue
        if ctx.legacy:
            result.n_legacy += 1
            continue
        ctxs.append(ctx)

    for ctx in ctxs:
        for rule in rules:
            for finding in rule.check(ctx):
                if not ctx.is_suppressed(finding):
                    findings.append(finding)
    by_rel = {ctx.rel: ctx for ctx in ctxs}
    for rule in rules:
        for finding in rule.check_project(ctxs):
            ctx = by_rel.get(finding.path)
            if ctx is None or not ctx.is_suppressed(finding):
                findings.append(finding)

    if baseline_path is None:
        result.new = sorted(findings)
        return result

    bpath = baseline_path if Path(baseline_path).is_absolute() else root / baseline_path
    bpath = Path(bpath)
    if update_baseline:
        baseline_mod.write(bpath, findings)
        result.grandfathered = sorted(findings)
        return result
    try:
        entries = baseline_mod.load(bpath)
    except baseline_mod.BaselineError as e:
        result.errors.append(str(e))
        return result
    result.errors.extend(baseline_mod.check_drift(entries, root))
    result.new, result.grandfathered, result.stale = baseline_mod.partition(findings, entries)
    return result


def render_json(res: LintResult) -> str:
    """Machine-readable findings document (shared schema with
    ``tools.audit``) — the CI artifact format."""
    findings = [dict(dataclasses.asdict(f), status="new") for f in res.new]
    findings += [dict(dataclasses.asdict(f), status="baselined") for f in res.grandfathered]
    return json.dumps(
        {
            "tool": "repro-lint",
            "findings": findings,
            "errors": res.errors,
            "stale_baseline": [dataclasses.asdict(e) for e in res.stale],
            "summary": {
                "files": res.n_files,
                "legacy_quarantined": res.n_legacy,
                "new": len(res.new),
                "baselined": len(res.grandfathered),
            },
            "exit_code": res.exit_code,
        },
        indent=1,
    )


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.lint",
        description="repro-lint: static checks for this repo's DESIGN.md contracts",
    )
    ap.add_argument("paths", nargs="*", default=["src", "tests", "scripts"])
    ap.add_argument("--root", default=".", help="repo root (paths resolve against it)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument(
        "--no-baseline",
        action="store_true",
        help="report every finding, ignoring the baseline",
    )
    ap.add_argument(
        "--update-baseline",
        action="store_true",
        help="rewrite the baseline from the current findings",
    )
    ap.add_argument(
        "--select",
        default=None,
        help="comma-separated rule codes to run (e.g. RPL101,RPL302)",
    )
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument(
        "--format",
        choices=("text", "json"),
        default="text",
        help="json emits the machine-readable findings document CI archives",
    )
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in all_rules():
            print(f"{r.code}  {r.name:28s} {r.doc}")
        return 0

    select = None
    if args.select:
        select = {c.strip() for c in args.select.split(",") if c.strip()}
    res = lint_paths(
        args.paths,
        root=args.root,
        baseline_path=None if args.no_baseline else args.baseline,
        update_baseline=args.update_baseline,
        select=select,
    )
    if args.format == "json":
        print(render_json(res))
    else:
        for f in res.new:
            print(f.render())
    for err in res.errors:
        print(f"error: {err}", file=sys.stderr)
    for e in res.stale:
        print(f"stale baseline entry (drifted or fixed): {e.render()}", file=sys.stderr)
    if args.update_baseline:
        print(f"baseline updated: {len(res.grandfathered)} entr"
              f"{'y' if len(res.grandfathered) == 1 else 'ies'}")
    summary = (
        f"{res.n_files} files checked ({res.n_legacy} legacy-template quarantined), "
        f"{len(res.new)} new finding(s), {len(res.grandfathered)} baselined"
    )
    print(summary, file=sys.stderr)
    if res.stale:
        print(
            "baseline drift: run `python -m tools.lint --update-baseline` after "
            "verifying the grandfathered findings really moved or were fixed",
            file=sys.stderr,
        )
    return res.exit_code


if __name__ == "__main__":
    sys.exit(main())
