"""repro-lint: AST-based static checks for this repo's DESIGN.md contracts.

Rule families (see DESIGN.md §13 for the contract each one pins):

  RPL1xx  jit-purity / recompile hazards        (DESIGN §5, §6)
  RPL2xx  dtype discipline (f32 device / f64 host oracle)  (DESIGN §2, §8)
  RPL3xx  serve-plane lock discipline            (DESIGN §9, §10, §11)
  RPL4xx  Pallas / kernel hygiene                (DESIGN §2, §6, §12)

Entry point: ``python -m tools.lint src tests scripts``.
Suppress a finding inline with ``# repro-lint: disable=RPL101`` (same line
or a standalone comment line directly above).  Grandfathered findings live
in ``tools/lint/baseline.txt``; quarantine a whole template-era file with a
``# repro-lint: legacy-template`` comment near its top.
"""

from tools.lint.cli import lint_paths, main  # noqa: F401
from tools.lint.framework import FileContext, Finding, Rule  # noqa: F401
