# Dev entry points.  PYTHONPATH is injected so targets work from a clean
# checkout; see README.md for what each target covers.

PYTHON ?= python
PYTEST_FLAGS ?= -x -q
export PYTHONPATH := src:$(PYTHONPATH)

# ruff-format adoption list: files here are kept black-clean; the
# pre-existing tree is linted (ruff check) but not reflowed wholesale.
FORMAT_PATHS ?= tools/lint tools/audit \
  src/repro/serving/tenants.py src/repro/core/device_table.py

.PHONY: test test-multidevice docs-links lint lint-deep audit check

test:
	$(PYTHON) -m pytest $(PYTEST_FLAGS)

# Simulated multi-device leg (DESIGN.md §12): the sharding / streaming /
# parity suites with 8 host devices forced, so shard_map really runs
# 8-way.  Plain `make test` keeps the single real CPU device on purpose
# (tests/conftest.py) — this target is the only one that overrides it.
test-multidevice:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PYTHON) -m pytest $(PYTEST_FLAGS) tests/test_mesh_sharding.py \
	  tests/test_sharding.py tests/test_streaming.py tests/test_bubble_flat.py \
	  tests/test_grid_pruning.py

docs-links:
	$(PYTHON) scripts/check_docs_links.py

lint:
	ruff check .
	ruff format --check $(FORMAT_PATHS)

# repro-lint (tools/lint): AST-level contract checks — jit purity (RPL1xx),
# dtype discipline (RPL2xx), serve-plane lock discipline (RPL3xx), kernel
# hygiene (RPL4xx).  Exit 1 = new findings, exit 2 = baseline drift.
lint-deep:
	$(PYTHON) -m tools.lint src tests scripts

# jaxpr-audit (tools/audit): abstract-trace contract analysis over every
# registered jit entry point (DESIGN.md §14) — f64/callback/pow-2/dense
# rules (RPL50x), recompile-churn gate, golden lowering digests.
# Regenerate goldens deliberately: python -m tools.audit --update-golden
audit:
	$(PYTHON) -m tools.audit

check: docs-links lint lint-deep audit test
