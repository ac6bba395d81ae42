# Operator/CI entrypoints (reference analogue: /root/reference/Makefile:79-111
# — compile/test/dialyzer/elvis).  This image has no third-party
# linter, so `lint` runs the stdlib AST gate; ruff/mypy configs live in
# pyproject.toml for hosts that have them.  Speed is measured by the one
# benchmark (BENCHMARK.json, benchmarks/run.py, PERF.md), on the chip.

PY ?= python

.PHONY: test smoke chaos native native-check lint multichip all

all: lint smoke

# full suite without the slow soaks (those are opt-in via `make chaos`)
test:
	$(PY) -m pytest tests/ -q -m 'not slow'

# the whole fault-injection suite INCLUDING the slow soaks: seeded
# partitions, endpoint crash/restart, drop/dup/delay storms, mid-handoff
# crashes — every scenario ends with byte-identical converged snapshots
chaos:
	$(PY) -m pytest tests/test_chaos.py -q

# native planes (ISSUE 16): rebuild BOTH checked-in .so's (inter-DC
# pump + serving front-end) with the ONE pinned flag set, embedding
# each source's sha256; `native-check` fails CI when a checked-in
# binary was built from different source than what's in the tree (the
# drift a hand-run g++ line can't detect)
native:
	$(PY) -m antidote_tpu.native_build

native-check:
	$(PY) -m antidote_tpu.native_build --check

# fast fundamental tier, <90s: clocks, router, WAL, metadata, txn layer,
# wire codecs, store tables, observability, console, supervision
smoke:
	$(PY) -m pytest -q -m smoke

lint:
	$(PY) tools/lint.py
	@if command -v ruff >/dev/null 2>&1; then ruff check .; fi

multichip:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('ok')"
