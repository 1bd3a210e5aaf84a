#!/usr/bin/env sh
# Tier-1 CI: CPU-only (JAX_PLATFORMS=cpu), offline, collection-strict.
#
# Fails on the first error *including* module collection errors (a module
# that fails to import is a hard failure, not a skip) — pytest exits
# non-zero on collection errors, and --strict-markers turns unknown
# marks (typo'd @pytest.mark.slow etc.) into errors too.
#
# Tier-1 collects every tests/test_*.py; the churn smoke (delete+insert
# cycles with consolidation) is one of them, in tests/test_maintenance.py.
set -eu
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

python -m pytest --collect-only -q >/dev/null   # collection gate
python -m pytest --strict-markers -q "$@"
