#!/usr/bin/env sh
# Tier-1 CI: CPU-only (JAX_PLATFORMS=cpu), offline, collection-strict.
#
# Fails on the first error *including* module collection errors (a module
# that fails to import is a hard failure, not a skip) — pytest exits
# non-zero on collection errors, and --strict-markers turns unknown
# marks (typo'd @pytest.mark.slow etc.) into errors too.
#
# Tier-1 collects every tests/test_*.py, including the fan-out suites
# (tests/test_search_many.py, tests/test_insert_many.py).  After the
# suite, the collection-gated smoke step drives the mixed
# search+insert fan-out benchmark end-to-end at CI scale (writes
# experiments/concurrent/fig11.json).
set -eu
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu

python -m pytest --collect-only -q >/dev/null   # collection gate
python -m pytest --strict-markers -q "$@"

PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.concurrent --smoke

# Kernel parity (the standalone Pallas kernels in interpret mode vs the
# jnp ops the engine runs) + traversal-state scaling (hashed visited sets
# must be flat in n_max); both exit non-zero on violation.
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.kernel_parity
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.footprint --state-scaling

# Churn smoke (maintenance subsystem): delete+insert cycles with
# consolidation on — exits non-zero if any insert drops, recall degrades
# beyond tolerance of the fresh-build baseline, or live-vertex search
# results change across a consolidation pass.
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.churn --smoke
