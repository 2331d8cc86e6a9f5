#!/bin/bash
# Nightly tier: the full sweeps tier-1 defers.
#
# Reference model: jenkins/spark-tests.sh + the nightly integration
# Jenkinsfiles run every TPC-DS/TPC-H query and the fuzz suites against
# real hardware each night.  Here:
#   * all 99 TPC-DS + all 22 TPC-H queries verified vs the host oracle
#     at SF0.01 (TPCDS_FULL/TPCH_FULL flip the smoke subsets to full
#     sweeps),
#   * the fuzz suites with a fresh random seed,
#   * the cross-process TCP shuffle tests (real second process).
#
# Usage: ci/nightly.sh  (writes artifacts/ci_nightly_<utc-date>.txt)
set -euo pipefail
cd "$(dirname "$0")/.."

STAMP=$(date -u +%Y%m%dT%H%M%SZ)
OUT="artifacts/ci_nightly_${STAMP}.txt"
mkdir -p artifacts

{
  echo "== nightly @ ${STAMP} (commit $(git rev-parse --short HEAD)) =="
  echo "-- static analysis: enginelint --strict --"
  python -m tools.enginelint spark_rapids_tpu/ --strict
  echo "-- full TPC-DS (99) + TPC-H (22) oracle sweeps --"
  TPCDS_FULL=1 TPCH_FULL=1 python -m pytest \
    tests/test_tpcds.py tests/test_tpch.py -q --durations=20
  echo "-- fuzz + transport --"
  python -m pytest tests/test_fuzz.py tests/test_tcp_shuffle.py \
    tests/test_shuffle_transport.py -q
  echo "== nightly PASS =="
} 2>&1 | tee "$OUT"
