#!/usr/bin/env bash
# graftnum: the repo's jaxpr-level numerics & determinism audit (rules
# NU001-NU005, see README "Numerics auditing"). Runs from any cwd;
# extra args pass through (e.g. `bash scripts/num.sh --list-rules`,
# `--no-baseline`, `--write-baseline`, `--report`, `--journal`).
#
# Unlike graftlint/graftsync this pass traces: it walks every
# registered round program's ClosedJaxpr (the round variants, the
# state-motion programs, and the scanned span) with a dtype/finiteness
# dataflow lattice — NaN-unsafe mask arithmetic, the PRECISION_SEAMS
# downcast registry, zero-guarded denominators, replay-determinism —
# and prices cross-shard psum reassociation as a per-program
# worst-case ulp bound gated exact-match against graftnum.baseline.json.
#
# Exit codes (the graftaudit/graftmesh/graftsync contract): 0 clean,
# 1 rule violations, 2 baseline drift only (regenerate with
# --write-baseline and commit the diff). The shipped violations
# baseline is EMPTY.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu python -m commefficient_tpu.analysis.numaudit "$@"
