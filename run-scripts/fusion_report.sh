#!/usr/bin/env bash
# Fused-vs-unfused dispatch report: runs the WordCount and PageRank
# example pipelines with program stitching on (default) and with
# THRILL_TPU_FUSE=0, checks exact result parity, and prints the device
# dispatch counts + delta per pipeline.
#
# Usage: run-scripts/fusion_report.sh [--pages N] [--edges M]
#            [--iters K] [--words N]
# Env:   JAX_PLATFORMS=cpu to force the host backend (default on a
#        box without an accelerator).
set -euo pipefail
cd "$(dirname "$0")/.."
exec python -m thrill_tpu.tools.fusion_report "$@"
