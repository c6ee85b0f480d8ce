#!/usr/bin/env bash
# Fast test subset: everything except the multi-second `slow` tests
# that start subprocesses (distributed, shard processes).  Full suite:
#   PYTHONPATH=src python -m pytest -x -q
set -euo pipefail
cd "$(dirname "$0")/.."
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -q -m "not slow" "$@"
