#!/bin/bash
# PR 25, chip call a3 (one v5e): as pr25-a2-sync-on-off.sh, for snap.statesync.
#   chiprun --timeout 2400 -- bash benchmark/tools/calls/pr25-a3-snap-on-off.sh
CALL=a3 CELL=snap.statesync SEEDS="2740000159 2850000163 2960000171" \
  exec bash "$(dirname "$0")/pr25-a2-sync-on-off.sh"
