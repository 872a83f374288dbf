#!/bin/bash
# PR 41, call p41f (FOUR chips: the whole host, 30 cores, no neighbour; the
# cell still asks for one and runs on the first): is the slow state a
# neighbour's? Twelve untraced snap.statesync runs, a seed a run, from
# `_archive_check`, through the benchmark's own command. For PERF.md
# section 7's next step; the cell's `chips` stays 1 in this PR.
#   chiprun --chips 4 --timeout 1700 -- bash benchmark/tools/calls/pr41-f.sh
RUNS=""; for s in 4201000247 4202000249 4203000251 4204000253 4205000261 4206000263 \
  4207000267 4208000269 4209000271 4210000273 4211000277 4212000281; do
  RUNS="$RUNS _archive_check:snap.statesync:$s:0"; done
CALL=p41f RUNS="$RUNS" bash benchmark/tools/calls/pr41-runs.sh \
  | grep "rc=\|^{\|LEFT\|^call" | cut -c1-900 | tail -c 20000
