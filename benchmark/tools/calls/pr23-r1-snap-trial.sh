#!/bin/bash
# PR 23, review round, chip call r1 (one v5e):
#   chiprun --timeout 1200 -- bash benchmark/tools/calls/pr23-r1-snap-trial.sh
# First chip runs of snap.statesync as a resumed sync: a traced run at the
# cell's length, the same seed over 30 s (does the rate depend on the
# window's length?), and the control on another seed.
out=chiprun_out/r1; mkdir -p $out
run() { name=$1; shift
  python3 benchmark/run.py "$@" > $out/$name.out 2> $out/$name.err
  echo "$name rc=$? : $*"; grep -h "window:\|source:\|resume:\|FAILED" $out/$name.out; tail -n 1 $out/$name.out; }
run snap_trace  --workload snap.statesync --seed 2210000017 --seconds 45 --trace 1
run snap_30s    --workload snap.statesync --seed 2210000017 --seconds 30 --trace 0
run snap_control --workload snap.statesync --seed 2310000027 --seconds 20 --trace 0 --control no-batch-check
