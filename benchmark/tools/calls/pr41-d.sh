#!/bin/bash
# PR 41, the observer runs of call p41e (one v5e; DIR: the checkout, default
# the working tree): what each observer of a traced
# snap.statesync window costs (REVIEW of PR 41): one seed, eight runs, the
# four modes of pr41-d.py there and back (both prof spans none none spans
# prof both), so that the machine's drift falls on all alike.
#   chiprun --timeout 1500 -- env DIR=_archive_check bash benchmark/tools/calls/pr41-d.sh
here=$(pwd); out=$here/chiprun_out/p41d; mkdir -p $out
cd $here/${DIR:-.}
seed=${SEED:-4181000191}; n=0
for mode in both prof spans none none spans prof both; do
  n=$((n + 1)); t0=$(date +%s)
  MODE=$mode python3 benchmark/tools/calls/pr41-d.py --workload snap.statesync \
    --seed $seed --seconds 45 > $out/$n-$mode.out 2> $out/$n-$mode.err
  echo "$n $mode rc=$? wall=$(( $(date +%s) - t0 ))s"
  grep -h "window: closed\|observer: \|span ring\|FAILED" $out/$n-$mode.out | cut -c1-1600
  tail -n 1 $out/$n-$mode.out | cut -c1-1200
done 2>&1 | tee $out/summary.txt | tail -c 23000
