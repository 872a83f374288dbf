#!/bin/bash
# PR 23, review round, chip call r3 (one v5e), from a checkout made of what
# git would commit (as r2):
#   chiprun --timeout 1500 -- bash _archive_check/benchmark/tools/calls/pr23-r3-snap-seeds.sh
# snap.statesync's control on two more seeds (20 s windows) and `correct` on
# three more seeds (15 s windows), each group in one process (tools/seeds.py),
# then one run of sync.dense as the driver calls it.
here=$(cd "$(dirname "$0")/../../.." && pwd)
out=$(pwd)/chiprun_out/r3; mkdir -p $out
cd "$here"; echo "checkout: $here"
python3 benchmark/tools/seeds.py --workload snap.statesync --seconds 20 \
  --seeds 2810000079,2910000081 --control no-batch-check --expect-correct 0 \
  > $out/snap_controls.out 2> $out/snap_controls.err
echo "snap_controls rc=$?"; grep -h "seeds:\|window: closed\|FAILED\|\"correct\"" $out/snap_controls.out
python3 benchmark/tools/seeds.py --workload snap.statesync --seconds 15 \
  --seeds 3010000093,3110000107,3210000119 \
  > $out/snap_seeds.out 2> $out/snap_seeds.err
echo "snap_seeds rc=$?"; grep -h "seeds:\|window: closed\|FAILED\|\"correct\"" $out/snap_seeds.out
python3 benchmark/run.py --workload sync.dense --seed 2460000013 --seconds 45 --trace 0 \
  > $out/dense.out 2> $out/dense.err
echo "dense rc=$?"; grep -h "window:\|FAILED" $out/dense.out; tail -n 1 $out/dense.out
