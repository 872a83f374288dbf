#!/bin/bash
# PR 23, review round, chip call r2 (one v5e), run from a checkout made of
# what git would commit:
#   git add -A; rm -rf _archive_check; mkdir _archive_check
#   git archive $(git write-tree) | tar -x -C _archive_check
#   chiprun --timeout 2400 -- bash _archive_check/benchmark/tools/calls/pr23-r2-snap-sets.sh
# Two sets of four runs of snap.statesync, the same seeds in both sets, every
# run a process of its own, 45 s, --trace 0: what the bound is set from.
here=$(cd "$(dirname "$0")/../../.." && pwd)
out=$(pwd)/chiprun_out/r2; mkdir -p $out
cd "$here"; echo "checkout: $here"; ls
for set in 1 2; do
  for seed in 2410000039 2510000041 2610000053 2710000067; do
    name=snap_set${set}_${seed}
    python3 benchmark/run.py --workload snap.statesync --seed $seed --seconds 45 --trace 0 > $out/$name.out 2> $out/$name.err
    echo "$name rc=$?"; grep -h "window: closed\|FAILED" $out/$name.out; tail -n 1 $out/$name.out
  done
done
