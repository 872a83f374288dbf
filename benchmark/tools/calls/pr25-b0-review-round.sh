#!/bin/bash
# PR 25, chip call b0 (one v5e), the review round in ONE call, because
# machines were scarce: (b1) four snap.statesync pairs parent/change at
# --trace 0, (b3) one traced run of each cell from the `git archive
# $(git write-tree)` checkout in _archive_check/ (the committed files are
# enough; scope_share's split without scope_map()'s fallback rules), (b2)
# three sync.dense pairs at --trace 0.
#   git add -A; rm -rf _archive_check _parent; mkdir _archive_check _parent
#   git archive $(git write-tree) | tar -x -C _archive_check
#   git archive 87b88a3 | tar -x -C _parent
#   chiprun --timeout 3550 -- bash benchmark/tools/calls/pr25-b0-review-round.sh
here=$(cd "$(dirname "$0")/../../.." && pwd)
pairs=$here/benchmark/tools/calls/pr25-b1-off-pairs.sh
CALL=b1 CELL=snap.statesync SEEDS="3510000229 3620000237 3730000241 3840000253" bash $pairs
out=$(pwd)/chiprun_out/b3; mkdir -p $out
for run in "sync.dense 4280000291" "snap.statesync 4390000303"; do
  set -- $run; name=${1}_${2}
  (cd $here/_archive_check && python3 benchmark/run.py --workload $1 --seed $2 --seconds 45 --trace 1) > $out/$name.out 2> $out/$name.err
  echo "traced $name rc=$?"; grep -h "window: closed\|FAILED\|scope_share:" $out/$name.out | cut -c1-1500; tail -n 1 $out/$name.out | cut -c1-3000
done
CALL=b2 CELL=sync.dense SEEDS="3950000261 4060000273 4170000287" bash $pairs
