#!/bin/bash
# PR 25, chip calls b1 and b2 (one v5e), the review round: does the change
# cost anything with tracing OFF? More pairs of the parent commit (87b88a3,
# `git archive` into _parent/) against the change at --trace 0, one seed a
# pair, every run a process of its own, the side that goes first alternating.
#   rm -rf _parent; mkdir _parent; git archive 87b88a3 | tar -x -C _parent
#   chiprun --timeout 2400 -- env CALL=b1 CELL=snap.statesync \
#     SEEDS="3510000229 3620000237 3730000241 3840000253" \
#     bash benchmark/tools/calls/pr25-b1-off-pairs.sh
#   chiprun --timeout 2400 -- env CALL=b2 CELL=sync.dense \
#     SEEDS="3950000261 4060000273 4170000287" \
#     bash benchmark/tools/calls/pr25-b1-off-pairs.sh
here=$(cd "$(dirname "$0")/../../.." && pwd)
out=$(pwd)/chiprun_out/${CALL:?}; mkdir -p $out
cell=${CELL:?}
run() {  # side seed
  dir=$here; [ $1 = parent ] && dir=$here/_parent
  name=${1}_t0_${2}
  (cd $dir && python3 benchmark/run.py --workload $cell --seed $2 --seconds 45 --trace 0) > $out/$name.out 2> $out/$name.err
  echo "$name rc=$?"; grep -h "FAILED" $out/$name.out; tail -n 1 $out/$name.out | cut -c1-1200
}
first=parent
for seed in ${SEEDS:?}; do
  if [ $first = parent ]; then run parent $seed; run change $seed; first=change
  else run change $seed; run parent $seed; first=parent; fi
done
