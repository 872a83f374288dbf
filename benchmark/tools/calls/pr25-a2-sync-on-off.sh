#!/bin/bash
# PR 25, chip call a2 (one v5e): what the instrumentation costs in sync.dense.
# The parent commit (87b88a3, unpacked by `git archive` into _parent/) and the
# change at --trace 0, and the change at --trace 1, three seeds, 45 s, every
# run a process of its own, parent and change alternating which goes first.
#   chiprun --timeout 3300 -- bash benchmark/tools/calls/pr25-a2-sync-on-off.sh
here=$(cd "$(dirname "$0")/../../.." && pwd)
out=$(pwd)/chiprun_out/${CALL:-a2}; mkdir -p $out
cell=${CELL:-sync.dense}
run() {  # side trace seed
  dir=$here; [ $1 = parent ] && dir=$here/_parent
  name=${1}_t${2}_${3}
  (cd $dir && python3 benchmark/run.py --workload $cell --seed $3 --seconds 45 --trace $2) > $out/$name.out 2> $out/$name.err
  echo "$name rc=$?"; grep -h "window: closed\|FAILED\|scope_share:" $out/$name.out; tail -n 1 $out/$name.out | cut -c1-1800
}
set -- ${SEEDS:-2410000121 2520000133 2630000147}
run parent 0 $1; run change 0 $1; run change 1 $1
run change 0 $2; run parent 0 $2; run change 1 $2
run parent 0 $3; run change 0 $3; run change 1 $3
