#!/bin/bash
# PR 25, chip call a4 (one v5e): the committed files are enough, and the
# benchmark files this PR adds run over a program that lacks what it adds.
#   git add -A; rm -rf _archive_check _parent_overlay; mkdir _archive_check _parent_overlay
#   git archive $(git write-tree) | tar -x -C _archive_check
#   git archive 87b88a3 | tar -x -C _parent_overlay
#   cp _archive_check/BENCHMARK.json _parent_overlay/
#   cp -r _archive_check/benchmark/. _parent_overlay/benchmark/
#   cp -r _archive_check/tests/benchmark/. _parent_overlay/tests/benchmark/
#   chiprun --timeout 1800 -- bash _archive_check/benchmark/tools/calls/pr25-a4-committed-files.sh
# One traced 45 s run of each cell from each of the two checkouts. Made twice:
# a4, and a5 on the final tree (CALL=a5 SYNC_SEED=3290000201 SNAP_SEED=3400000213).
root=$(pwd)
out=$root/chiprun_out/${CALL:-a4}; mkdir -p $out
run() {  # checkout cell seed
  name=${1}_${2}_${3}
  (cd $root/$1 && python3 benchmark/run.py --workload $2 --seed $3 --seconds 45 --trace 1) > $out/$name.out 2> $out/$name.err
  echo "$name rc=$?"; grep -h "window: closed\|FAILED\|scope_share:" $out/$name.out | cut -c1-600; tail -n 1 $out/$name.out | cut -c1-2500
}
sync=${SYNC_SEED:-3070000183}; snap=${SNAP_SEED:-3180000197}
run _archive_check sync.dense $sync
run _archive_check snap.statesync $snap
run _parent_overlay sync.dense $sync
run _parent_overlay snap.statesync $snap
