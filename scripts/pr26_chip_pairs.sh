#!/bin/bash
# PR 26, chip calls (one v5e): the parent commit (be140ce, `git archive`
# into _parent/) against the change, one seed a pair, every run a process
# of its own, the side that goes first alternating; TRACED seeds first, as
# one traced run of the change each (the per-layer line).
#   rm -rf _parent; mkdir _parent; git archive be140ce | tar -x -C _parent
#   chiprun --timeout 3000 -- env CALL=c1 CELL=snap.statesync \
#     TRACED="4280000291" SEEDS="4390000307 4400000311 4510000323" \
#     bash scripts/pr26_chip_pairs.sh
#   chiprun --timeout 2400 -- env CALL=c2 CELL=sync.dense \
#     SEEDS="4620000337 4730000349" bash scripts/pr26_chip_pairs.sh
# CHANGE_DIR=_archive_check runs the change from what git would commit;
# SOLO seeds run the change alone at --trace 0 (its own spread). Call c3:
#   git add -A; rm -rf _archive_check; mkdir _archive_check
#   git archive $(git write-tree) | tar -x -C _archive_check
#   chiprun --timeout 3400 -- bash -c 'export CHANGE_DIR=_archive_check; \
#     CALL=c3 CELL=snap.statesync TRACED=4840000351 \
#     SEEDS="4950000367 5060000371 5170000383" \
#     SOLO="5280000397 5390000401 5500000413" bash scripts/pr26_chip_pairs.sh; \
#     CALL=c3 CELL=sync.dense TRACED=5610000427 bash scripts/pr26_chip_pairs.sh'
here=$(pwd)
out=$here/chiprun_out/${CALL:?}; mkdir -p $out
cell=${CELL:?}
run() {  # side seed trace
  dir=$here/${CHANGE_DIR:-.}; [ $1 = parent ] && dir=$here/_parent
  name=${1}_t${3}_${2}
  (cd $dir && python3 benchmark/run.py --workload $cell --seed $2 --seconds 45 --trace $3) > $out/$name.out 2> $out/$name.err
  echo "$name rc=$?"; grep -h "window: closed\|FAILED\|scope_share:" $out/$name.out | cut -c1-400; tail -n 1 $out/$name.out | cut -c1-3000
}
for seed in $TRACED; do run change $seed 1; done
for seed in $SOLO; do run change $seed 0; done
first=parent
for seed in $SEEDS; do
  if [ $first = parent ]; then run parent $seed 0; run change $seed 0; first=change
  else run change $seed 0; run parent $seed 0; first=parent; fi
done
