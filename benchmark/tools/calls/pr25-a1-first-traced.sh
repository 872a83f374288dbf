#!/bin/bash
# PR 25, chip call a1 (one v5e): the change's first traced run of each cell,
# 45 s: do the new per-layer metrics report, what does the trace call the
# programs and the Mosaic instruction, how much of jit_fused_fixpoint's
# device time finds a stage.
#   chiprun --timeout 1500 -- bash benchmark/tools/calls/pr25-a1-first-traced.sh
here=$(cd "$(dirname "$0")/../../.." && pwd)
out=$(pwd)/chiprun_out/a1; mkdir -p $out
cd "$here"
run() {  # name cell seed trace
  python3 benchmark/run.py --workload $2 --seed $3 --seconds 45 --trace $4 > $out/$1.out 2> $out/$1.err
  echo "$1 rc=$?"; grep -h "window: closed\|FAILED\|scope_share:\|programs by device time" $out/$1.out; tail -n 1 $out/$1.out
}
run sync_t1_2410000121 sync.dense 2410000121 1
run snap_t1_2740000159 snap.statesync 2740000159 1
