#!/bin/bash
# PR 41, call p41b (one v5e, working tree): what the final tree has to
# show besides its two sets: three traced snap runs (the whole 27 s trace
# back, the spans in the breakdown, how long the reduction takes), the
# three sync cells once each (the three metrics PR 41 put into the manifest
# on a traced line), the control on three seeds at the cell's new size in
# one process.
#   chiprun --timeout 3500 -- bash benchmark/tools/calls/pr41-b.sh
here=$(pwd); out=$here/chiprun_out/p41b; mkdir -p $out
CALL=p41b/traced RUNS=".:snap.statesync:4141000109:1 .:snap.statesync:4142000113:1 .:snap.statesync:4143000127:1" \
  bash benchmark/tools/calls/pr41-runs.sh | cut -c1-1800 | tail -c 5000
CALL=p41b/sync RUNS=".:sync.deep:4144000131:1 .:sync.contracts:4145000137:1 .:sync.dense:4146000139:0" \
  bash benchmark/tools/calls/pr41-runs.sh | grep -v "^\[" | cut -c1-2600 | tail -c 8000
python3 benchmark/tools/seeds.py --workload snap.statesync --seconds 45 \
  --seeds 4141000109,4142000113,4143000127 --control no-batch-check \
  --expect-correct 0 > $out/control.out 2> $out/control.err
echo "control rc=$?"
grep -h "FAILED\|seeds: \|^{" $out/control.out | cut -c1-300
# (then ten untraced snap runs, seeds 4151000141 ... 4160000163, through a
# wrapper of run.py with a host probe before and after the window; the
# wrapper is not kept, what it read is in PERF.md section 6)
