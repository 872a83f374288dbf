#!/bin/bash
# PR 41, call p41g (one v5e): is the speed drawn once a process or once a
# window? Seven untraced snap.statesync windows, a seed each, in ONE process
# (tools/seeds.py: later seeds find the programs compiled), from
# `_archive_check`. The last of this PR's chip-minutes.
#   chiprun --timeout 800 -- bash benchmark/tools/calls/pr41-g.sh
here=$(pwd); out=$here/chiprun_out/p41g; mkdir -p $out
cd _archive_check
python3 benchmark/tools/seeds.py --workload snap.statesync --seconds 45 \
  --seeds 4221000283,4222000287,4223000289,4224000293,4225000297,4226000299,4227000301 \
  > $out/seeds.out 2> $out/seeds.err
echo "rc=$?"
grep -h "^seeds: \|window: closed\|^{" $out/seeds.out | cut -c1-1300 | tail -c 20000
