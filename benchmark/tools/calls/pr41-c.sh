#!/bin/bash
# PR 41, call p41c (one v5e): the final tree as git would commit it
# (`_archive_check` = `git archive $(git write-tree)`, unpacked before the
# call), through the benchmark's own command: two sets of six untraced
# snap.statesync runs with the same six seeds in both (the bound's
# measurement), then one traced run on a seventh seed.
#   chiprun --timeout 3000 -- bash benchmark/tools/calls/pr41-c.sh
S="4171000169 4172000171 4173000173 4174000177 4175000179 4176000181"
RUNS=""; for s in $S $S; do RUNS="$RUNS _archive_check:snap.statesync:$s:0"; done
CALL=p41c RUNS="$RUNS _archive_check:snap.statesync:4177000183:1" \
  bash benchmark/tools/calls/pr41-runs.sh | grep "rc=\|^{\|LEFT" | cut -c1-1500 | tail -c 20000
