#!/bin/bash
# PR 41, call p41e (one v5e): the tree as reviewed and repaired, as git
# would commit it (`_archive_check` = `git archive $(git write-tree)`,
# unpacked before the call), through the benchmark's own command: two sets
# of six untraced snap.statesync runs with the same six seeds in both (the
# bound's measurement), one traced run on a seventh, then the eight
# observer runs of pr41-d.sh on an eighth.
#   chiprun --timeout 3500 -- bash benchmark/tools/calls/pr41-e.sh
S="4191000197 4192000199 4193000211 4194000223 4195000227 4196000229"
RUNS=""; for s in $S $S; do RUNS="$RUNS _archive_check:snap.statesync:$s:0"; done
CALL=p41e RUNS="$RUNS _archive_check:snap.statesync:4197000233:1" \
  bash benchmark/tools/calls/pr41-runs.sh | grep "rc=\|^{\|LEFT" | cut -c1-1200 | tail -c 14000
DIR=_archive_check bash benchmark/tools/calls/pr41-d.sh | tail -c 9000
