#!/bin/bash
# PR 41, call p41i (one v5e; the third session): the refusal's steps 1 to 3
# in one call, because chips were scarce (the first asking of this session
# found none) and steps 1 and 2 were expected to find nothing to change:
# first pr41-h.sh's four runs on the working tree (seed A twice, B, C,
# through pr41-h.py), then six untraced runs of the cell as git would
# commit it (`_archive_check` = `git archive $(git write-tree)`, unpacked
# before the call), a seed a run, through the benchmark's own command.
# (ARCHIVE_SEEDS: fewer of the six, when the session's end is near.)
#   chiprun --timeout 1700 -- bash benchmark/tools/calls/pr41-i.sh
CALL=p41i bash benchmark/tools/calls/pr41-h.sh | cut -c1-1500 | tail -c 11000
CALL=p41i START=4 DIR=_archive_check RUNNER=benchmark/run.py \
  SEEDS="${ARCHIVE_SEEDS:-4251000319 4252000321 4253000323 4254000331 4255000337 4256000347}" \
  bash benchmark/tools/calls/pr41-h.sh | cut -c1-1300 | tail -c 11000
