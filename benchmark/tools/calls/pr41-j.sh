#!/bin/bash
# PR 41, call p41j (one v5e; the third session): the refusal's step 3,
# asked for when call p41i had run steps 1 and 2 alone (its archive was not
# in the copy). Six untraced runs of the cell as git would commit it
# (`_archive_check` = `git archive $(git write-tree)`, unpacked before the
# call), a seed a run, through the benchmark's own command.
#   chiprun --timeout 800 -- bash benchmark/tools/calls/pr41-j.sh
CALL=p41j DIR=_archive_check RUNNER=benchmark/run.py \
  SEEDS="${ARCHIVE_SEEDS:-4251000319 4252000321 4253000323 4254000331 4255000337 4256000347}" \
  bash benchmark/tools/calls/pr41-h.sh | cut -c1-1300 | tail -c 20000
