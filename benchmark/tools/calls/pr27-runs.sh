#!/bin/bash
# PR 27, every chip call (one v5e): a list of runs, each a process of its
# own, in the order given. A run is <dir>:<cell>:<seed>:<trace>[:<control>];
# <dir> is a checkout under the repo's root (`.` the working tree,
# `_archive_check` what git would commit, `_parent` the parent commit,
# `_parent_overlay` the parent under this PR's benchmark files). Runs of one
# seed in one checkout share that checkout's seed cache, so the first pays
# the new seed's set-up and the later ones the cached one's.
#   chiprun --timeout 3400 -- env CALL=<name> RUNS="<run> <run> ..." \
#     bash benchmark/tools/calls/pr27-runs.sh
# The calls made, with their RUNS, are listed in pr27-calls.md beside this
# file.
here=$(pwd)
out=$here/chiprun_out/${CALL:?}; mkdir -p $out
for r in ${RUNS:?}; do
  IFS=: read -r dir cell seed trace control <<< "$r"
  name=${dir//\//_}_${cell}_${seed}_t${trace}${control:+_$control}
  t0=$(date +%s)
  ps -eo pid= | sort > $out/.pids_before
  (cd $here/$dir && python3 benchmark/run.py --workload $cell --seed $seed \
     --seconds 45 --trace $trace ${control:+--control $control}) \
     > $out/$name.out 2> $out/$name.err
  echo "$name rc=$? wall=$(( $(date +%s) - t0 ))s"
  # what the run left running (the driver refuses a run that leaves any)
  ps -eo pid=,ppid=,stat=,args= > $out/.ps_after
  while read -r pid rest; do
    grep -qx " *$pid" $out/.pids_before || echo "LEFT RUNNING: $pid ${rest:0:160}"
  done < <(grep -v "ps -eo\|\[kworker" $out/.ps_after)
  grep -h "window: closed\|FAILED\|IN WINDOW\|node reads\|seed: \|warm-up: \|scope_share:\|fallback counters\|token slots read" \
    $out/$name.out | cut -c1-400
  tail -n 3 $out/$name.err | cut -c1-400
  tail -n 1 $out/$name.out | cut -c1-3500
done
