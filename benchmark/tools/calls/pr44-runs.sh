#!/bin/bash
# PR 44, the chip calls' runner (one v5e), after pr41-runs.sh: a list of
# runs, each a process of its own, in the order given. A run is
# <dir>:<cell>:<seed>:<trace>[:<control>]; <dir> is a checkout under the
# repo's root (`.` the working tree, `_archive_check` what git would commit,
# `_parent_overlay` the parent commit under this PR's benchmark files). Runs
# of one seed in one checkout share that checkout's seed cache.
#   chiprun --timeout 3400 -- env CALL=<name> RUNS="<run> <run> ..." \
#     bash benchmark/tools/calls/pr44-runs.sh
# The calls made, with their RUNS, are listed in pr44-calls.md beside this
# file. Everything a run printed is in chiprun_out/<call>/<run>.out|.err;
# what is echoed here is also in chiprun_out/<call>/summary.txt.
here=$(pwd)
out=$here/chiprun_out/${CALL:?}; mkdir -p $out
{
echo "call $CALL seconds ${SECONDS_ARG:-45} nproc $(nproc) $(date -u +%FT%TZ)"
free -g | head -2; df -h $here | tail -1
for r in ${RUNS:?}; do
  IFS=: read -r dir cell seed trace control <<< "$r"
  d=${dir//\//_}; [ "$d" = "." ] && d=tree  # ("." gave hidden files in f44b and f44c)
  name=${d}_${cell}_${seed}_t${trace}${control:+_$control}
  t0=$(date +%s)
  ps -eo pid= | sort > $out/.pids_before
  (cd $here/$dir && python3 benchmark/run.py --workload $cell --seed $seed \
     --seconds ${SECONDS_ARG:-45} --trace $trace ${control:+--control $control}) \
     > $out/$name.out 2> $out/$name.err
  echo "$name rc=$? wall=$(( $(date +%s) - t0 ))s"
  # what the run left running (the driver refuses a run that leaves any)
  ps -eo pid=,ppid=,stat=,args= > $out/.ps_after
  while read -r pid rest; do
    grep -qx " *$pid" $out/.pids_before || echo "LEFT RUNNING: $pid ${rest:0:160}"
  done < <(grep -v "ps -eo\|\[kworker" $out/.ps_after)
  grep -h "window: \|span ring\|FAILED\|IN WINDOW\|resume: \|trace: \|peers: \|seed: \|checks: \|control: \|node: " \
    $out/$name.out | cut -c1-1200
  grep -v "cpu_aot_loader" $out/$name.err | tail -n 3 | cut -c1-300
  tail -n 1 $out/$name.out | cut -c1-3000
done
} 2>&1 | tee $out/summary.txt | tail -c 23000
