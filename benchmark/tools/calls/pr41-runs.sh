#!/bin/bash
# PR 41, the chip calls' runner (one v5e): a list of runs, each a process of
# its own, in the order given. A run is <dir>:<cell>:<seed>:<trace>[:<control>];
# <dir> is a checkout under the repo's root (`.` the working tree,
# `_archive_check` what git would commit). Runs of one seed in one checkout
# share that checkout's seed cache. (Calls p41a and p41b's probe went through
# a wrapper of run.py that is not kept: pr41-calls.md.)
#   chiprun --timeout 3400 -- env CALL=<name> RUNS="<run> <run> ..." \
#     bash benchmark/tools/calls/pr41-runs.sh
# The calls made, with their RUNS, are listed in pr41-calls.md beside this
# file. Everything a run printed is in chiprun_out/<call>/<run>.out|.err;
# what is echoed here is also in chiprun_out/<call>/summary.txt.
here=$(pwd)
out=$here/chiprun_out/${CALL:?}; mkdir -p $out
{
echo "call $CALL seconds ${SECONDS_ARG:-45} nproc $(nproc) $(date -u +%FT%TZ)"
for r in ${RUNS:?}; do
  IFS=: read -r dir cell seed trace control <<< "$r"
  name=${dir//\//_}_${cell}_${seed}_t${trace}${control:+_$control}
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
  grep -h "window: closed\|span ring\|FAILED\|IN WINDOW\|resume: \|trace: " \
    $out/$name.out | cut -c1-2400
  tail -n 2 $out/$name.err | cut -c1-300
  tail -n 1 $out/$name.out | cut -c1-3000
done
} 2>&1 | tee $out/summary.txt | tail -c 23000
