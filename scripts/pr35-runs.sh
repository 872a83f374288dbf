#!/bin/bash
# PR 35's chip calls: benchmark/tools/calls/pr27-runs.sh's list of runs
# (<dir>:<cell>:<seed>:<trace>[:<control>], each a process of its own, 45 s
# windows), with a traced run's `execute` spans written to
# chiprun_out/<call>/<run>.spans.jsonl (scripts/execute_span_dump.py) and
# summed by lane (scripts/summarize_execute_spans.py).
#   chiprun --timeout 3400 -- env CALL=<name> RUNS="<run> ..." bash scripts/pr35-runs.sh
here=$(pwd)
out=$here/chiprun_out/${CALL:?}; mkdir -p $out
for r in ${RUNS:?}; do
  IFS=: read -r dir cell seed trace control <<< "$r"
  name=${dir//\//_}_${cell}_${seed}_t${trace}${control:+_$control}
  t0=$(date +%s)
  ps -eo pid= | sort > $out/.pids_before
  runner="benchmark/run.py"
  [ "$trace" = 1 ] && runner="$here/scripts/execute_span_dump.py $out/$name.spans.jsonl"
  (cd $here/$dir && python3 $runner --workload $cell --seed $seed \
     --seconds 45 --trace $trace ${control:+--control $control}) \
     > $out/$name.out 2> $out/$name.err
  echo "$name rc=$? wall=$(( $(date +%s) - t0 ))s"
  ps -eo pid=,ppid=,stat=,args= > $out/.ps_after
  while read -r pid rest; do
    grep -qx " *$pid" $out/.pids_before || echo "LEFT RUNNING: $pid ${rest:0:160}"
  done < <(grep -v "ps -eo\|\[kworker" $out/.ps_after)
  grep -h "window: closed\|FAILED\|IN WINDOW\|node reads\|seed: \|warm-up: \|execute lanes\|fallback counters" \
    $out/$name.out | cut -c1-400
  [ -f $out/$name.spans.jsonl ] && python3 $here/scripts/summarize_execute_spans.py $out/$name.spans.jsonl
  tail -n 3 $out/$name.err | cut -c1-400
  tail -n 1 $out/$name.out | cut -c1-3500
done
