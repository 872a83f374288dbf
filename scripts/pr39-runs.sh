#!/bin/bash
# PR 39's chip calls: scripts/pr35-runs.sh's list of runs
# (<dir>:<cell>:<seed>:<trace>[:<control>], each a process of its own, 45 s
# windows; a traced run goes through scripts/execute_span_dump.py, which from
# this PR also prints what khipu_trie_* gained over the window), and two more
# values of <trace>: `p` runs scripts/profile_execute.py on the cell's traffic
# at 20,000 accounts (cProfile's table of execute_block), `w` the same without
# cProfile (the wall time a block and the counters). (`s`, an untraced run of
# snap.statesync through a wrapper that printed the window's CPU seconds, went
# with the wrapper in PR 42: the driver's slices say it since PR 41.)
#   chiprun --timeout 3400 -- env CALL=<name> RUNS="<run> ..." bash scripts/pr39-runs.sh
here=$(pwd)
out=$here/chiprun_out/${CALL:?}; mkdir -p $out
for r in ${RUNS:?}; do
  IFS=: read -r dir cell seed trace control <<< "$r"
  name=${dir//\//_}_${cell}_${seed}_t${trace}${control:+_$control}
  t0=$(date +%s)
  if [ "$trace" = p ] || [ "$trace" = w ]; then
    (cd $here/$dir && python3 $here/scripts/profile_execute.py --workload $cell \
       --seed $seed --blocks 12 $([ "$trace" = w ] && echo --plain)) \
       > $out/$name.out 2> $out/$name.err
    echo "$name rc=$? wall=$(( $(date +%s) - t0 ))s"
    tail -n 18 $out/$name.out | cut -c1-200
    continue
  fi
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
  grep -h "window: closed\|FAILED\|IN WINDOW\|node reads\|seed: \|warm-up: \|execute lanes\|fallback counters\|trie counters\|parent get timer\|window host" \
    $out/$name.out $out/$name.err | cut -c1-400
  [ -f $out/$name.spans.jsonl ] && python3 $here/scripts/summarize_execute_spans.py $out/$name.spans.jsonl
  tail -n 3 $out/$name.err | cut -c1-400
  tail -n 1 $out/$name.out | cut -c1-3500
done
