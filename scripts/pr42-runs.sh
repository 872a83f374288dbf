#!/bin/bash
# PR 42's chip calls: a list of runs (<dir>:<cell>:<seed>:<trace>), each a
# process of its own at the benchmark's 45 s, from the checkout in <dir> (`.`
# the tree under test, `_parent` the parent's `git archive`, `_parent_overlay`
# the parent with this PR's BENCHMARK.json and benchmark/ laid over it, as the
# driver runs the parent's traced side). A traced run of a sync cell goes
# through scripts/execute_span_dump.py (the execute spans by lane and part,
# and the read path's counters over the window).
#   chiprun --timeout 3400 -- env CALL=<name> RUNS="<run> ..." bash scripts/pr42-runs.sh
here=$(pwd)
out=$here/chiprun_out/${CALL:?}; mkdir -p $out
for r in ${RUNS:?}; do
  IFS=: read -r dir cell seed trace <<< "$r"
  name=${dir//[.\/]/_}_${cell}_${seed}_t${trace}
  t0=$(date +%s)
  ps -eo pid= | sort > $out/.pids_before
  runner="benchmark/run.py"
  [ "$trace" = 1 ] && [ "${cell%%.*}" = sync ] && \
    runner="$here/scripts/execute_span_dump.py $out/$name.spans.jsonl"
  (cd $here/$dir && python3 $runner --workload $cell --seed $seed \
     --seconds 45 --trace $trace) > $out/$name.out 2> $out/$name.err
  echo "$name rc=$? wall=$(( $(date +%s) - t0 ))s"
  ps -eo pid=,ppid=,stat=,args= > $out/.ps_after
  while read -r pid rest; do
    grep -qx " *$pid" $out/.pids_before || echo "LEFT RUNNING: $pid ${rest:0:160}"
  done < <(grep -v "ps -eo\|\[kworker" $out/.ps_after)
  grep -h "window: closed\|FAILED\|IN WINDOW\|node reads\|execute lanes\|fallback counters\|trie counters" \
    $out/$name.out $out/$name.err | cut -c1-2500
  [ -f $out/$name.spans.jsonl ] && python3 $here/scripts/summarize_execute_spans.py --window 3 $out/$name.spans.jsonl
  tail -n 2 $out/$name.err | cut -c1-300
  tail -n 1 $out/$name.out | cut -c1-6000
done
