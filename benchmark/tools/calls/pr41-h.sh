#!/bin/bash
# PR 41, call p41h (one v5e; the third session, after the driver refused
# the bound 0.10): steps 1 and 2 of the refusal's ladder in one call, on
# the working tree as refused. Seed A twice, then seeds B and C once: does
# the seed change the work (A against A, against B and C), and does the
# call's first run, which compiles, differ from the others in more than
# setup_s? Each run is a process of its own through pr41-h.py (run.py
# untouched, a host probe before and after the window, the other threads'
# CPU by name).
#   chiprun --timeout 900 -- bash benchmark/tools/calls/pr41-h.sh
here=$(pwd); out=$here/chiprun_out/${CALL:=p41h}; mkdir -p $out
echo "call $CALL nproc $(nproc) $(date -u +%FT%TZ)"
i=${START:-0}
for seed in ${SEEDS:-4241000311 4241000311 4242000313 4243000317}; do
  i=$((i + 1)); name=${i}_$seed; t0=$(date +%s)
  (cd ${DIR:-.} && python3 ${RUNNER:-benchmark/tools/calls/pr41-h.py} \
     --workload snap.statesync --seed $seed --seconds 45 --trace 0) \
     > $out/$name.out 2> $out/$name.err
  echo "$name rc=$? wall=$(( $(date +%s) - t0 ))s"
  grep -h "window: closed\|host probe\|observer: \|FAILED\|resume: " \
    $out/$name.out | cut -c1-1800
  tail -n 1 $out/$name.out | cut -c1-900
done 2>&1 | tee -a $out/summary.txt | tail -c 23000
