#!/bin/bash
# Lays PR 44's eight per-layer metrics of snap.fullstate over a checkout, as
# the `benchmark` PR that takes them in will: the eight data files into
# benchmark/metrics/ and their entries appended to BENCHMARK.json's per_layer
# (idempotent). They are not in BENCHMARK.json because the driver takes new
# entries at the end of a list only, and tests/benchmark/test_driver_metrics.py
# pins the list's last fourteen (PERF.md section 7).
#   bash scripts/pr44-metrics-overlay.sh <checkout>
here=$(cd "$(dirname "$0")" && pwd); dir=${1:?checkout}
cp $here/pr44-metrics/*.snap.json $dir/benchmark/metrics/
python3 - $dir/BENCHMARK.json $here/pr44-metrics/per_layer.json <<'PY'
import json, sys
path, new = sys.argv[1], json.load(open(sys.argv[2]))
b = json.load(open(path))
have = {m["name"] for m in b["per_layer"]}
b["per_layer"] += [m for m in new if m["name"] not in have]
json.dump(b, open(path, "w"), indent=1)
PY
