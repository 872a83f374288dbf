#!/bin/bash
# Lays PR 43's two per-layer metrics over a checkout, as the `benchmark` PR
# that takes them in will: the two data files into benchmark/metrics/ and
# their entries appended to BENCHMARK.json's per_layer (idempotent).
#   bash scripts/pr43-overlay.sh <checkout>
here=$(cd "$(dirname "$0")" && pwd); dir=${1:?checkout}
cp $here/pr43-metrics/*.sync.json $dir/benchmark/metrics/
python3 - $dir/BENCHMARK.json $here/pr43-metrics/per_layer.json <<'PY'
import json, sys
path, new = sys.argv[1], json.load(open(sys.argv[2]))
b = json.load(open(path))
have = {m["name"] for m in b["per_layer"]}
b["per_layer"] += [m for m in new if m["name"] not in have]
json.dump(b, open(path, "w"), indent=1)
PY
