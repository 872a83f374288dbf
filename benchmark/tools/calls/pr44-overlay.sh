#!/bin/bash
# PR 44: what the driver does to the parent's checkout before it tries a new
# cell there: the parent commit's files under <dir>, with this tree's
# BENCHMARK.json and the directories of its `paths` laid over them (caches
# left out). Run from the repo's root:
#   bash benchmark/tools/calls/pr44-overlay.sh _parent_overlay 7da3d78
dir=${1:?}; commit=${2:?}
rm -rf "$dir"; mkdir "$dir"
git archive "$commit" | tar -x -C "$dir"
cp BENCHMARK.json "$dir"/
tar -c --exclude=cache/'*' --exclude=__pycache__ benchmark tests/benchmark | tar -x -C "$dir"
