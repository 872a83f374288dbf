#!/usr/bin/env python3
"""One benchmark run with the window's ``execute`` spans written out.

    python3 <repo>/scripts/execute_span_dump.py <out.jsonl> --workload <cell> \
        --seed <n> --seconds <s> --trace 1

Runs ``benchmark/run.py`` of the checkout it is started in (the working
tree, or a copy of another commit) with the arguments after the first,
and writes one JSON line per ``execute`` span of the window to
``out.jsonl``: the span's seconds and its tags (the lanes' transactions
and seconds, ``batches``, ``fallback``, and from PR 35 ``reruns`` /
``rerun_txs``; from PR 42 the parts outside the lanes, and beside them
the block's ``commit`` span (``commit_s``, its three parts, ``accounts``,
``slots``) and its ``window.build`` span (``build_s`` and the driver
thread's own ``misses`` / ``miss_s`` / ``miss_wait_s`` /
``miss_engine_s``)). What no per-layer metric reads yet is read from this
dump: the residue lane's seconds a transaction on blocks that stood,
``optimistic_s`` a block on blocks that fell back, sum(rerun_txs) /
sum(txs). From PR 39 it also prints what ``khipu_trie_*`` gained over
the window, label by label (``walk="python"`` above 0: the extension was
not bound for some look-up), where the driver hands over its registry
snapshots (``sync.deep``, ``sync.contracts``), and the process's totals
since boot where it does not (``sync.dense``); from PR 42 the same line
carries the store's read-path counters that no metric reads
(``khipu_kesque_get_*``, ``khipu_kesque_append_lock_held_seconds_total``,
``khipu_nodestore_lock_wait_seconds_total``: every thread's, by topic
or store). Edits nothing under
``benchmark/``: it wraps ``run.per_layer``, which is handed the driver's
artefacts.
"""

import json
import os
import sys


COUNTERS = ("khipu_trie_", "khipu_kesque_get_", "khipu_kesque_append_lock",
            "khipu_nodestore_lock_wait")


def trie_counters(artefacts) -> str:
    """``COUNTERS``' families over the window (or since boot), one line."""
    snaps = artefacts.get("registry")
    if snaps:
        what = "over the window"
    else:
        from khipu_tpu.observability.registry import REGISTRY

        what, snaps = "since boot", ({}, REGISTRY.snapshot())
    out = []
    for family, close in sorted(snaps[1].items()):
        if not family.startswith(COUNTERS):
            continue
        was = snaps[0].get(family, 0)
        if isinstance(close, dict):
            out += [f"{family}{{{k}}} {v - (was or {}).get(k, 0):.6g}"
                    for k, v in sorted(close.items())
                    if family.startswith("khipu_trie_")
                    or v != (was or {}).get(k, 0)]  # eleven topics: moved only
        else:
            out.append(f"{family} {close - was:.6g}")
    return f"trie counters {what}: " + (", ".join(out) or "none")


def main() -> int:
    out_path = os.path.abspath(sys.argv[1])
    sys.path.insert(0, os.getcwd())
    from benchmark import run

    inner = run.per_layer

    def per_layer(cell_name, outcome):
        ring = outcome.artefacts.get("spans") or []
        spans = [s for s in ring if s.name == "execute"]
        # the block's commit span and its window.build span's own tags
        # (PR 42: commit by part, the driver thread's misses) ride on
        # the block's row, the spans' seconds as commit_s and build_s
        beside = {}
        for s in ring:
            if s.name in ("commit", "window.build") and "block" in s.tags:
                row = beside.setdefault(s.tags["block"], {})
                row[s.name.split(".")[-1] + "_s"] = s.t1 - s.t0
                row.update({k: v for k, v in s.tags.items()
                            if k not in ("block", "txs")})
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            for s in spans:
                f.write(json.dumps({
                    "seconds": s.t1 - s.t0, **s.tags,
                    **beside.get(s.tags.get("block"), {})}) + "\n")
        print(f"execute spans: {len(spans)} written to {out_path}",
              flush=True)
        print(trie_counters(outcome.artefacts), flush=True)
        return inner(cell_name, outcome)

    run.per_layer = per_layer
    return run.main(sys.argv[2:])


if __name__ == "__main__":
    sys.exit(main())
