#!/usr/bin/env python3
"""Reads what ``execute_span_dump.py`` wrote: per group of blocks (those
that fell back whole, those that stood) the blocks, the transactions and
seconds of each lane, and the re-run share.

    python3 scripts/summarize_execute_spans.py <out.jsonl> [...]
"""

import json
import sys

LANES = ("vector", "checked", "residue", "optimistic", "sequential")


def summarize(path: str) -> None:
    rows = [json.loads(line) for line in open(path)]
    print(f"{path}: {len(rows)} execute spans")
    for fb in (0, 1):
        grp = [r for r in rows if int(r.get("fallback", 0)) == fb]
        if not grp:
            continue
        n = len(grp)
        txs = sum(r["txs"] for r in grp)
        print(f" fallback={fb}: {n} blocks, {txs} txs, execute "
              f"{1e3 * sum(r['seconds'] for r in grp) / n:.2f} ms a block")
        for lane in LANES:
            k = sum(r.get(lane, 0) for r in grp)
            s = sum(r.get(lane + "_s", 0.0) for r in grp)
            if k or s:
                per = f"{1e3 * s / k:.3f} ms a tx" if k else "no tx stood"
                print(f"  {lane}: {k} txs, {s:.3f} s, "
                      f"{1e3 * s / n:.2f} ms a block, {per}")
    if any("rerun_txs" in r for r in rows):
        rerun = sum(r.get("rerun_txs", 0) for r in rows)
        txs = sum(r["txs"] for r in rows)
        print(f" reruns {sum(r.get('reruns', 0) for r in rows)}, rerun_txs "
              f"{rerun} of {txs} txs = {100.0 * rerun / txs:.3f} %")


if __name__ == "__main__":
    for p in sys.argv[1:]:
        summarize(p)
