#!/usr/bin/env python3
"""Reads what ``execute_span_dump.py`` wrote: per group of blocks (those
that fell back whole, those that stood) the blocks, the transactions and
seconds of each lane, and the re-run share.

    python3 scripts/summarize_execute_spans.py [--window N] <out.jsonl> [...]

With ``--window N`` (the cell's commit window, 3 in the sync cells) it
also prints the means by a block's place in its window: the first block
after a seal runs beside the stage threads of the window before and
pays their GIL, the last runs almost alone.
"""

import json
import sys

LANES = ("vector", "checked", "residue", "optimistic", "sequential")
PARTS = ("plan", "post", "checkpoint", "validate")  # PR 42: outside the lanes


def by_place(rows, window: int) -> None:
    """Means by (block - first block) % window, ms a block."""
    first = rows[0]["block"]
    keys = [k for k in ("seconds", "plan_s", "post_s", "vector_s",
                        "residue_s", "commit_s", "miss_s", "misses")
            if all(k in r for r in rows)]
    for place in range(window):
        grp = [r for r in rows if (r["block"] - first) % window == place]
        print(f" place {place} of {window} ({len(grp)} blocks): " + ", ".join(
            f"{k} "
            f"{(1 if k == 'misses' else 1e3) * sum(r[k] for r in grp) / len(grp):.2f}"
            for k in keys))


def summarize(path: str, window: int = 0) -> None:
    rows = [json.loads(line) for line in open(path)]
    print(f"{path}: {len(rows)} execute spans")
    if window and rows:
        by_place(rows, window)
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
    if all(p + "_s" in r for r in rows for p in PARTS):
        n = len(rows)
        named = sum(r[k + "_s"] for r in rows for k in LANES + PARTS)
        whole = sum(r["seconds"] for r in rows)
        print(" outside the lanes, ms a block: " + ", ".join(
            f"{p} {1e3 * sum(r[p + '_s'] for r in rows) / n:.3f}"
            for p in PARTS)
            + f"; no lane or part {1e3 * (whole - named) / n:.3f} "
            f"({100.0 * (whole - named) / whole:.2f} %); world copies "
            f"{sum(r['copies'] for r in rows) / n:.1f} a block, "
            f"{1e3 * sum(r['copy_s'] for r in rows) / n:.3f} ms (inside "
            "the lanes)")
    if any("rerun_txs" in r for r in rows):
        rerun = sum(r.get("rerun_txs", 0) for r in rows)
        txs = sum(r["txs"] for r in rows)
        print(f" reruns {sum(r.get('reruns', 0) for r in rows)}, rerun_txs "
              f"{rerun} of {txs} txs = {100.0 * rerun / txs:.3f} %")


if __name__ == "__main__":
    args = sys.argv[1:]
    window = int(args[1]) if args[:1] == ["--window"] else 0
    for p in args[2:] if window else args:
        summarize(p, window)
