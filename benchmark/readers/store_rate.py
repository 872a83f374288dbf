"""Persist-stage store throughput: node bytes the window's batches landed
in the host store over the seconds the store writes took
(``WindowCommitter``'s always-on counters), in MB/s."""

from typing import Dict


def read(art: Dict):
    rows = art.get("replay_stats") or []
    seconds = sum(s.persist_store_seconds for s in rows)
    if seconds <= 0:
        return None
    return sum(s.persist_bytes for s in rows) / seconds / 1e6
