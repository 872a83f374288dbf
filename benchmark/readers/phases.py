"""``ReplayStats.phases`` summed over the window's batches, as
milliseconds per block or per commit window. Foreground phases tile the
driver thread's wall clock; ``*_bg`` phases are the collector stages'
busy time and overlap it."""

from typing import Dict, List

from benchmark.readers.spans import units


def read(art: Dict, phases: List[str], per: str):
    rows = art.get("replay_stats") or []
    n = units(art, per)
    if not rows or not n:
        return None
    return 1000.0 * sum(
        s.phases.get(p, 0.0) for s in rows for p in phases) / n
