"""The share of a phase in which its thread did not run. Every span
records thread CPU time (``time.thread_time``) beside wall time; over
the window's spans of the given names this is ``100 * sum(wall - cpu) /
sum(wall)``: waiting for the GIL and being descheduled, and for spans
that write files, the I/O too."""

from typing import Dict, List

from benchmark.readers import spans as S


def read(art: Dict, names: List[str]):
    found = [s for n in names for s in S.named(art, name=n)]
    wall = sum(s.t1 - s.t0 for s in found)
    if wall <= 0:
        return None
    cpu = sum(min(s.t1 - s.t0, max(0.0, s.tt1 - s.tt0)) for s in found)
    return 100.0 * (wall - cpu) / wall
