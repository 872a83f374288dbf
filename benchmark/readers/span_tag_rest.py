"""What the named tags leave of the window's spans of one name: ``100 *
(sum of durations - sum of the named tags) / sum of durations``. The
tags are seconds the program booked inside the span (``execute``: the
five lanes' ``<lane>_s`` and the parts outside them); the rest is what
has no name there, which should stay small (``loop_untimed_share.snap``
is its twin for the fast-sync loop). A span that lacks one of the tags
(a program that does not write it yet) leaves nothing to read, and so
does a duration of 0."""

from typing import Dict, List

from benchmark.readers import spans as S


def read(art: Dict, name: str, named: List[str]):
    found = S.named(art, name=name)
    if not found or any(t not in s.tags for s in found for t in named):
        return None
    whole = sum(s.t1 - s.t0 for s in found)
    if whole <= 0:
        return None
    told = sum(float(s.tags[t]) for s in found for t in named)
    return 100.0 * (whole - told) / whole
