"""Microseconds per node inside calls the driver timed through its
proxies (``art["timers"][label] = [seconds, calls, items]``). With
``remainder_of`` set, the metric is what is left of that artefact
(seconds) after the named timers and ``also`` artefacts are taken out:
the loop's own time."""

from typing import Dict, List


def read(art: Dict, timers: List[str], per: str = "node",
         remainder_of: str = None, also: List[str] = ()):
    book = art.get("timers")
    n = art.get({"node": "nodes"}[per], 0)
    if not book or not n:
        return None
    inside = sum(book[t][0] for t in timers if t in book)
    inside += sum(float(art.get(k, 0.0)) for k in also)
    if remainder_of is not None:
        inside = float(art[remainder_of]) - inside
    return 1e6 * inside / n
