"""Span ring arithmetic shared by the span readers: the spans a driver
hands over are those that overlap the window; each has ``name``,
``t0``/``t1`` (perf_counter), ``sid``, ``parent`` and ``tags``."""

from typing import Dict, Iterable, List


def named(art: Dict, name: str = None, prefix: str = None,
          tags: Dict = None) -> List:
    out = []
    for s in art.get("spans") or []:
        if name is not None and s.name != name:
            continue
        if prefix is not None and not s.name.startswith(prefix):
            continue
        if tags and any(str(s.tags.get(k)) != str(v) for k, v in tags.items()):
            continue
        out.append(s)
    return out


def self_seconds(art: Dict, spans: Iterable) -> float:
    """Duration minus what direct children cover (children of one parent
    on one thread do not overlap, so their durations add)."""
    child_time: Dict[int, float] = {}
    for s in art.get("spans") or []:
        if s.parent is not None:
            child_time[s.parent] = child_time.get(s.parent, 0.0) + (s.t1 - s.t0)
    return sum(max(0.0, (s.t1 - s.t0) - child_time.get(s.sid, 0.0))
               for s in spans)


def units(art: Dict, per: str) -> float:
    """How many blocks or commit windows the window held."""
    return float(art.get({"block": "blocks", "window": "windows"}[per], 0))
