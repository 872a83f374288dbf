"""What the program's always-on counters gained over the window, as a
ratio: ``scale * sum(delta of each num family) / den``. The registry is
cumulative since boot, so the driver hands over ``registry`` = (its
snapshot at the window's opening, at its close) and this takes the
difference.

A counter is named ``{"family": ..., "labels": {label: value or list of
values}}``; samples of the family whose labels match are summed (no
``labels``: all of them). ``den`` is a list of such counters, or one of
``"block"``, ``"window"`` (the driver's counts) or ``"tx"`` (the sum of
``ReplayStats.txs`` over the window's batches). A program without the
family (the parent of the PR that added it) reads as None, and so does
a denominator of 0."""

import re
from typing import Dict, List, Optional, Union

from benchmark.readers import spans as S

_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def gained(art: Dict, family: str, labels: Dict = None) -> Optional[float]:
    """Close minus open of one family, summed over the matching samples."""
    snaps = art.get("registry")
    if not snaps or any(family not in s for s in snaps):
        return None
    totals = []
    for snap in snaps:
        value = snap[family]
        samples = value if isinstance(value, dict) else {"_": value}
        total = 0.0
        for key, v in samples.items():
            have = dict(_LABEL.findall(key))
            if all(have.get(k) in (w if isinstance(w, list) else [w])
                   for k, w in (labels or {}).items()):
                total += float(v)
        totals.append(total)
    return totals[1] - totals[0]


def _sum(art: Dict, counters: List[Dict]) -> Optional[float]:
    parts = [gained(art, c["family"], c.get("labels")) for c in counters]
    return None if None in parts else sum(parts)


def read(art: Dict, num: List[Dict], den: Union[str, List[Dict]],
         scale: float = 1.0):
    above = _sum(art, num)
    if den == "tx":
        below = float(sum(s.txs for s in art.get("replay_stats") or []))
    elif isinstance(den, str):
        below = S.units(art, den)
    else:
        below = _sum(art, den)
    if above is None or not below:
        return None
    return scale * above / below
