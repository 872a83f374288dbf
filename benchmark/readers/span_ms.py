"""Milliseconds inside spans of one name (or name prefix), per block or
per commit window; ``self_time`` takes out what child spans cover."""

from typing import Dict

from benchmark.readers import spans as S


def read(art: Dict, per: str, name: str = None, prefix: str = None,
         self_time: bool = False):
    found = S.named(art, name=name, prefix=prefix)
    n = S.units(art, per)
    if not found or not n:
        return None
    total = (S.self_seconds(art, found) if self_time
             else sum(s.t1 - s.t0 for s in found))
    return 1000.0 * total / n
