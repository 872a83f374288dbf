"""How many spans of one name carry the given tags, as a percentage of
the window's blocks or commit windows."""

from typing import Dict

from benchmark.readers import spans as S


def read(art: Dict, name: str, tags: Dict, per: str):
    n = S.units(art, per)
    if not art.get("spans") or not n:
        return None
    return 100.0 * len(S.named(art, name=name, tags=tags)) / n
