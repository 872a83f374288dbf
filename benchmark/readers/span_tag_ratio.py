"""A ratio of span tags summed over the window's spans of one name:
``sum(product of the num tags) / sum(den tag)``. A span that lacks one
of the tags (a program that does not write it yet) leaves nothing to
read."""

from math import prod
from typing import Dict, List

from benchmark.readers import spans as S


def read(art: Dict, name: str, num: List[str], den: str):
    found = S.named(art, name=name)
    if not found or any(t not in s.tags for s in found for t in (*num, den)):
        return None
    below = sum(float(s.tags[den]) for s in found)
    if below <= 0:
        return None
    return sum(prod(float(s.tags[t]) for t in num) for s in found) / below
