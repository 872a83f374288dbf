"""The mean of one tag over the window's spans of one name. A span that
lacks the tag (a program that does not write it yet) leaves nothing to
read."""

from typing import Dict

from benchmark.readers import spans as S


def read(art: Dict, name: str, tag: str):
    found = S.named(art, name=name)
    if not found or any(tag not in s.tags for s in found):
        return None
    return sum(float(s.tags[tag]) for s in found) / len(found)
