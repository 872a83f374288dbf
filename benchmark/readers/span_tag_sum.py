"""Tags summed over the window's spans of one name, as a ratio:
``scale * sum(num tags) / den``. ``den`` is a list of tags (their sum),
``"span"`` (how many such spans) or ``"block"`` / ``"window"`` (the
driver's counts). A span that lacks one of the tags (a program that
does not write it yet) leaves nothing to read, and so does a
denominator of 0."""

from typing import Dict, List, Union

from benchmark.readers import spans as S


def read(art: Dict, name: str, num: List[str], den: Union[str, List[str]],
         scale: float = 1.0):
    found = S.named(art, name=name)
    tags = list(num) + (den if isinstance(den, list) else [])
    if not found or any(t not in s.tags for s in found for t in tags):
        return None
    if den == "span":
        below = float(len(found))
    elif isinstance(den, str):
        below = S.units(art, den)
    else:
        below = sum(float(s.tags[t]) for s in found for t in den)
    if not below:
        return None
    return scale * sum(float(s.tags[t]) for s in found for t in num) / below
