"""A number the driver measured itself and handed over by name."""

from typing import Dict


def read(art: Dict, key: str):
    v = art.get(key)
    return None if v is None else float(v)
