"""A memory-bound kernel's share of the HBM roofline: the bytes it has to
read (handed over by the driver from the classes' shapes, see
``drivers/statesync.resident_bytes``) over the device time of the traced
programs whose name matches, over the peak of this ``device_kind`` in
benchmark/peaks.json. An unlisted device is an error."""

import json
import os
from typing import Dict

from benchmark.readers import trace_share

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peak(kind: str, key: str) -> float:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return float(table[kind][key]["value"])


def read(art: Dict, bytes_key: str, program: str):
    r = trace_share.reduced(art)
    nbytes = art.get(bytes_key)
    if r is None or not nbytes:
        return None
    seconds = sum(s for name, s in r["programs_all"] if program in name)
    if seconds <= 0:
        return None
    kind = art.get("device_kind")
    return 100.0 * (nbytes / seconds) / peak(kind, "hbm_bytes_per_s")
