"""Numbers from the device trace: ``100 * num / den`` (or 100 minus it)
of two quantities of the reduction — ``busy_s``, ``window_s``,
``mosaic_s``. The reduction itself is benchmark/reduce/xplane.py and is
done once per run, however many metrics read it."""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.reduce import xplane


def reduced(art: Dict) -> Optional[Dict]:
    if "trace_reduced" in art:
        return art["trace_reduced"]
    tw = art.get("trace")
    path = tw.xplane_path() if tw is not None else None
    out = None
    if path is not None:
        profile = xplane.load(path)
        offset = xplane.clock_offset_ns(profile)
        spans = []
        if offset is not None:
            spans = [(s.name, s.t0 * 1e9 - offset, s.t1 * 1e9 - offset)
                     for s in art.get("spans", [])]
        out = xplane.reduce(profile, host_spans=spans)
        if out["busy_s"] <= 0:
            out = None
    art["trace_reduced"] = out
    return out


def read(art: Dict, num: str, den: str, complement: bool = False):
    r = reduced(art)
    if r is None or r[den] <= 0:
        return None
    share = 100.0 * r[num] / r[den]
    return 100.0 - share if complement else share
