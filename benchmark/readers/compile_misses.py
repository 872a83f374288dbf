"""Fused-signature compiles (``compile_log`` misses) stamped inside the
window: warm-up should have met every signature, so this should be 0;
a compile that falls inside the window is counted, never hidden."""

from typing import Dict


def read(art: Dict):
    if "compile_events" not in art or "wall_window" not in art:
        return None
    lo, hi = art["wall_window"]
    return float(sum(1 for e in art["compile_events"]
                     if e["kind"] == "miss" and lo <= e["t"] <= hi))
