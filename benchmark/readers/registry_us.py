"""Microseconds per node stored inside one phase of the fast-sync loop,
from the program's own always-on counters
(``khipu_fastsync_phase_seconds_total{phase=}``, served by the newest
``StateSyncer`` through the process registry; the driver cannot reach
the syncer object). The window's syncer is built after the warm-up's,
so the registry slot is the window's when this runs; to be sure, nothing
is read unless ``khipu_fastsync_nodes_total{kind="state"}`` equals the
driver's own count of nodes stored. A program without these counters
(the parent of the PR that added them) reads as None."""

from typing import Dict, Optional


def fastsync(art: Dict) -> Optional[Dict[str, Dict[str, float]]]:
    """{family: {its one label's value, or "": number}} of the
    ``khipu_fastsync_*`` families, or None (see module docstring)."""
    from khipu_tpu.observability.registry import REGISTRY

    fams = {
        name: {next(iter(labels.values()), ""): float(v)
               for labels, v in samples}
        for name, (_kind, _help, samples) in REGISTRY.families().items()
        if name.startswith("khipu_fastsync_")}
    stored = fams.get("khipu_fastsync_nodes_total", {}).get("state")
    if not art.get("nodes") or stored != art["nodes"]:
        return None
    return fams


def read(art: Dict, phase: str):
    fams = fastsync(art)
    if fams is None:
        return None
    seconds = fams.get("khipu_fastsync_phase_seconds_total", {}).get(phase)
    return None if seconds is None else 1e6 * seconds / art["nodes"]
