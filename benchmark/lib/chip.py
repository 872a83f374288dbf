"""The device as JAX reports it, and the one place the harness decides
whether it may measure."""

from __future__ import annotations

import os
import sys
from typing import Dict


def stamp() -> Dict:
    import jax

    devices = jax.devices()
    return {
        "platform": str(devices[0].platform),
        "kind": str(devices[0].device_kind),
        "count": len(devices),
    }


def require(chips: int, rehearse: bool) -> Dict:
    """The device stamp, or exit 3 with no result line: a measurement
    needs a TPU with at least ``chips`` devices. ``--rehearse`` runs
    tiny sizes on whatever JAX finds and prints that platform."""
    dev = stamp()
    print(f"bench: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} rehearse={int(rehearse)}", flush=True)
    if rehearse:
        return dev
    if dev["platform"] != "tpu" or dev["count"] < chips:
        print(f"bench: need {chips} TPU chip(s), found {dev['count']} "
              f"{dev['platform']} device(s); nothing measured "
              "(--rehearse runs tiny sizes on the CPU and is never a "
              "measurement)", file=sys.stderr)
        sys.exit(3)
    return dev


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip; 0 where the backend does
    not report it (the CPU rehearsal)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def place_compile_cache() -> str:
    """The program's own placement: ``$JAX_COMPILATION_CACHE_DIR`` if
    set, else ``<checkout>/.jax_cache`` — a fixed path inside the
    checkout, so only a checkout's first run of a cell compiles."""
    from khipu_tpu import device

    path = device.place_compile_cache()
    os.makedirs(path, exist_ok=True)
    return path
