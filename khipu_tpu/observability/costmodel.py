"""Per-window roofline cost model: attainable vs achieved per seal
sub-phase.

A round-6 CPU capture billed 34.9 s/window to one opaque ``seal`` span. The
sub-phase instrumentation (seal.pack / seal.alias_gather /
seal.dispatch_build / seal.upload / seal.rootcheck / seal.journal)
splits that wall into named steps; this module answers the NEXT
question — "is each step as fast as the hardware allows, and if not,
what is it bound by?" — by joining three measurements per window:

* TransferLedger bytes + crossing counts per sub-phase site
  (observability/profiler.py ``window_report``),
* span wall seconds per sub-phase (trace.py ring snapshot),
* node/hash counts carried as span tags (``seal.pack`` tags the
  window's node count).

against this device's floors (``DEVICE_FLOORS``, keyed by
``jax.devices()[0].device_kind``; method in docs/roofline.md):

* ``bytes_s``    = device_bytes / sustained host->device bytes/s — the
  floor for any step that must move bytes across the host<->device
  boundary.
* ``dispatch_s`` = d2h_crossings x the round trip of one materialised
  fetch — the fixed floor per blocking device->host fetch. Async h2d
  enqueues do not pay it.
* ``compute_s``  = hashes / the kernel-only Keccak rate on resident
  576 B tiles.

``attainable_s`` is the max applicable floor (rooflines compose as
max, not sum: transfers overlap compute on this pipeline). The
verdict per sub-phase:

* the argmax floor's name (``bytes-bound`` / ``dispatch-bound`` /
  ``compute-bound``) when achieved is within ``FIXED_OVERHEAD_FACTOR``
  of attainable — the step is pushing a real hardware limit;
* ``fixed-overhead`` when achieved exceeds every floor by more than
  that factor (or no floor applies at all) — the time is going to
  host-side work / framework overhead, i.e. the step is OPTIMIZABLE
  without faster hardware;
* ``not calibrated`` when the device has no row in ``DEVICE_FLOORS``.
  A device is never classified against another device's numbers.

Surfaces: the ``khipu_window_costs(n)`` RPC (jsonrpc/eth_service.py)
and a chrome-trace counter track (export.counter_tracks appends
``cost_tracks``) so perfetto shows attainable-vs-achieved per window
next to the span timeline. Everything here is read-only over
snapshots — safe to call from the metrics thread while a replay runs.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence

from khipu_tpu.observability.profiler import D2H, H2D, HOST, LEDGER
from khipu_tpu.observability.recorder import SEAL_SUBPHASES
from khipu_tpu.observability.trace import Span, Tracer, tracer


class DeviceFloors(NamedTuple):
    """One device's three measured floors."""

    fetch_round_trip_s: float   # one materialised device->host fetch
    h2d_bytes_per_s: float      # sustained upload at window-upload size
    kernel_hashes_per_s: float  # kernel-only Keccak, resident 576 B tiles


# Measured floors per ``device_kind``. Every number is a chip run by
# the PR that wrote the row; the command is beside it. No row, no
# verdict: an unlisted device reads "not calibrated".
DEVICE_FLOORS: Dict[str, DeviceFloors] = {
    # One attached v5e chip, 13-core host (my chip run, PR 21):
    #   chiprun -- python scripts/calibrate_floors.py --upload-bytes 8388608
    # printed fetch_round_trip_s 0.00118997 (median of 200 gather +
    # device_get of 4 x 32 B), h2d_bytes_per_s 4501207586 (median of 50
    # device_put of 8 MiB — the smoke's windows upload 6.7-7.9 MB; at
    # 4 MiB the same command read 3398414107), kernel_hashes_per_s
    # 96530888 (1,048,576 resident 576 B rows, 40- vs 8-round programs,
    # delta 0.3476 s). A second run of the command an hour apart read
    # 0.00118138 / - / 96493804.
    "TPU v5 lite": DeviceFloors(
        fetch_round_trip_s=1.19e-3,
        h2d_bytes_per_s=4.50e9,
        kernel_hashes_per_s=96.5e6,
    ),
}

# achieved more than this multiple over EVERY applicable floor means
# the time is host/framework overhead, not a hardware bound
FIXED_OVERHEAD_FACTOR = 3.0

NOT_CALIBRATED = "not calibrated"

_BOUND_NAMES = {
    "bytes_s": "bytes-bound",
    "dispatch_s": "dispatch-bound",
    "compute_s": "compute-bound",
}


@functools.lru_cache(maxsize=1)
def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def device_floors(device_kind: Optional[str] = None
                  ) -> Optional[DeviceFloors]:
    """The floors row for ``device_kind`` (default: the device this
    process runs on), or None when it has not been calibrated."""
    return DEVICE_FLOORS.get(
        device_kind if device_kind is not None else _device_kind()
    )


def subphase_floors(floors: DeviceFloors, device_bytes: int,
                    d2h_crossings: int, hashes: int) -> Dict[str, float]:
    """The applicable roofline floors for one sub-phase's inputs.
    A floor appears only when its driving quantity was observed — a
    step that moved no bytes has no bytes floor, not a zero floor."""
    out: Dict[str, float] = {}
    if device_bytes > 0:
        out["bytes_s"] = device_bytes / floors.h2d_bytes_per_s
    if d2h_crossings > 0:
        out["dispatch_s"] = d2h_crossings * floors.fetch_round_trip_s
    if hashes > 0:
        out["compute_s"] = hashes / floors.kernel_hashes_per_s
    return out


def classify(achieved_s: float,
             floors: Optional[Dict[str, float]]) -> dict:
    """Attainable-vs-achieved verdict for one sub-phase. ``floors`` is
    ``subphase_floors(...)`` output, or None for an uncalibrated
    device."""
    if floors is None:
        return {
            "floors": {}, "attainable_s": 0.0,
            "bound": NOT_CALIBRATED, "efficiency": 0.0,
        }
    attainable = max(floors.values()) if floors else 0.0
    if attainable <= 0:
        bound = "fixed-overhead"
    elif achieved_s > FIXED_OVERHEAD_FACTOR * attainable:
        bound = "fixed-overhead"
    else:
        bound = _BOUND_NAMES[max(floors, key=floors.get)]
    eff = (
        min(1.0, attainable / achieved_s) if achieved_s > 0 else 0.0
    )
    return {
        "floors": {k: round(v, 6) for k, v in floors.items()},
        "attainable_s": round(attainable, 6),
        "bound": bound,
        "efficiency": round(eff, 4),
    }


def _window_spans(spans: Sequence[Span], lo: int, hi: int) -> List[Span]:
    return [
        s for s in spans
        if s.tags.get("block_lo") == lo and s.tags.get("block_hi") == hi
    ]


def window_costs(number: int,
                 spans: Optional[Sequence[Span]] = None,
                 tracer_: Optional[Tracer] = None) -> dict:
    """The ``khipu_window_costs(n)`` payload: per-sub-phase roofline
    rows for the window containing block ``number``, plus the headline
    verdict (the costliest sub-phase and what it is bound by).

    Returns ``{"found": False, ...}`` when the ledger has no window
    covering ``number``.
    """
    rep = LEDGER.window_report(number)
    if rep is None:
        return {
            "found": False,
            "number": number,
            "ledgerEnabled": LEDGER.enabled,
        }
    t = tracer_ if tracer_ is not None else tracer
    if spans is None:
        spans = t.snapshot()
    lo, hi = rep["block_lo"], rep["block_hi"]

    # span-side join: seconds + node counts per sub-phase name. Window
    # spans carry block_lo/hi range tags; sub-phase spans inherit them
    # only on the driver side, so fall back to ANY span of that name
    # when the window-scoped filter finds none (single-window bench
    # captures) — ledger seconds remain the last resort.
    scoped = _window_spans(spans, lo, hi)
    span_s: Dict[str, float] = {}
    span_nodes: Dict[str, int] = {}
    for s in spans:
        if s.name not in SEAL_SUBPHASES:
            continue
        span_s[s.name] = span_s.get(s.name, 0.0) + s.duration
        n = s.tags.get("nodes")
        if n:
            span_nodes[s.name] = span_nodes.get(s.name, 0) + int(n)
    scoped_s: Dict[str, float] = {}
    for s in scoped:
        if s.name in SEAL_SUBPHASES:
            scoped_s[s.name] = scoped_s.get(s.name, 0.0) + s.duration

    dev_floors = device_floors()
    rows: Dict[str, dict] = {}
    sub = rep.get("subphases", {})
    names = set(sub) | set(scoped_s) | set(span_s)
    for name in sorted(names):
        if not name.startswith("seal."):
            continue
        ledger_row = sub.get(name, {})
        device_bytes = 0
        d2h_crossings = 0
        ledger_s = 0.0
        for site, agg in ledger_row.get("sites", {}).items():
            ledger_s += agg["seconds"]
            if agg["direction"] == HOST:
                continue
            device_bytes += agg["bytes"]
            if agg["direction"] == D2H:
                d2h_crossings += agg["count"]
        achieved = scoped_s.get(name) or span_s.get(name) or ledger_s
        hashes = span_nodes.get(name, 0)
        floors = (
            subphase_floors(dev_floors, device_bytes, d2h_crossings,
                            hashes)
            if dev_floors is not None else None
        )
        rows[name] = {
            "achieved_s": round(achieved, 6),
            "device_bytes": device_bytes,
            "d2h_crossings": d2h_crossings,
            "hashes": hashes,
            **classify(achieved, floors),
        }

    verdict = None
    if rows:
        top = max(rows, key=lambda n: rows[n]["achieved_s"])
        verdict = {
            "subphase": top,
            "bound": rows[top]["bound"],
            "achieved_s": rows[top]["achieved_s"],
            "attainable_s": rows[top]["attainable_s"],
        }
    return {
        "found": True,
        "number": number,
        "window": rep["window"],
        "block_lo": lo,
        "block_hi": hi,
        "blocks": rep["blocks"],
        "subphases": rows,
        "verdict": verdict,
        "device_kind": _device_kind(),
        # this device's measured floors, or None: not calibrated
        "floors": dev_floors._asdict() if dev_floors is not None else None,
    }


def cost_tracks(tracer_: Optional[Tracer] = None) -> List[dict]:
    """Chrome counter ("C") events: one ``window cost model`` sample
    per sealed window — achieved vs attainable seconds summed over its
    seal sub-phases, stamped at the window's last ledger event. The
    track renders under the span timeline so "this window ran 5x over
    its roofline" is visible in perfetto without leaving the trace."""
    t = tracer_ if tracer_ is not None else tracer
    events = LEDGER.events()
    if not events:
        return []
    last_t0: Dict[int, float] = {}
    for ev in events:
        if ev.window >= 0:
            last_t0[ev.window] = max(
                last_t0.get(ev.window, 0.0), ev.t0 + ev.duration
            )
    out: List[dict] = []
    for window, lo, hi in list(LEDGER._windows):
        costs = window_costs(lo, tracer_=t)
        if not costs.get("found") or not costs["subphases"]:
            continue
        achieved = sum(
            r["achieved_s"] for r in costs["subphases"].values()
        )
        attainable = sum(
            r["attainable_s"] for r in costs["subphases"].values()
        )
        ts = last_t0.get(window)
        if ts is None:
            continue
        out.append({
            "name": "window cost model (s)", "ph": "C",
            "pid": 1, "tid": 0,
            "ts": round((ts - t.epoch_perf) * 1e6, 3),
            "args": {
                "achieved_s": round(achieved, 6),
                "attainable_s": round(attainable, 6),
            },
        })
    return out
