"""Cost-model-adaptive commit: pick the commit path the backend is
actually fast at (docs/roofline.md "The adaptive commit rule").

The device-resident commit (storage/device_mirror.py) is a bet: that
d2d gathers and the fused fixpoint beat the host memcpy + scalar keccak
they replaced. On the CPU backend the bet loses ~20x (a round-7 CPU capture) —
there "device" memory IS host RAM, so every d2d gather is a memcpy with
dispatch overhead on top, and the fused fixpoint re-hashes
``rounds x padded_rows`` where the host path hashes each node once.
This module closes the loop the cost model (observability/costmodel.py)
opened: measure, decide, and keep deciding.

Two instruments, one controller:

* ``probe_backend()`` — a one-shot, process-cached measurement per
  backend platform: time a jit d2d gather against the same-shape host
  fancy-index memcpy. Device commit only engages when d2d wins by
  ``adaptive_d2d_margin`` — on real HBM it wins by orders of
  magnitude; where device memory is host RAM it cannot, by
  construction, clear the margin. The probe's upload is billed to the
  ledger site ``adaptive.probe`` (KL001).
* ``AdaptiveCommitController`` — an EWMA over each window's seal-stage
  cost per hash (a window whose dispatch COMPILED its program is counted
  but not sampled: compile seconds say nothing about the steady state),
  one series per mode, with a Schmitt trigger between them: flip
  device -> host when the device EWMA exceeds
  ``adaptive_flip_ratio`` x the host estimate, flip back only below
  ``adaptive_flip_back_ratio`` x, and never flip before
  ``adaptive_dwell_windows`` windows have passed in the current mode
  (the hysteresis band + dwell kill oscillation). The host estimate
  starts from a calibrated scalar-keccak floor and is replaced by the
  measured host EWMA once host windows run. ``device_mirror_commit``
  stays the CAP: the controller only ever downgrades device -> host.

The controller also turns the ``seal.upload`` roofline verdict into a
``pipeline_depth`` recommendation: a bytes-bound upload overlaps with
more windows in flight (raise depth toward ``adaptive_depth_max``,
GPipe-style), a fixed-overhead upload does not (lower it and stop
paying queue memory for overlap that cannot happen). The verdict needs
this device's own floors (costmodel.DEVICE_FLOORS, keyed by
``device_kind``); a device without a row gets no hint.

Every decision is exported as the ``khipu_adaptive_*`` registry family
and a ``window.adapt`` flight-recorder event. Both commit paths
produce byte-identical state roots, so adaptive timing nondeterminism
never touches replay bit-exactness — only which hardware does the
hashing.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

from khipu_tpu import device
from khipu_tpu.observability.costmodel import (
    classify,
    device_floors,
    subphase_floors,
)
from khipu_tpu.observability.profiler import H2D, LEDGER
from khipu_tpu.observability.registry import REGISTRY
from khipu_tpu.observability.trace import event

__all__ = [
    "ADAPTIVE_GAUGES",
    "ProbeResult",
    "probe_backend",
    "AdaptiveCommitController",
]

ADAPTIVE_GAUGES = REGISTRY.gauge_group("khipu_adaptive", {
    # 1 while the controller holds the device-mirror commit path
    "device_mode": 0,
    # mode changes, ever (the initial probe downgrade counts)
    "flips_total": 0,
    "windows_observed": 0,
    # backend probe readout (bytes/s; 0 until a probe ran)
    "probe_d2d_bytes_per_s": 0,
    "probe_memcpy_bytes_per_s": 0,
    # current pipeline_depth recommendation (0 = no opinion yet)
    "depth_hint": 0,
    # per-hash seal-stage EWMAs the Schmitt trigger compares (seconds)
    "ewma_device_hash_s": 0.0,
    "ewma_host_hash_s": 0.0,
    # flips wanted by the ratio but suppressed by the dwell window
    "flap_suppressed_total": 0,
}, help="cost-model-adaptive commit controller (sync/adaptive.py)")

# probe workload: ~0.5 MB gathered through ~2k rows — small enough to
# be noise at startup
_PROBE_ROWS = 2048
_PROBE_COLS = 256
_PROBE_REPS = 3

# one probe per backend platform per process — jit warmup is the
# expensive part and the answer cannot change under our feet
_PROBE_CACHE: Dict[str, "ProbeResult"] = {}


class ProbeResult:
    """One backend's gather-vs-memcpy measurement."""

    __slots__ = ("platform", "d2d_bytes_per_s", "memcpy_bytes_per_s",
                 "device_ok")

    def __init__(self, platform: str, d2d_bytes_per_s: float,
                 memcpy_bytes_per_s: float, device_ok: bool):
        self.platform = platform
        self.d2d_bytes_per_s = d2d_bytes_per_s
        self.memcpy_bytes_per_s = memcpy_bytes_per_s
        self.device_ok = device_ok

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"<Probe {self.platform} d2d={self.d2d_bytes_per_s:.3g}B/s "
            f"memcpy={self.memcpy_bytes_per_s:.3g}B/s "
            f"ok={self.device_ok}>"
        )


def _measure_probe(platform: str, margin: float) -> ProbeResult:
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)  # KL003: seeded, replay-stable
    host = rng.integers(0, 256, size=(_PROBE_ROWS, _PROBE_COLS),
                        dtype=np.uint8)
    idx = rng.permutation(_PROBE_ROWS).astype(np.int32)

    gather = jax.jit(lambda a, i: a[i])
    with LEDGER.transfer("adaptive.probe", H2D,
                         host.nbytes + idx.nbytes):
        dev = jnp.asarray(host)
        idx_dev = jnp.asarray(idx)
    # the gather stays on device — its bytes never cross the boundary
    # (the H2D upload above is the only crossing, already ledgered)
    # khipu-lint: ok KL001 device-resident gather, no host<->device bytes
    gather(dev, idx_dev).block_until_ready()  # warm: compile + paths

    t0 = time.perf_counter()
    for _ in range(_PROBE_REPS):
        # khipu-lint: ok KL001 device-resident gather, no host<->device bytes
        gather(dev, idx_dev).block_until_ready()
    d2d_s = (time.perf_counter() - t0) / _PROBE_REPS

    host[idx]  # warm the host path too (page faults, cache)
    t0 = time.perf_counter()
    for _ in range(_PROBE_REPS):
        host[idx]
    memcpy_s = (time.perf_counter() - t0) / _PROBE_REPS

    nbytes = host.nbytes
    d2d_rate = nbytes / d2d_s if d2d_s > 0 else 0.0
    memcpy_rate = nbytes / memcpy_s if memcpy_s > 0 else 0.0
    # where device memory is host RAM the gather can never clear the
    # margin; real HBM clears it by orders of magnitude
    ok = d2d_rate >= margin * memcpy_rate > 0
    return ProbeResult(platform, d2d_rate, memcpy_rate, ok)


def probe_backend(margin: float = 1.5) -> ProbeResult:
    """Measure (once per backend platform) whether d2d gathers beat the
    host memcpy they would replace by ``margin``. A backend that fails
    to start, or a probe that fails to run, raises: the caller asked
    for device commit, and "no device" is not an answer to hide behind
    a host fallback."""
    platform = device.platform()
    cached = _PROBE_CACHE.get(platform)
    if cached is not None:
        return cached
    result = _measure_probe(platform, margin)
    _PROBE_CACHE[platform] = result
    ADAPTIVE_GAUGES["probe_d2d_bytes_per_s"] = int(result.d2d_bytes_per_s)
    ADAPTIVE_GAUGES["probe_memcpy_bytes_per_s"] = int(
        result.memcpy_bytes_per_s
    )
    return result


def _calibrate_host_hash_s(samples: int = 256) -> float:
    """Seconds per scalar host keccak — the host estimate the trigger
    compares against until measured host windows replace it."""
    from khipu_tpu.base.crypto.keccak import keccak256

    msg = b"\x5a" * 128  # a typical branch-node encoding size
    keccak256(msg)  # bind the implementation outside the clock
    t0 = time.perf_counter()
    for _ in range(samples):
        keccak256(msg)
    return (time.perf_counter() - t0) / samples


class AdaptiveCommitController:
    """Per-committer mode controller. All methods run on the seal-stage
    thread (one window at a time), so plain attributes suffice."""

    def __init__(self, sync_cfg, device_cap: bool = True):
        self.cfg = sync_cfg
        # the config is the CAP: adaptive only downgrades device->host
        self.device_cap = bool(device_cap)
        self.device_mode = self.device_cap
        self.windows = 0
        self.flips = 0
        self.flaps_suppressed = 0
        self._dwell = 0  # windows spent in the current mode
        self._ewma: Dict[str, Optional[float]] = {
            "device": None, "host": None,
        }
        self.host_floor_s = _calibrate_host_hash_s()
        self.depth_hint: Optional[int] = None
        self.probe: Optional[ProbeResult] = None
        if self.device_cap and sync_cfg.adaptive_probe:
            self.probe = probe_backend(sync_cfg.adaptive_d2d_margin)
            if not self.probe.device_ok:
                self._flip(False, "probe", ratio=0.0)
        self._export()

    # ------------------------------------------------------ observations

    def mode(self) -> str:
        return "device" if self.device_mode else "host"

    def observe_window(self, mode: str, hashes: int,
                       seal_seconds: float,
                       compiled: bool = False) -> None:
        """One window's seal-stage verdict: ``hashes`` nodes resolved in
        ``seal_seconds`` under ``mode``. Updates that mode's EWMA, then
        re-runs the Schmitt trigger. ``compiled``: the dispatch built
        its program inside ``seal_seconds`` — the window counts toward
        the dwell but is not an EWMA sample."""
        self.windows += 1
        self._dwell += 1
        if hashes > 0 and seal_seconds > 0 and not compiled:
            per_hash = seal_seconds / hashes
            prev = self._ewma.get(mode)
            alpha = self.cfg.adaptive_ewma_alpha
            self._ewma[mode] = (
                per_hash if prev is None
                else alpha * per_hash + (1.0 - alpha) * prev
            )
        self._decide()
        self._export()

    def note_upload(self, upload_bytes: int,
                    upload_seconds: float) -> None:
        """Roofline-classify the window's ``seal.upload`` and move the
        pipeline-depth recommendation: bytes-bound uploads overlap with
        deeper pipelines; fixed-overhead ones do not."""
        if upload_seconds <= 0:
            return
        floors = device_floors()
        if floors is None:
            return  # not calibrated for this device: no opinion
        verdict = classify(
            upload_seconds, subphase_floors(floors, upload_bytes, 0, 0)
        )
        prev = self.depth_hint
        base = prev if prev is not None else self.cfg.pipeline_depth
        if verdict["bound"] == "bytes-bound":
            hint = min(self.cfg.adaptive_depth_max, base + 1)
        elif verdict["bound"] == "fixed-overhead":
            hint = max(1, base - 1)
        else:
            hint = base
        self.depth_hint = hint
        ADAPTIVE_GAUGES["depth_hint"] = hint
        if hint != prev:
            event("window.adapt", kind="depth", depth_hint=hint,
                  bound=verdict["bound"], upload_bytes=upload_bytes)

    # --------------------------------------------------------- decisions

    def _host_estimate(self) -> float:
        measured = self._ewma.get("host")
        return measured if measured is not None else self.host_floor_s

    def _decide(self) -> None:
        if not self.device_cap:
            return
        host_est = self._host_estimate()
        dev = self._ewma.get("device")
        if host_est <= 0 or dev is None:
            return
        ratio = dev / host_est
        if self.device_mode and ratio > self.cfg.adaptive_flip_ratio:
            if self._dwell >= self.cfg.adaptive_dwell_windows:
                self._flip(False, "ewma", ratio=ratio)
            else:
                self.flaps_suppressed += 1
                ADAPTIVE_GAUGES["flap_suppressed_total"] = (
                    self.flaps_suppressed
                )
        elif (not self.device_mode
              and ratio < self.cfg.adaptive_flip_back_ratio
              and (self.probe is None or self.probe.device_ok)):
            if self._dwell >= self.cfg.adaptive_dwell_windows:
                self._flip(True, "ewma", ratio=ratio)
            else:
                self.flaps_suppressed += 1
                ADAPTIVE_GAUGES["flap_suppressed_total"] = (
                    self.flaps_suppressed
                )

    def _flip(self, device_mode: bool, reason: str,
              ratio: float) -> None:
        self.device_mode = device_mode
        self.flips += 1
        self._dwell = 0
        event("window.adapt", kind="mode", mode=self.mode(),
              reason=reason, ratio=round(ratio, 4),
              window=self.windows)

    def _export(self) -> None:
        ADAPTIVE_GAUGES["device_mode"] = int(self.device_mode)
        ADAPTIVE_GAUGES["flips_total"] = self.flips
        ADAPTIVE_GAUGES["windows_observed"] = self.windows
        ADAPTIVE_GAUGES["flap_suppressed_total"] = self.flaps_suppressed
        dev = self._ewma.get("device")
        host = self._ewma.get("host")
        ADAPTIVE_GAUGES["ewma_device_hash_s"] = (
            round(dev, 9) if dev is not None else 0.0
        )
        ADAPTIVE_GAUGES["ewma_host_hash_s"] = (
            round(host, 9) if host is not None else 0.0
        )
