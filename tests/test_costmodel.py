"""Per-window cost model (observability/costmodel.py): floors,
bound classification, and the ledger x span join — driven by synthetic
ledger events and spans, no replay needed (the end-to-end surface is
covered by the recorded-replay smoke in test_observability.py)."""

import types

import pytest

from khipu_tpu.observability import costmodel, recorder
from khipu_tpu.observability.costmodel import (
    FIXED_OVERHEAD_FACTOR,
    NOT_CALIBRATED,
    DeviceFloors,
    classify,
    cost_tracks,
    device_floors,
    subphase_floors,
    window_costs,
)
from khipu_tpu.observability.profiler import D2H, H2D, HOST, LEDGER
from khipu_tpu.observability.trace import Tracer

# the tests' OWN floors (round numbers, no device's): 100 ms per
# materialised fetch, 20 MB/s upload, 80 M hashes/s
FLOORS = DeviceFloors(
    fetch_round_trip_s=0.1, h2d_bytes_per_s=20e6,
    kernel_hashes_per_s=80e6,
)


@pytest.fixture(autouse=True)
def _clean_ledger():
    LEDGER.reset()
    yield
    LEDGER.disable()
    LEDGER.reset()


@pytest.fixture
def calibrated(monkeypatch):
    """Run as a device whose floors are FLOORS."""
    monkeypatch.setattr(costmodel, "_device_kind", lambda: "test-device")
    monkeypatch.setitem(costmodel.DEVICE_FLOORS, "test-device", FLOORS)


def _span(name, duration, **tags):
    """A snapshot-shaped span: window_costs only reads name, duration,
    and tags."""
    return types.SimpleNamespace(name=name, duration=duration, tags=tags)


class TestFloors:
    def test_no_observed_quantity_no_floor(self):
        assert subphase_floors(FLOORS, 0, 0, 0) == {}

    def test_each_quantity_drives_its_floor(self):
        floors = subphase_floors(FLOORS, 20_000_000, 2, 80_000_000)
        assert floors["bytes_s"] == pytest.approx(1.0)
        assert floors["dispatch_s"] == pytest.approx(0.2)
        assert floors["compute_s"] == pytest.approx(1.0)

    def test_partial_quantities_partial_floors(self):
        floors = subphase_floors(FLOORS, 4096, 0, 0)
        assert set(floors) == {"bytes_s"}

    def test_v5e_row_is_the_one_measured_row(self):
        assert set(costmodel.DEVICE_FLOORS) == {"TPU v5 lite"}
        row = device_floors("TPU v5 lite")
        assert all(v > 0 for v in row)
        # an attached chip: millisecond fetches, GB/s uploads
        assert row.fetch_round_trip_s < 0.01
        assert row.h2d_bytes_per_s > 1e9

    def test_unknown_device_kind_has_no_row(self):
        assert device_floors("no such accelerator") is None
        # the CPU the tests run on is not a calibrated device either
        assert device_floors() is None


class TestClassify:
    def test_bytes_bound_within_overhead_factor(self):
        floors = {"bytes_s": 0.10, "dispatch_s": 0.05}
        v = classify(0.15, floors)
        assert v["bound"] == "bytes-bound"
        assert v["attainable_s"] == pytest.approx(0.10)
        assert v["efficiency"] == pytest.approx(0.6667, abs=1e-3)

    def test_dispatch_bound_when_rtt_floor_dominates(self):
        floors = {"bytes_s": 0.01, "dispatch_s": 0.182}
        assert classify(0.2, floors)["bound"] == "dispatch-bound"

    def test_fixed_overhead_past_factor(self):
        floors = {"bytes_s": 0.01}
        v = classify(FIXED_OVERHEAD_FACTOR * 0.01 + 0.001, floors)
        assert v["bound"] == "fixed-overhead"

    def test_no_floors_is_fixed_overhead(self):
        v = classify(0.5, {})
        assert v["bound"] == "fixed-overhead"
        assert v["attainable_s"] == 0.0
        assert v["efficiency"] == 0.0

    def test_uncalibrated_device_gets_no_verdict(self):
        v = classify(0.5, None)
        assert v["bound"] == NOT_CALIBRATED
        assert v["floors"] == {} and v["attainable_s"] == 0.0

    def test_efficiency_caps_at_one(self):
        # achieved FASTER than the floor (calibration drift) reads as
        # fully efficient, never >1
        assert classify(0.05, {"bytes_s": 0.10})["efficiency"] == 1.0


def _synthetic_window():
    """One sealed window with one ledger event per sub-phase shape:
    an h2d upload, a d2h rootcheck (2 crossings), and a host-only
    pack."""
    LEDGER.enable()
    LEDGER.note_window(1, 0, 7)
    with LEDGER.context(window=1, phase="seal"):
        LEDGER.record("seal.upload", H2D, 2_000_000, duration=0.02)
        LEDGER.record("seal.pack", HOST, 4096, duration=0.01)
    with LEDGER.context(window=1, phase="collect"):
        # the collect-thread rootcheck keeps phase="collect"; its SITE
        # carries the sub-phase attribution
        LEDGER.record("seal.rootcheck", D2H, 512, duration=0.05)
        LEDGER.record("seal.rootcheck", D2H, 512, duration=0.05)


class TestWindowCosts:
    def test_not_found_shape(self):
        out = window_costs(999, spans=[])
        assert out == {
            "found": False, "number": 999,
            "ledgerEnabled": LEDGER.enabled,
        }

    def test_join_and_verdicts(self, calibrated):
        _synthetic_window()
        spans = [
            # 2 MB / 20 MB/s = 0.1 s floor; 0.15 s achieved -> within
            # the overhead factor, bytes-bound
            _span("seal.upload", 0.15),
            # 2 d2h crossings * 100 ms = 0.2 s floor; 0.2 s achieved
            _span("seal.rootcheck", 0.20),
            # 800k hashes / 80 M/s = 10 ms floor; 0.5 s achieved is
            # >3x over it -> fixed-overhead (host-side work)
            _span("seal.pack", 0.5, nodes=800_000),
        ]
        out = window_costs(3, spans=spans)
        assert out["found"]
        assert (out["block_lo"], out["block_hi"]) == (0, 7)
        rows = out["subphases"]
        up = rows["seal.upload"]
        assert up["bound"] == "bytes-bound"
        assert up["device_bytes"] == 2_000_000
        assert up["d2h_crossings"] == 0  # h2d enqueues pay no RTT
        assert up["floors"]["bytes_s"] == pytest.approx(0.1)
        assert up["efficiency"] == pytest.approx(0.6667, abs=1e-3)
        rc = rows["seal.rootcheck"]
        assert rc["bound"] == "dispatch-bound"
        assert rc["d2h_crossings"] == 2
        pk = rows["seal.pack"]
        assert pk["bound"] == "fixed-overhead"
        assert pk["device_bytes"] == 0  # HOST bytes never cross
        assert pk["hashes"] == 800_000
        # headline: the costliest sub-phase names the verdict
        assert out["verdict"]["subphase"] == "seal.pack"
        assert out["verdict"]["bound"] == "fixed-overhead"
        assert out["device_kind"] == "test-device"
        assert out["floors"] == FLOORS._asdict()

    def test_uncalibrated_device_reports_not_calibrated(self):
        """No row for this device_kind (the tests' CPU): every
        sub-phase still reports what it achieved and moved, but its
        bound is 'not calibrated' — never another device's verdict."""
        _synthetic_window()
        out = window_costs(3, spans=[_span("seal.upload", 0.15)])
        assert out["floors"] is None
        for row in out["subphases"].values():
            assert row["bound"] == NOT_CALIBRATED
            assert row["floors"] == {}
        assert out["subphases"]["seal.upload"]["device_bytes"] == 2_000_000
        assert out["verdict"]["bound"] == NOT_CALIBRATED

    def test_ledger_seconds_are_the_span_fallback(self):
        """No spans at all (tracer off while the ledger ran): achieved
        falls back to the ledger's own crossing seconds, so the RPC
        still classifies instead of reporting zeros."""
        _synthetic_window()
        out = window_costs(3, spans=[])
        assert out["subphases"]["seal.upload"]["achieved_s"] == (
            pytest.approx(0.02)
        )
        assert out["subphases"]["seal.rootcheck"]["achieved_s"] == (
            pytest.approx(0.10)
        )

    def test_cost_tracks_emit_one_counter_per_window(self, calibrated):
        _synthetic_window()
        t = Tracer()
        events = cost_tracks(tracer_=t)
        assert len(events) == 1
        ev = events[0]
        assert ev["name"] == "window cost model (s)"
        assert ev["ph"] == "C"
        assert ev["args"]["achieved_s"] > 0
        assert ev["args"]["attainable_s"] > 0
        assert isinstance(ev["ts"], float)

    def test_empty_ledger_no_tracks(self):
        assert cost_tracks(tracer_=Tracer()) == []


class _FakeHist:
    def __init__(self, s):
        self.value = {"sum": s}


class TestPhaseShares:
    def test_subphases_share_the_canonical_denominator(
            self, monkeypatch):
        """Sub-phases nest inside window.seal: they are excluded from
        the denominator (no double-billing) but reported as fractions
        of the same canonical total, so seal.upload reads directly
        against a ceiling."""
        canon = recorder.LIFECYCLE_PHASES + (recorder.PHASE_STALL,)
        sums = {p: 0.0 for p in canon + recorder.SEAL_SUBPHASES
                + recorder.EXEC_SUBPHASES}
        sums[recorder.PHASE_SEAL] = 6.0
        sums[recorder.PHASE_COLLECT] = 4.0
        sums["seal.upload"] = 5.0
        monkeypatch.setattr(
            recorder, "PHASE_HISTOGRAMS",
            {p: _FakeHist(v) for p, v in sums.items()},
        )
        shares = recorder.phase_shares()
        assert shares[recorder.PHASE_SEAL] == pytest.approx(0.6)
        assert shares[recorder.PHASE_COLLECT] == pytest.approx(0.4)
        assert shares["seal.upload"] == pytest.approx(0.5)
        # zero-sum phases are omitted entirely
        assert recorder.PHASE_ANNOUNCE not in shares

    def test_empty_histograms_empty_shares(self, monkeypatch):
        canon = recorder.LIFECYCLE_PHASES + (recorder.PHASE_STALL,)
        monkeypatch.setattr(
            recorder, "PHASE_HISTOGRAMS",
            {p: _FakeHist(0.0)
             for p in canon + recorder.SEAL_SUBPHASES
             + recorder.EXEC_SUBPHASES},
        )
        assert recorder.phase_shares() == {}
