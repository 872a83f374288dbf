"""Cost-model-adaptive commit tests (sync/adaptive.py): the backend
probe gate, the EWMA Schmitt trigger (flip without flapping), the
upload-verdict depth hint, and the end-to-end CPU fallback — a replay
with the adaptive controller on a slow-d2d backend commits on the host
path and still lands on the bit-exact chain."""

import dataclasses

import pytest

import khipu_tpu.sync.adaptive as adaptive_mod
from khipu_tpu.config import SyncConfig
from khipu_tpu.sync.adaptive import (
    ADAPTIVE_GAUGES,
    AdaptiveCommitController,
    ProbeResult,
    probe_backend,
)


def _sync_cfg(**overrides):
    overrides.setdefault("adaptive_probe", False)  # unit tests doctor it
    return SyncConfig(**overrides)


def _slow_probe(platform="doctored"):
    """A backend where the d2d gather LOSES to the host memcpy 100x —
    the round-7 1-core-CPU shape."""
    return ProbeResult(platform, 1e6, 1e8, False)


def _fast_probe(platform="doctored-hbm"):
    return ProbeResult(platform, 1e11, 1e9, True)


class TestProbeGate:
    def test_doctored_slow_d2d_backend_starts_in_host_mode(
            self, monkeypatch):
        """THE acceptance flip: on a backend whose 'device' memory is
        host RAM the probe cannot clear the margin, so the controller
        downgrades to host commit BEFORE the first window — no 34 s
        device fixpoint is ever paid."""
        monkeypatch.setattr(
            adaptive_mod, "probe_backend", lambda margin: _slow_probe()
        )
        ctrl = AdaptiveCommitController(
            _sync_cfg(adaptive_probe=True), device_cap=True
        )
        assert ctrl.mode() == "host"
        assert not ctrl.device_mode
        assert ctrl.flips == 1  # the probe downgrade is a counted flip
        assert ADAPTIVE_GAUGES["device_mode"] == 0

    def test_fast_d2d_backend_keeps_device_mode(self, monkeypatch):
        monkeypatch.setattr(
            adaptive_mod, "probe_backend", lambda margin: _fast_probe()
        )
        ctrl = AdaptiveCommitController(
            _sync_cfg(adaptive_probe=True), device_cap=True
        )
        assert ctrl.mode() == "device"
        assert ctrl.flips == 0

    def test_no_device_cap_never_probes_never_flips(self):
        ctrl = AdaptiveCommitController(
            _sync_cfg(adaptive_probe=True), device_cap=False
        )
        assert ctrl.mode() == "host"
        assert ctrl.probe is None
        # a miraculous device EWMA cannot upgrade past the config cap
        ctrl._ewma["device"] = 1e-12
        ctrl._dwell = 10**6
        ctrl.observe_window("host", 100, 1.0)
        assert ctrl.mode() == "host" and ctrl.flips == 0

    def test_real_cpu_probe_is_cached_and_consistent(self):
        """Smoke the real measurement on whatever backend the test
        host has: sane rates, process-cache hit on the second call."""
        p1 = probe_backend(margin=1.5)
        p2 = probe_backend(margin=1.5)
        assert p1 is p2  # cached per platform
        assert p1.d2d_bytes_per_s >= 0 and p1.memcpy_bytes_per_s >= 0


    def test_probe_exception_on_tpu_propagates(self, monkeypatch):
        """On a chip a probe that cannot run is a fault to surface,
        not a reason to commit on the host: nothing turns it into
        ``device_ok=False``."""
        monkeypatch.setattr(adaptive_mod.device, "platform", lambda: "tpu")
        monkeypatch.delitem(adaptive_mod._PROBE_CACHE, "tpu", raising=False)

        def boom(platform, margin):
            raise RuntimeError("mosaic said no")

        monkeypatch.setattr(adaptive_mod, "_measure_probe", boom)
        with pytest.raises(RuntimeError, match="mosaic said no"):
            probe_backend(margin=1.5)
        assert "tpu" not in adaptive_mod._PROBE_CACHE  # nothing cached
        with pytest.raises(RuntimeError):
            AdaptiveCommitController(
                _sync_cfg(adaptive_probe=True), device_cap=True
            )

    def test_backend_that_fails_to_start_propagates(self, monkeypatch):
        def no_backend():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(adaptive_mod.device, "platform", no_backend)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            probe_backend(margin=1.5)


class TestSchmittTrigger:
    def _device_ctrl(self, **overrides):
        ctrl = AdaptiveCommitController(_sync_cfg(**overrides),
                                        device_cap=True)
        ctrl.probe = _fast_probe()  # probe said ok; EWMAs now decide
        return ctrl

    def test_slow_device_windows_flip_to_host_after_dwell(self):
        """Device windows costing 100x the host floor per hash must
        flip the mode — but only once ``adaptive_dwell_windows`` have
        been spent in device mode (no knee-jerk on the first bad
        window), and the flip must not oscillate afterwards."""
        ctrl = self._device_ctrl()
        dwell = ctrl.cfg.adaptive_dwell_windows
        slow = 100.0 * ctrl.host_floor_s  # per-hash, ratio 100 >> 2.0
        for i in range(dwell - 1):
            ctrl.observe_window("device", 1000, 1000 * slow)
            assert ctrl.mode() == "device", f"flipped early at {i}"
        assert ctrl.flaps_suppressed == dwell - 1  # wanted, held back
        ctrl.observe_window("device", 1000, 1000 * slow)
        assert ctrl.mode() == "host"
        assert ctrl.flips == 1
        # more slow-device evidence must NOT flip again (already host)
        for _ in range(3 * dwell):
            ctrl.observe_window("host", 1000, 1000 * ctrl.host_floor_s)
        assert ctrl.mode() == "host" and ctrl.flips == 1

    def test_compiling_window_is_not_an_observation(self):
        """A first window whose dispatch compiled for 30 s (10 ms per
        hash against a host floor of microseconds) followed by fast
        ones must leave device mode on past the dwell — with the
        compile in the EWMA the 0.6^n tail alone held the ratio above
        ``adaptive_flip_ratio`` and flipped for good at the dwell."""
        ctrl = self._device_ctrl()
        dwell = ctrl.cfg.adaptive_dwell_windows
        fast = 0.5 * ctrl.host_floor_s  # per-hash, ratio 0.5 < 2.0
        ctrl.observe_window("device", 3000, 30.0, compiled=True)
        assert ctrl._ewma["device"] is None  # counted, not sampled
        assert ctrl.windows == 1
        for _ in range(3 * dwell):
            ctrl.observe_window("device", 3000, 3000 * fast)
        assert ctrl.mode() == "device"
        assert ctrl.flips == 0 and ctrl.flaps_suppressed == 0
        # control: the same 30 s window AS a sample does flip
        bad = self._device_ctrl()
        bad.observe_window("device", 3000, 30.0)
        for _ in range(dwell):
            bad.observe_window("device", 3000, 3000 * fast)
        assert bad.mode() == "host"

    def test_hysteresis_band_blocks_flap(self):
        """A ratio inside the band (flip_back_ratio < r < flip_ratio)
        moves NOTHING in either mode — the band is the no-trade zone
        that kills oscillation on noisy backends."""
        ctrl = self._device_ctrl()
        ctrl._dwell = 10**6  # dwell satisfied; only the band holds
        mid = 1.0  # host == device per-hash: inside (0.5, 2.0)
        for _ in range(20):
            ctrl.observe_window("device", 1000,
                                1000 * mid * ctrl.host_floor_s)
        assert ctrl.mode() == "device" and ctrl.flips == 0

    def test_flip_back_needs_probe_ok_and_low_ratio(self):
        """Host mode flips back to device only when the device EWMA
        drops below ``flip_back_ratio`` x host AND the probe cleared
        the backend — a slow-d2d backend stays host forever."""
        ctrl = self._device_ctrl()
        ctrl.device_mode = False  # already downgraded
        ctrl._ewma["device"] = 0.1 * ctrl.host_floor_s  # 10x cheaper
        ctrl._dwell = 10**6
        ctrl.probe = _slow_probe()
        ctrl.observe_window("host", 1000, 1000 * ctrl.host_floor_s)
        assert ctrl.mode() == "host"  # probe veto holds
        ctrl.probe = _fast_probe()
        ctrl.observe_window("host", 1000, 1000 * ctrl.host_floor_s)
        assert ctrl.mode() == "device"
        assert ctrl.flips == 1

    def test_gauges_track_the_controller(self):
        ctrl = self._device_ctrl()
        ctrl.observe_window("device", 10, 10 * ctrl.host_floor_s)
        assert ADAPTIVE_GAUGES["windows_observed"] == ctrl.windows
        assert ADAPTIVE_GAUGES["device_mode"] == int(ctrl.device_mode)
        assert ADAPTIVE_GAUGES["ewma_device_hash_s"] > 0


class TestDepthHint:
    @pytest.fixture(autouse=True)
    def _calibrated(self, monkeypatch):
        # the hint needs this device's floors; the tests' CPU has no
        # row, so hand it one (the verdict itself is doctored below)
        from khipu_tpu.observability.costmodel import DeviceFloors

        monkeypatch.setattr(
            adaptive_mod, "device_floors",
            lambda: DeviceFloors(1e-3, 1e9, 1e8),
        )

    def _ctrl(self):
        return AdaptiveCommitController(_sync_cfg(), device_cap=False)

    def test_bytes_bound_upload_deepens_pipeline(self, monkeypatch):
        ctrl = self._ctrl()
        monkeypatch.setattr(
            adaptive_mod, "classify",
            lambda achieved, floors: {"bound": "bytes-bound"},
        )
        base = ctrl.cfg.pipeline_depth
        ctrl.note_upload(1 << 20, 0.5)
        assert ctrl.depth_hint == min(ctrl.cfg.adaptive_depth_max,
                                      base + 1)
        for _ in range(10):  # saturates at the cap, never beyond
            ctrl.note_upload(1 << 20, 0.5)
        assert ctrl.depth_hint == ctrl.cfg.adaptive_depth_max
        assert ADAPTIVE_GAUGES["depth_hint"] == ctrl.depth_hint

    def test_fixed_overhead_upload_shallows_pipeline(self, monkeypatch):
        ctrl = self._ctrl()
        monkeypatch.setattr(
            adaptive_mod, "classify",
            lambda achieved, floors: {"bound": "fixed-overhead"},
        )
        for _ in range(10):
            ctrl.note_upload(64, 0.5)
        assert ctrl.depth_hint == 1  # floors at 1, never 0

    def test_zero_duration_upload_is_ignored(self):
        ctrl = self._ctrl()
        ctrl.note_upload(1 << 20, 0.0)
        assert ctrl.depth_hint is None

    def test_uncalibrated_device_gets_no_hint(self, monkeypatch):
        """A device without a floors row is never classified against
        another device's numbers: no verdict, no depth hint."""
        monkeypatch.setattr(adaptive_mod, "device_floors", lambda: None)
        ctrl = self._ctrl()
        ctrl.note_upload(1 << 20, 0.5)
        assert ctrl.depth_hint is None


class TestAdaptiveReplay:
    def test_cpu_replay_flips_to_host_and_lands_bit_exact(
            self, monkeypatch):
        """End to end: a device-commit replay whose probe reports a
        slow-d2d backend must run its windows on the host path (no
        device fixpoint) and produce the identical chain — adaptive
        routing never touches state roots."""
        from tests.test_window import (
            ADDRS, CFG, ETH, MINER, chain as _chain_fixture,  # noqa: F401
        )
        from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
        from khipu_tpu.storage.storages import Storages
        from khipu_tpu.sync.replay import ReplayDriver
        from khipu_tpu.trie.bulk import host_hasher

        # build the 5-block fixture chain directly (module fixture is
        # in another file; importing the function, not the fixture)
        from khipu_tpu.sync.chain_builder import ChainBuilder
        from tests.test_window import INIT, tx

        builder = ChainBuilder(
            Blockchain(Storages(), CFG), CFG,
            GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}),
        )
        blocks = [builder.add_block(
            [tx(0, 0, None, 0, gas=300_000, payload=INIT)],
            coinbase=MINER)]
        blocks.append(builder.add_block([tx(1, 0, ADDRS[2], 123)],
                                        coinbase=MINER))
        blocks.append(builder.add_block([tx(2, 0, ADDRS[0], 1)],
                                        coinbase=MINER))

        monkeypatch.setattr(
            adaptive_mod, "probe_backend", lambda margin: _slow_probe()
        )
        cfg = dataclasses.replace(
            CFG, sync=SyncConfig(parallel_tx=False,
                                 commit_window_blocks=2,
                                 pipeline_depth=2),
        )
        assert cfg.sync.adaptive_commit  # on by default

        def _fresh():
            bc = Blockchain(Storages(), cfg)
            bc.load_genesis(
                GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS})
            )
            return bc

        bc = _fresh()
        driver = ReplayDriver(bc, cfg, device_commit=True)
        driver.hasher = host_hasher
        stats = driver.replay(blocks)
        assert stats.blocks == 3
        assert bc.get_header_by_number(3).hash == blocks[-1].hash
        assert ADAPTIVE_GAUGES["device_mode"] == 0
        assert ADAPTIVE_GAUGES["windows_observed"] >= 1

        # oracle: plain host replay, no device commit, no adaptive
        ref_cfg = dataclasses.replace(
            cfg, sync=dataclasses.replace(cfg.sync,
                                          adaptive_commit=False),
        )
        ref = _fresh()
        ReplayDriver(ref, ref_cfg).replay(blocks)
        for n in range(1, 4):
            assert (bc.get_header_by_number(n).hash
                    == ref.get_header_by_number(n).hash)
        assert (bc.get_header_by_number(3).state_root
                == ref.get_header_by_number(3).state_root)
