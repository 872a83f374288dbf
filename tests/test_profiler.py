"""Data-movement ledger tests (khipu_tpu/observability/profiler.py):
exact byte accounting against a known-size node fixture, zero-cost
disabled mode (bit-exact replay, no extra device syncs), chrome counter
tracks, and the registry / sampling satellites that rode along (scrape-pass collector caching,
histogram bucket overrides, deterministic per-trace-id sampling)."""

import dataclasses
import json
import random

import pytest

from khipu_tpu.base.crypto.keccak import keccak256
from khipu_tpu.base.crypto.secp256k1 import (
    privkey_to_pubkey,
    pubkey_to_address,
)
from khipu_tpu.config import (
    ObservabilityConfig,
    SyncConfig,
    fixture_config,
)
from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
from khipu_tpu.domain.transaction import Transaction, sign_transaction
from khipu_tpu.observability import export, recorder
from khipu_tpu.observability.profiler import (
    COLLECT_CLASSES,
    D2H,
    H2D,
    HOST,
    LEDGER,
    TransferLedger,
    _NULL_TRANSFER,
)
from khipu_tpu.observability.registry import MetricsRegistry
from khipu_tpu.observability.trace import trace_sampled, tracer
from khipu_tpu.storage.device_mirror import TILE, DeviceNodeMirror
from khipu_tpu.storage.storages import Storages
from khipu_tpu.sync.chain_builder import ChainBuilder
from khipu_tpu.sync.replay import ReplayDriver

CFG = fixture_config(chain_id=1)
KEYS = [(i + 1).to_bytes(32, "big") for i in range(4)]
ADDRS = [pubkey_to_address(privkey_to_pubkey(k)) for k in KEYS]
ETH = 10**18


@pytest.fixture(autouse=True)
def _clean_ledger():
    """Every test starts and ends with a disabled, empty ledger (the
    registry counters persist by design — they are monotonic)."""
    LEDGER.disable()
    LEDGER.reset()
    yield
    LEDGER.disable()
    LEDGER.reset()


def _chain(n_blocks=8, txs_per_block=8):
    builder = ChainBuilder(
        Blockchain(Storages(), CFG), CFG,
        GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}),
    )
    blocks = []
    nonces = [0] * 4
    for n in range(n_blocks):
        txs = []
        for j in range(txs_per_block):
            i = j % 4
            txs.append(
                sign_transaction(
                    Transaction(
                        nonces[i], 10**9, 21_000,
                        ADDRS[(i + 1) % 4], 100 + n,
                    ),
                    KEYS[i], chain_id=1,
                )
            )
            nonces[i] += 1
        blocks.append(builder.add_block(txs, coinbase=b"\xaa" * 20))
    return blocks


def _fresh_chain(cfg):
    bc = Blockchain(Storages(), cfg)
    bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
    return bc


def _pipeline_cfg(w=2, depth=2):
    return dataclasses.replace(
        CFG,
        sync=SyncConfig(
            parallel_tx=True, commit_window_blocks=w,
            pipeline_depth=depth,
        ),
    )


# --------------------------------------------------------- ledger core


class TestLedgerCore:
    def test_disabled_transfer_is_inert_singleton(self):
        """The _NULL_SPAN pattern: while disabled, every call site gets
        the SAME inert object — no allocation, no recording."""
        t1 = LEDGER.transfer("x", H2D, 100)
        t2 = LEDGER.transfer("y", D2H, 10**9)
        assert t1 is _NULL_TRANSFER and t2 is _NULL_TRANSFER
        with t1:
            pass
        assert LEDGER.recorded == 0
        assert LEDGER.events() == []

    def test_exact_byte_accounting(self):
        """N events of a known size: totals must be EXACT, not
        approximate — the ledger is an accountant, not a sampler."""
        LEDGER.enable()
        n, size = 64, 576
        for _ in range(n):
            with LEDGER.transfer("fixture.site", H2D, size):
                pass
        LEDGER.record("fixture.site", D2H, 32, duration=0.001)
        totals = LEDGER.totals()
        assert totals[("fixture.site", H2D)]["bytes"] == n * size
        assert totals[("fixture.site", H2D)]["count"] == n
        assert totals[("fixture.site", D2H)]["bytes"] == 32
        assert LEDGER.direction_totals() == {
            H2D: n * size, D2H: 32,
        }

    def test_host_direction_stays_out_of_device_totals(self):
        LEDGER.enable()
        LEDGER.record("window.store", HOST, 4096)
        LEDGER.record("real.site", H2D, 10)
        assert LEDGER.direction_totals() == {H2D: 10, D2H: 0}
        # but the event IS in the ring for classification
        host = [e for e in LEDGER.events() if e.direction == HOST]
        assert len(host) == 1 and host[0].nbytes == 4096

    def test_failed_transfer_not_committed(self):
        LEDGER.enable()
        with pytest.raises(RuntimeError):
            with LEDGER.transfer("x", H2D, 100):
                raise RuntimeError("device fell over")
        assert LEDGER.recorded == 0

    def test_context_tags_and_nesting(self):
        LEDGER.enable()
        with LEDGER.context(window=5, phase="seal"):
            LEDGER.record("a", H2D, 1)
            with LEDGER.context(phase="collect"):
                LEDGER.record("b", D2H, 2)
            LEDGER.record("c", H2D, 3)
        LEDGER.record("d", H2D, 4)
        evs = {e.site: e for e in LEDGER.events()}
        assert (evs["a"].window, evs["a"].phase) == (5, "seal")
        assert (evs["b"].window, evs["b"].phase) == (5, "collect")
        assert (evs["c"].window, evs["c"].phase) == (5, "seal")
        assert (evs["d"].window, evs["d"].phase) == (-1, "")

    def test_window_report_resolution_newest_wins(self):
        """An epoch re-replay reuses block numbers; the report must
        resolve to the NEWEST window covering the block."""
        LEDGER.enable()
        LEDGER.note_window(10, 10, 13)
        with LEDGER.context(window=10, phase="seal"):
            LEDGER.record("old", H2D, 111)
        LEDGER.note_window(12, 12, 15)
        with LEDGER.context(window=12, phase="seal"):
            LEDGER.record("new", H2D, 222)
        rep = LEDGER.window_report(12)
        assert rep["window"] == 12 and rep["blocks"] == 4
        assert "new" in rep["phases"]["seal"]["sites"]
        assert "old" not in rep["phases"]["seal"]["sites"]
        # block 10 is only covered by the first window
        assert LEDGER.window_report(10)["window"] == 10

    def test_window_report_classifies_collect_traffic(self):
        LEDGER.enable()
        LEDGER.note_window(1, 1, 2)
        with LEDGER.context(window=1, phase="collect"):
            LEDGER.record("fused.collect", D2H, 1000)
            LEDGER.record("window.store", HOST, 500)
            LEDGER.record("block.save", HOST, 0, duration=0.01)
        rep = LEDGER.window_report(1)
        cls = rep["collect_classes"]
        assert cls["placeholder-resolution"]["bytes"] == 1000
        assert cls["store-write"]["bytes"] == 500
        assert cls["block-save"]["seconds"] > 0
        # device bytes/block excludes the host events
        assert rep["device_bytes_per_block"] == {D2H: 500}

    def test_ring_overflow_drop_oldest(self):
        led = TransferLedger(capacity=8)
        led.enable()
        for i in range(20):
            led.record(f"s{i}", H2D, i)
        assert led.recorded == 20
        assert led.dropped == 12
        evs = led.events()
        assert len(evs) == 8
        assert evs[0].site == "s12" and evs[-1].site == "s19"

    def test_reset_drops_events_keeps_counters(self):
        """Registry counters are monotonic by contract; reset clears
        the ring and per-block state only."""
        LEDGER.enable()
        LEDGER.record("persist.site", H2D, 100)
        LEDGER.note_blocks(4)
        pair = LEDGER._counters[("persist.site", H2D)]
        before = pair[0].value
        LEDGER.reset()
        assert LEDGER.events() == [] and LEDGER.blocks == 0
        assert LEDGER._counters[("persist.site", H2D)][0].value == before
        LEDGER.record("persist.site", H2D, 50)
        assert pair[0].value == before + 50

    def test_registry_families_and_bytes_per_block_gauge(self):
        from khipu_tpu.observability.registry import REGISTRY

        LEDGER.enable()
        LEDGER.record("gauge.site", H2D, 640)
        LEDGER.note_blocks(2)
        text = REGISTRY.prometheus_text()
        assert text.count(
            "# TYPE khipu_device_transfer_bytes_total counter"
        ) == 1
        assert text.count(
            "# TYPE khipu_device_transfer_seconds_total counter"
        ) == 1
        assert 'site="gauge.site"' in text
        snap = REGISTRY.snapshot()
        gauge = snap.get("khipu_device_transfer_bytes_per_block", {})
        assert gauge.get('direction="h2d"') == 320

    def test_config_enables_ledger(self):
        from khipu_tpu.observability.profiler import apply_config

        apply_config(ObservabilityConfig())  # disabled: no stomp
        assert not LEDGER.enabled
        apply_config(
            ObservabilityConfig(ledger_enabled=True, ledger_capacity=128)
        )
        assert LEDGER.enabled and LEDGER.capacity == 128


# --------------------------------------- exact accounting, device path


@pytest.fixture(scope="module")
def mirror_fixture():
    """N known-size nodes admitted into the real device mirror — the
    fixture the exact-byte tests audit against."""
    n, size = 40, 300
    rng = random.Random(11)
    items = {}
    while len(items) < n:
        enc = rng.randbytes(size)
        items[keccak256(enc)] = enc
    m = DeviceNodeMirror(capacity_rows_per_class=1024)
    m.admit(items)
    m.flush()
    return m, items, size


class TestDeviceByteAccounting:
    def test_mirror_get_exact_bytes(self, mirror_fixture):
        """Each mirror.get fetches one word-major row — exactly
        nwords*4 bytes. The ledger totals must equal calls x row size,
        and agree with the bytes jax.device_get actually moved."""
        import jax
        import numpy as np

        m, items, size = mirror_fixture
        hashes = list(items)[:7]
        measured = []
        real_get = jax.device_get

        def counting_get(x):
            out = real_get(x)
            measured.append(np.asarray(out).nbytes)
            return out

        LEDGER.enable()
        LEDGER.reset()
        try:
            jax.device_get = counting_get
            for h in hashes:
                assert m.get(h) == items[h]
        finally:
            jax.device_get = real_get
        totals = LEDGER.totals()
        got = totals[("mirror.get", D2H)]
        cm = next(iter(m._classes.values()))
        assert got["count"] == len(hashes)
        assert got["bytes"] == len(hashes) * cm.nwords * 4
        # the ledger's claim vs what device_get actually hauled
        assert got["bytes"] == sum(measured)

    def test_mirror_admit_records_h2d(self):
        rng = random.Random(12)
        items = {}
        for _ in range(TILE):  # one full tile: no partial-tile tax
            enc = rng.randbytes(128)
            items[keccak256(enc)] = enc
        LEDGER.enable()
        m = DeviceNodeMirror(capacity_rows_per_class=TILE)
        m.admit(items)
        m.flush()
        totals = LEDGER.totals()
        admit = totals[("mirror.admit", H2D)]
        assert admit["count"] >= 1 and admit["bytes"] > 0
        # a full tile never pays the partial-tile claim round trip
        assert ("mirror.claim", D2H) not in totals


# ------------------------------------------------------- disabled mode


class TestDisabledMode:
    def test_disabled_replay_bit_exact(self):
        """Ledger on vs off: byte-identical chain heads (replay
        validates every window root, so any instrumentation-induced
        divergence would raise long before this assert)."""
        chain = _chain(8, 8)
        cfg = _pipeline_cfg()
        bc_off = _fresh_chain(cfg)
        ReplayDriver(bc_off, cfg).replay(chain)
        LEDGER.enable()
        bc_on = _fresh_chain(cfg)
        ReplayDriver(bc_on, cfg).replay(chain)
        LEDGER.disable()
        h_off = bc_off.get_header_by_number(8)
        h_on = bc_on.get_header_by_number(8)
        assert h_off.hash == h_on.hash == chain[-1].hash
        assert h_off.state_root == h_on.state_root

    def test_no_extra_device_syncs(self, mirror_fixture):
        """Enabling the ledger must not change HOW MANY device syncs a
        workload performs — nbytes comes from host-side attribute loads
        (arr.nbytes / precomputed sizes), never a device_get."""
        import jax

        m, items, _size = mirror_fixture
        hashes = list(items)[:5]
        counts = []
        real_get = jax.device_get

        def run():
            calls = [0]

            def counting_get(x):
                calls[0] += 1
                return real_get(x)

            jax.device_get = counting_get
            try:
                for h in hashes:
                    m.get(h)
                assert m.verify() == 0
            finally:
                jax.device_get = real_get
            counts.append(calls[0])

        run()  # disabled
        LEDGER.enable()
        run()  # enabled
        LEDGER.disable()
        assert counts[0] == counts[1] and counts[0] > 0


# ------------------------------------------------------ counter tracks


class TestCounterTracks:
    def _synthetic_ledger(self):
        LEDGER.enable()
        with LEDGER.context(window=1, phase="seal"):
            for i in range(3):
                LEDGER.record("fused.dispatch", H2D, 1000 * (i + 1),
                              duration=0.01)
        with LEDGER.context(window=1, phase="collect"):
            LEDGER.record("fused.collect", D2H, 512, duration=0.02)
            LEDGER.record("window.store", HOST, 4096, duration=0.001)

    def test_counter_tracks_valid_chrome_json(self):
        self._synthetic_ledger()
        doc = export.chrome_trace(spans=[])
        text = json.dumps(doc)  # must be JSON-serializable
        doc2 = json.loads(text)
        counters = [
            e for e in doc2["traceEvents"] if e.get("ph") == "C"
        ]
        names = {e["name"] for e in counters}
        assert "transfer bytes in flight" in names
        assert "transfer bytes (cumulative)" in names
        for e in counters:
            assert isinstance(e["ts"], (int, float))
            assert all(
                isinstance(v, (int, float)) for v in e["args"].values()
            )

    def test_in_flight_track_sums_to_zero(self):
        """Every +start edge has a matching -end edge: the last
        in-flight sample must be 0 on every direction."""
        self._synthetic_ledger()
        events = export.counter_tracks()
        flight = [
            e for e in events if e["name"] == "transfer bytes in flight"
        ]
        assert flight, "no in-flight samples"
        assert all(v == 0 for v in flight[-1]["args"].values())
        # host events never enter the in-flight track
        assert all(
            "host" not in e["args"] for e in flight
        )

    def test_cumulative_track_is_monotone_per_phase(self):
        self._synthetic_ledger()
        events = export.counter_tracks()
        cum = [
            e for e in events
            if e["name"] == "transfer bytes (cumulative)"
        ]
        last = {}
        for e in cum:
            for phase, v in e["args"].items():
                assert v >= last.get(phase, 0)
                last[phase] = v
        assert last.get("seal") == 6000
        assert last.get("collect") == 512

    def test_empty_ledger_adds_no_counter_events(self):
        assert export.counter_tracks() == []


# ------------------------------------------------------- window report


class TestWindowReportRPC:
    def test_not_found_shape(self):
        rep = recorder.window_report(999)
        assert rep == {
            "found": False, "number": 999, "ledgerEnabled": False,
        }

    def test_report_through_replay(self):
        """End-to-end: a pipelined replay with the ledger on produces a
        per-window phase x site record with store-write and block-save
        classification (host-hasher path: host-side classes only)."""
        chain = _chain(8, 8)
        cfg = _pipeline_cfg(w=2, depth=2)
        LEDGER.enable()
        ReplayDriver(_fresh_chain(cfg), cfg).replay(chain)
        LEDGER.disable()
        rep = recorder.window_report(3)
        assert rep["found"]
        assert rep["block_lo"] <= 3 <= rep["block_hi"]
        # host-hasher path: seal dispatches nothing to a device and
        # rootchecks resolve from the in-host mapping, so the ledger
        # events land in the spill (persist) and block-save (save)
        # stages of the staged collector
        assert {"persist", "save"} <= set(rep["phases"])
        cls = rep["collect_classes"]
        assert cls["store-write"]["bytes"] > 0
        assert cls["block-save"]["seconds"] > 0


# ----------------------------------------------- registry satellites


class TestRegistryScrapePass:
    def test_collector_pulled_once_per_pass(self):
        reg = MetricsRegistry()
        pulls = [0]

        def collector():
            pulls[0] += 1
            return [("khipu_test_gauge", "gauge", {}, 7)]

        reg.register_collector("t", collector)
        # one exposition pass = one pull, however many families read it
        text = reg.prometheus_text()
        assert "khipu_test_gauge 7" in text
        assert pulls[0] == 1
        reg.snapshot()
        assert pulls[0] == 2
        assert reg.collector_pulls == 2

    def test_scrape_pass_caches_and_restores(self):
        reg = MetricsRegistry()
        pulls = [0]
        reg.register_collector(
            "t", lambda: (
                pulls.__setitem__(0, pulls[0] + 1)
                or [("khipu_x", "gauge", {}, pulls[0])]
            )
        )
        with reg.scrape_pass():
            reg.snapshot()
            reg.prometheus_text()
            reg.snapshot()
        assert pulls[0] == 1, "one pull per pass, however many reads"
        reg.snapshot()  # pass closed: fresh pull
        assert pulls[0] == 2

    def test_histogram_bucket_override(self):
        reg = MetricsRegistry()
        h = reg.histogram("khipu_h", buckets=(0.1, 1.0))
        assert h.buckets == (0.1, 1.0)
        # re-register with different buckets before any observation:
        # override applies
        h2 = reg.histogram("khipu_h", buckets=(0.5, 2.0, 8.0))
        assert h2 is h and h.buckets == (0.5, 2.0, 8.0)
        h.observe(0.7)
        # after the first observation the shape is frozen
        reg.histogram("khipu_h", buckets=(9.0,))
        assert h.buckets == (0.5, 2.0, 8.0)
        text = reg.prometheus_text()
        assert 'le="2.0"' in text and 'le="+Inf"' in text


# ------------------------------------------------ sampling satellite


class TestTraceSampling:
    def test_trace_sampled_deterministic(self):
        tid = "00deadbeef"
        expect = int(tid, 16) % 10_000 < 250
        assert trace_sampled(tid, 250) == expect
        # same id, same answer, every process (no PYTHONHASHSEED)
        assert trace_sampled(tid, 250) == trace_sampled(tid, 250)
        assert trace_sampled(tid, 10_000) is True
        assert trace_sampled(tid, 0) is False
        assert trace_sampled("not-hex", 1) is True  # foreign id: keep

    def test_rate_distribution_rough(self):
        ids = [
            "%032x" % random.Random(i).getrandbits(128)
            for i in range(400)
        ]
        kept = sum(trace_sampled(t, 5000) for t in ids)
        assert 120 <= kept <= 280  # ~50% with slack

    def test_set_sample_rate_gates_enabled(self):
        t = tracer
        assert not t.enabled
        try:
            t.enable()
            t.set_sample_rate(10_000)
            assert t.enabled
            t.set_sample_rate(0)
            assert not t.enabled and t._on and not t.sampled
            t.set_sample_rate(10_000)
            assert t.enabled
        finally:
            t.disable()
            t.set_sample_rate(10_000)
            t.reset()

    def test_unsampled_tracer_records_nothing(self):
        t = tracer
        try:
            t.enable()
            t.set_sample_rate(0)
            with t.span("should.not.record"):
                pass
            assert t.recorded == 0
        finally:
            t.disable()
            t.set_sample_rate(10_000)
            t.reset()

    def test_apply_config_sets_rate(self):
        from khipu_tpu.observability.trace import apply_config

        t = tracer
        try:
            apply_config(
                ObservabilityConfig(enabled=True, sample_per_10k=7)
            )
            assert t._on and t.sample_per_10k == 7
            assert t.enabled == trace_sampled(t.trace_id, 7)
        finally:
            t.disable()
            t.set_sample_rate(10_000)
            t.reset()
