"""Flight-recorder tests (khipu_tpu/observability/): zero-cost-when-
off, ring-overflow accounting, cross-thread lifecycle linkage through
the deep pipeline, occupancy agreement with the live gauge, chrome
trace_event export, the bounded fused compile cache, and the
per-phase breakdown of a recorded replay."""

import dataclasses
import json
import threading

import pytest

from khipu_tpu.base.crypto.secp256k1 import (
    privkey_to_pubkey,
    pubkey_to_address,
)
from khipu_tpu.config import ObservabilityConfig, SyncConfig, fixture_config
from khipu_tpu.domain.block import Block
from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
from khipu_tpu.domain.transaction import Transaction, sign_transaction
from khipu_tpu.observability import export, recorder
from khipu_tpu.observability.profiler import LEDGER
from khipu_tpu.observability.trace import (
    Tracer,
    _NULL_SPAN,
    span,
    tracer,
)
from khipu_tpu.storage.storages import Storages
from khipu_tpu.sync.chain_builder import ChainBuilder
from khipu_tpu.sync.replay import ReplayDriver

CFG = fixture_config(chain_id=1)
KEYS = [(i + 1).to_bytes(32, "big") for i in range(4)]
ADDRS = [pubkey_to_address(privkey_to_pubkey(k)) for k in KEYS]
ETH = 10**18
MINER = b"\xaa" * 20


def tx(i, nonce, to, value):
    return sign_transaction(
        Transaction(nonce, 10**9, 21_000, to, value), KEYS[i], chain_id=1
    )


def pipeline_cfg(w=2, depth=2):
    return dataclasses.replace(
        CFG,
        sync=SyncConfig(
            parallel_tx=True, commit_window_blocks=w, pipeline_depth=depth
        ),
    )


N_BLOCKS = 20


def _transfer_chain(n_blocks, txs_per_block):
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
            txs.append(tx(i, nonces[i], ADDRS[(i + 1) % 4], 100 + n))
            nonces[i] += 1
        blocks.append(builder.add_block(txs, coinbase=MINER))
    return blocks


@pytest.fixture(scope="module")
def chain():
    """20 transfer blocks (windowed pipeline shape, no device needed).
    Big enough that per-window constant overhead (span record, queue
    hand-off) amortizes below the occupancy-agreement tolerance — at 5
    blocks x 3 txs the span-vs-gauge check sat on the tolerance edge
    and flaked under CI load."""
    return _transfer_chain(N_BLOCKS, 16)


def _fresh_chain(cfg):
    bc = Blockchain(Storages(), cfg)
    bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
    return bc


@pytest.fixture(scope="module")
def traced_replay(chain):
    """One pipelined replay with the recorder ON; yields
    (stats, spans snapshot). Module-scoped: several tests interrogate
    the same trace. Restores the disabled default afterwards."""
    tracer.enable()
    tracer.reset()
    try:
        cfg = pipeline_cfg(w=2, depth=2)
        bc = _fresh_chain(cfg)
        stats = ReplayDriver(bc, cfg).replay(chain)
        spans = tracer.snapshot()
        yield stats, spans
    finally:
        tracer.disable()
        tracer.reset()


def _recorded_replay(n_blocks, txs_per_block, chrome_out=None):
    """A fresh chain through wire RLP (replay pays sender recovery and
    parse, like a sync), replayed on the host hasher with the recorder
    and the transfer ledger ON and reset after the chain build, so
    spans and ledger cover exactly the replay. Returns (stats, spans);
    restores the disabled default."""
    blocks = [
        Block.decode(b.encode())
        for b in _transfer_chain(n_blocks, txs_per_block)
    ]
    cfg = pipeline_cfg(w=2, depth=2)
    bc = _fresh_chain(cfg)
    tracer.enable()
    LEDGER.enable()
    try:
        tracer.reset()
        LEDGER.reset()
        stats = ReplayDriver(bc, cfg, device_commit=False).replay(blocks)
        spans = tracer.snapshot()
        if chrome_out:
            export.dump_chrome_trace(chrome_out)
    finally:
        tracer.disable()
        LEDGER.disable()
    return stats, spans


# ------------------------------------------------------ disabled mode


class TestDisabledMode:
    def test_span_is_inert_singleton(self):
        assert not tracer.enabled
        s = span("anything", block=7)
        assert s is _NULL_SPAN
        assert s is span("other")  # shared: no allocation per call
        assert s.token is None
        before = tracer.recorded
        with s as inner:
            inner.set_tag("k", "v")  # all no-ops
        assert tracer.recorded == before
        assert tracer.snapshot() == []

    def test_disabled_replay_roots_bit_exact(self, chain):
        """A traced replay and an untraced replay of the same blocks
        land on byte-identical chain heads (replay validates every
        window root, so any tracing-induced divergence would raise)."""
        cfg = pipeline_cfg(w=2, depth=2)
        bc_off = _fresh_chain(cfg)
        ReplayDriver(bc_off, cfg).replay(chain)
        tracer.enable()
        tracer.reset()
        try:
            bc_on = _fresh_chain(cfg)
            ReplayDriver(bc_on, cfg).replay(chain)
        finally:
            tracer.disable()
            tracer.reset()
        h_off = bc_off.get_header_by_number(N_BLOCKS)
        h_on = bc_on.get_header_by_number(N_BLOCKS)
        assert h_off.hash == h_on.hash == chain[-1].hash
        assert h_off.state_root == h_on.state_root

    def test_config_enables_tracer(self, chain):
        """ObservabilityConfig(enabled=True) on the driver's config
        flips the process tracer on at construction."""
        cfg = dataclasses.replace(
            pipeline_cfg(),
            observability=ObservabilityConfig(
                enabled=True, ring_capacity=4096
            ),
        )
        assert not tracer.enabled
        try:
            ReplayDriver(_fresh_chain(cfg), cfg)
            assert tracer.enabled
            assert tracer.capacity == 4096
        finally:
            tracer.disable()
            tracer.reset()


# ------------------------------------------------------- ring buffer


class TestRing:
    def test_overflow_drop_oldest_and_counter(self):
        t = Tracer(capacity=8)
        t.enable()
        for i in range(20):
            t.event("e", i=i)
        assert t.recorded == 20
        assert t.dropped == 12
        kept = t.snapshot()
        assert [s.tags["i"] for s in kept] == list(range(12, 20))

    def test_reset_clears_drop_counter(self):
        t = Tracer(capacity=4)
        t.enable()
        for i in range(9):
            t.event("e", i=i)
        assert t.dropped == 5
        t.reset()
        assert t.dropped == 0 and t.snapshot() == []
        t.event("e", i=0)
        assert t.recorded == 1 and t.dropped == 0

    def test_concurrent_appends_lock_free(self):
        """8 writer threads into a small ring: no exception, exact
        recorded count, dropped = recorded - capacity."""
        t = Tracer(capacity=64)
        t.enable()

        def burst():
            for i in range(500):
                with t.span("w", i=i):
                    pass

        threads = [threading.Thread(target=burst) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert t.recorded == 4000
        assert t.dropped == 4000 - 64
        assert len(t.snapshot()) == 64


# ------------------------------------- lifecycle across the pipeline


class TestLifecycle:
    def test_cross_thread_parent_linkage(self, traced_replay):
        """window.collect / window.persist run on the collector thread
        but carry the DRIVER's seal-span token as parent — the explicit
        cross-thread edge thread-local nesting cannot express."""
        _, spans = traced_replay
        by_id = {s.sid: s for s in spans}
        collects = [s for s in spans if s.name == recorder.PHASE_COLLECT]
        assert collects, "no window.collect spans recorded"
        for c in collects:
            parent = by_id[c.parent]
            assert parent.name == recorder.PHASE_SEAL
            assert parent.tid != c.tid, "collect ran on the driver?"
            assert parent.tags["block_lo"] == c.tags["block_lo"]
        persists = [s for s in spans if s.name == recorder.PHASE_PERSIST]
        assert persists
        assert all(
            by_id[p.parent].name == recorder.PHASE_SEAL for p in persists
        )

    def test_no_nesting_violations(self, traced_replay):
        _, spans = traced_replay
        assert recorder.nesting_violations(spans) == []

    def test_trace_block_lifecycle_complete(self, traced_replay):
        """khipu_trace_block(n)'s record: every required phase present,
        in pipeline order, spanning both threads."""
        _, spans = traced_replay
        for n in (1, 3, 5):
            rec = recorder.lifecycle(spans, n)
            assert rec["complete"], rec["phaseOrder"]
            order = rec["phaseOrder"]
            assert order.index("window.build") < order.index("window.seal")
            assert (
                order.index("window.seal") < order.index("window.collect")
            )
            assert len(rec["threads"]) >= 2
        assert recorder.traced_blocks(spans) == list(range(1, N_BLOCKS + 1))

    def test_occupancy_agrees_with_gauge(self, traced_replay, chain):
        """Acceptance gate: occupancy recomputed FROM SPANS agrees with
        the live pipeline_occupancy gauge. The band allows for the
        systematic ~0.02 one-sided bias inherent to self-measurement
        (a span's clock cannot include its own record cost, the gauge's
        busy clock does); a real accounting bug diverges by tens of
        points. Scheduler preemption can still blow ANY single run's
        band on a loaded box, so disagreement re-measures on fresh
        replays — a real bug disagrees every time. (The module tracer
        stays enabled; the ring holds 64k spans, so the extra replays
        cannot overflow it for the later live-ring tests.)"""
        stats, spans = traced_replay
        if abs(recorder.occupancy(spans) - stats.pipeline_occupancy) < 0.08:
            return
        deltas = []
        for attempt in range(2):
            cfg = pipeline_cfg(w=2, depth=2)
            bc = _fresh_chain(cfg)
            already = len(tracer.snapshot())
            st = ReplayDriver(bc, cfg).replay(chain)
            sp = tracer.snapshot()[already:]  # this replay's spans only
            delta = abs(recorder.occupancy(sp) - st.pipeline_occupancy)
            if delta < 0.08:
                return
            deltas.append(delta)
        raise AssertionError(
            f"span-vs-gauge occupancy disagreed on 3/3 runs: {deltas}"
        )

    def test_phase_percentiles(self, traced_replay):
        _, spans = traced_replay
        pct = recorder.phase_percentiles(spans)
        for phase in recorder.REQUIRED_PHASES:
            assert pct[phase]["count"] > 0
            assert (
                pct[phase]["p50_s"]
                <= pct[phase]["p90_s"]
                <= pct[phase]["p99_s"]
            )


# ----------------------------------------------------------- export


class TestExport:
    def test_chrome_trace_json_valid(self, traced_replay, tmp_path):
        _, spans = traced_replay
        path = tmp_path / "trace.json"
        export.dump_chrome_trace(str(path), spans)
        doc = json.loads(path.read_text())
        events = doc["traceEvents"]
        assert events and doc["displayTimeUnit"] == "ms"
        # "C" = the counter tracks (export.counter_tracks) every dump
        # now carries — occupancy timeline + transfer-ledger bytes
        assert all(e["ph"] in ("M", "X", "i", "s", "f", "C") for e in events)
        cs = [e for e in events if e["ph"] == "C"]
        assert cs and all("ts" in e and e["args"] for e in cs)
        # every complete event carries microsecond ts + dur
        xs = [e for e in events if e["ph"] == "X"]
        assert xs and all(e["dur"] >= 0 and "ts" in e for e in xs)
        # cross-thread handoffs emit PAIRED flow events on distinct tids
        starts = {e["id"]: e for e in events if e["ph"] == "s"}
        finishes = [e for e in events if e["ph"] == "f"]
        assert finishes and starts
        for f in finishes:
            s = starts[f["id"]]
            assert s["tid"] != f["tid"]

    def test_snapshot_rpc_payload(self, traced_replay):
        """The khipu_traces RPC body while the ring still holds the
        replay's spans (module fixture keeps the tracer enabled)."""
        snap = export.snapshot()
        assert snap["enabled"] and snap["dropped"] == 0
        assert snap["blocks"] == list(range(1, N_BLOCKS + 1))
        assert set(recorder.REQUIRED_PHASES) <= set(
            snap["phasePercentiles"]
        )
        assert 0.0 <= snap["occupancy"] <= 1.0
        assert {"hits", "misses", "evictions"} <= set(
            snap["compileCache"]
        )
        block = export.trace_block(2)
        assert block["complete"]

    def test_eth_service_exposes_trace_rpcs(self):
        from khipu_tpu.jsonrpc.eth_service import EthService

        for name in ("khipu_traces", "khipu_trace_block",
                     "khipu_dump_chrome_trace", "khipu_metrics",
                     "khipu_metrics_text"):
            assert callable(getattr(EthService, name))


# ------------------------------------------------- fused compile cache


class TestCompileCache:
    def test_lru_eviction_bounded_and_logged(self):
        from khipu_tpu.trie.fused import _build_fused, compile_cache

        old_cap = compile_cache.stats()["capacity"]
        compile_cache.clear()
        recorder.compile_log.reset()
        try:
            compile_cache.set_capacity(2)
            sigs = [((1, 16, 4),), ((1, 32, 4),), ((1, 48, 4),)]
            for sig in sigs:
                _build_fused(sig, 8, True, 0)
            st = compile_cache.stats()
            assert st["size"] == 2 and st["capacity"] == 2
            log = recorder.compile_log.snapshot()
            assert log["misses"] == 3
            assert log["evictions"] == 1  # oldest signature evicted
            # the evicted signature misses again; the resident ones hit
            _build_fused(sigs[0], 8, True, 0)
            _build_fused(sigs[2], 8, True, 0)
            log = recorder.compile_log.snapshot()
            assert log["misses"] == 4 and log["hits"] == 1
            kinds = [e["kind"] for e in log["events"]]
            assert kinds.count("evict") == log["evictions"]
        finally:
            compile_cache.set_capacity(old_cap)
            compile_cache.clear()
            recorder.compile_log.reset()

    def test_set_capacity_evicts_down(self):
        from khipu_tpu.trie.fused import _build_fused, compile_cache

        old_cap = compile_cache.stats()["capacity"]
        compile_cache.clear()
        recorder.compile_log.reset()
        try:
            compile_cache.set_capacity(8)
            for n in (16, 32, 48, 64):
                _build_fused(((1, n, 4),), 8, True, 0)
            assert compile_cache.stats()["size"] == 4
            compile_cache.set_capacity(1)
            assert compile_cache.stats()["size"] == 1
            assert recorder.compile_log.snapshot()["evictions"] == 3
        finally:
            compile_cache.set_capacity(old_cap)
            compile_cache.clear()
            recorder.compile_log.reset()


# ------------------------------------------- recorded-replay breakdown


class TestPhaseBreakdown:
    def test_driver_phases_tile_the_wall(self):
        """The per-phase breakdown of a recorded replay: the driver's
        spans tile what ``ReplayStats.phases`` booked on the same
        thread around the same statements (within 10%), and both tile
        the driver's wall clock on the tiny fixture chain. Host hasher
        keeps this out of 'slow'."""
        # Spans and phases are read on the driver thread a few
        # statements apart, so a pre-emption lands in both and the
        # tight band holds beside busy neighbours. The wall also holds
        # what no phase books (the stage threads' start, the closing
        # joins), which stretches when the scheduler is slow to run
        # them: that comparison keeps a band that holds under load.
        # Both retry over up to 3 independent runs (the first replay of
        # a process pays its imports inside a span); a real accounting
        # bug disagrees on every run. The structural checks (phases
        # present, no drops, block count) assert unconditionally.
        for attempt in range(3):
            stats, spans = _recorded_replay(24, 8)
            assert not tracer.enabled  # helper restores the default
            assert stats.blocks == 24
            assert stats.seconds > 0
            breakdown = recorder.phase_breakdown(spans)
            for phase in recorder.REQUIRED_PHASES:
                assert phase in breakdown, breakdown
            assert tracer.dropped == 0
            driver_total = sum(
                v for k, v in breakdown.items()
                if k in recorder.DRIVER_PHASES
            )
            ph = stats.phases
            built = sum(
                ph[k] for k in ("senders", "validate", "execute", "commit")
            )
            build_span = breakdown[recorder.PHASE_BUILD]
            phases_ok = abs(build_span - built) <= 0.10 * build_span
            # the seal close-out and the stalls are a millisecond or
            # two in all: held to the whole, not each to itself
            booked = built + ph["seal"] + ph["collect"] + ph["save"]
            phases_ok = phases_ok and (
                abs(driver_total - booked) <= 0.10 * driver_total
            )
            wall_ok = (
                0.65 * stats.seconds <= driver_total
                <= 1.02 * stats.seconds
            )
            # same self-measurement bias allowance as
            # test_occupancy_agrees_with_gauge
            occ_ok = abs(
                recorder.occupancy(spans) - stats.pipeline_occupancy
            ) < 0.08
            if phases_ok and wall_ok and occ_ok:
                break
        else:
            raise AssertionError(
                "breakdown disagreed with the phases or the wall clock "
                f"on 3/3 runs: driver {driver_total} booked {booked} "
                f"wall {stats.seconds} {breakdown} {ph}"
            )


# ------------------------------------------------- unified registry


class TestRegistry:
    """khipu_tpu/observability/registry.py: the typed instrument set +
    pull collectors every legacy counter dict migrated onto."""

    def test_counter_gauge_histogram(self):
        from khipu_tpu.observability.registry import MetricsRegistry

        r = MetricsRegistry()
        c = r.counter("reqs_total", help="requests")
        c.inc()
        c.inc(4)
        assert c.value == 5
        g = r.gauge("depth")
        g.set(7)
        g.inc()
        g.dec(3)
        assert g.value == 5
        h = r.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.05, 5.0):
            h.observe(v)
        hv = h.value
        assert hv["count"] == 4
        assert abs(hv["sum"] - 5.105) < 1e-9
        # cumulative le semantics: 1 <=0.01, 3 <=0.1, 3 <=1.0 (+Inf=4)
        assert hv["buckets"] == {0.01: 1, 0.1: 3, 1.0: 3}

    def test_idempotent_reregister_and_kind_conflict(self):
        from khipu_tpu.observability.registry import MetricsRegistry

        r = MetricsRegistry()
        a = r.counter("x_total")
        assert r.counter("x_total") is a  # same (name, labels) -> same
        with pytest.raises(ValueError):
            r.gauge("x_total")  # kind flip is a bug, loudly
        # distinct labels are distinct instruments of one family
        ep1 = r.counter("y_total", labels={"endpoint": "a"})
        ep2 = r.counter("y_total", labels={"endpoint": "b"})
        assert ep1 is not ep2
        ep1.inc(2)
        snap = r.snapshot()
        assert snap["y_total"] == {'endpoint="a"': 2, 'endpoint="b"': 0}

    def test_gauge_group_shim_keeps_dict_call_sites(self):
        from khipu_tpu.observability.registry import MetricsRegistry

        r = MetricsRegistry()
        gg = r.gauge_group("khipu_pipe", {"in_flight": 0, "depth": 2})
        # the verbatim legacy write patterns
        gg["in_flight"] += 1
        gg["in_flight"] += 1
        gg["depth"] = 4
        assert gg["in_flight"] == 2
        assert "depth" in gg and len(gg) == 2
        assert dict(gg.items())["depth"] == 4
        # the values LIVE in the registry, served by name
        snap = r.snapshot()
        assert snap["khipu_pipe_in_flight"] == 2
        assert snap["khipu_pipe_depth"] == 4
        gg.reset()
        assert r.snapshot()["khipu_pipe_depth"] == 2

    def test_collector_replace_by_key_and_failure_dropped(self):
        from khipu_tpu.observability.registry import MetricsRegistry

        r = MetricsRegistry()
        r.register_collector(
            "j", lambda: [("d", "gauge", {}, 1)]
        )
        r.register_collector(
            "j", lambda: [("d", "gauge", {}, 9)]
        )  # newest owner of the state wins — no dead-entry leak
        def boom():
            raise RuntimeError("broken source")
        r.register_collector("bad", boom)
        snap = r.snapshot()
        assert snap["d"] == 9  # replaced, not duplicated
        assert "bad" not in snap  # failure dropped, scrape survived
        r.unregister_collector("j")
        assert "d" not in r.snapshot()

    def test_prometheus_text_exposition(self):
        from khipu_tpu.observability.registry import MetricsRegistry

        r = MetricsRegistry()
        r.counter("c_total", help="a counter").inc(3)
        r.gauge("g", labels={"shard": "a"}).set(1)
        r.gauge("g", labels={"shard": "b"}).set(2)
        h = r.histogram("h_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = r.prometheus_text()
        lines = text.splitlines()
        assert "# HELP c_total a counter" in lines
        assert "# TYPE c_total counter" in lines
        assert "c_total 3" in lines
        assert 'g{shard="a"} 1' in lines and 'g{shard="b"} 2' in lines
        assert lines.count("# TYPE g gauge") == 1  # ONE family header
        assert 'h_seconds_bucket{le="0.1"} 1' in lines
        assert 'h_seconds_bucket{le="1.0"} 2' in lines
        assert 'h_seconds_bucket{le="+Inf"} 2' in lines
        assert "h_seconds_count 2" in lines
        assert any(ln.startswith("h_seconds_sum ") for ln in lines)

    def test_exposition_escaping_hostile_values_round_trip(self):
        """Exposition-format escaping audit (the PR-10 satellite):
        backslash, double-quote, and newline in label VALUES and
        backslash/newline in HELP text must round-trip per format
        0.0.4 — a label value containing a literal ``\\n`` used to be
        able to smuggle a fake sample line into the document."""
        from khipu_tpu.observability.registry import MetricsRegistry

        hostile = 'a\\b"c\nd'
        r = MetricsRegistry()
        r.gauge("g", labels={"ep": hostile}).set(1)
        r.counter(
            "c_total", help='back\\slash and\nnewline "quoted"'
        ).inc(2)
        text = r.prometheus_text()
        lines = text.splitlines()
        # label value: \ -> \\, " -> \", newline -> \n (no raw newline
        # survives inside a sample line)
        assert 'g{ep="a\\\\b\\"c\\nd"} 1' in lines, lines
        # HELP: \ -> \\, newline -> \n, quotes stay verbatim
        assert (
            '# HELP c_total back\\\\slash and\\nnewline "quoted"'
            in lines
        ), lines
        # nothing hostile injected a bogus line: every line is a
        # comment or starts with a known family name
        for ln in lines:
            assert ln.startswith(("#", "g{", "c_total")), ln
        # and the escapes DECODE back to the original strings under
        # the format's unescape rules (round-trip, not just mangling)
        sample = next(ln for ln in lines if ln.startswith("g{"))
        raw = sample[len('g{ep="'):sample.rindex('"')]
        unescaped = (
            raw.replace("\\\\", "\x00")
            .replace('\\"', '"')
            .replace("\\n", "\n")
            .replace("\x00", "\\")
        )
        assert unescaped == hostile

    def test_process_registry_serves_migrated_families(self):
        """The legacy dicts (PIPELINE_GAUGES, WINDOW_GAUGES, chaos
        fault log, tracer ring health) all surface as families of THE
        process registry."""
        from khipu_tpu.observability.registry import REGISTRY
        import khipu_tpu.chaos.plan  # noqa: F401 - registers collector
        import khipu_tpu.ledger.window  # noqa: F401
        import khipu_tpu.sync.replay  # noqa: F401

        snap = REGISTRY.snapshot()
        for family in (
            "khipu_pipeline_depth",
            "khipu_pipeline_in_flight",
            "khipu_pipeline_windows_sealed",
            "khipu_window_fused_fallbacks",
            "khipu_chaos_faults_fired_total",
            "khipu_trace_spans_recorded_total",
            "khipu_trace_enabled",
        ):
            assert family in snap, family


# --------------------------------------------- snapshot fence (bugfix)


class TestSnapshotFence:
    def test_two_thread_snapshot_stress(self):
        """The copy-consistency fix: a reader snapshotting while a
        writer floods the ring must never raise (deque mutation mid-
        iteration) and every snapshot must be internally ordered —
        oldest first, tags monotonic — even across drop-oldest
        overflow."""
        t = Tracer(capacity=256)
        t.enable()
        stop = threading.Event()
        writer_err = []

        def writer():
            i = 0
            try:
                while not stop.is_set():
                    t.event("stress", i=i)
                    i += 1
            except Exception as e:  # pragma: no cover - the regression
                writer_err.append(e)

        th = threading.Thread(target=writer, name="stress-writer")
        th.start()
        try:
            snapshots = 0
            for _ in range(400):
                snap = t.snapshot()
                assert len(snap) <= t.capacity
                seq = [s.tags["i"] for s in snap if s.name == "stress"]
                # a torn copy would interleave out of order or dup
                assert seq == sorted(seq)
                assert len(set(seq)) == len(seq)
                snapshots += 1
        finally:
            stop.set()
            th.join(timeout=10)
        assert not writer_err
        assert snapshots == 400
        assert t.dropped > 0  # the stress actually wrapped the ring


# ----------------------------------- metrics superset + text agreement


class TestMetricsSuperset:
    @pytest.fixture(scope="class")
    def svc(self, chain):
        """EthService over a freshly replayed pipelined chain."""
        from khipu_tpu.jsonrpc.eth_service import EthService
        from khipu_tpu.txpool import PendingTransactionsPool

        cfg = pipeline_cfg(w=2, depth=2)
        bc = _fresh_chain(cfg)
        ReplayDriver(bc, cfg).replay(chain)
        return EthService(bc, cfg, PendingTransactionsPool())

    def test_khipu_metrics_is_key_compatible_superset(self, svc):
        """Every pre-registry key survives unchanged; the registry
        snapshot rides along as a new section and AGREES with the
        legacy values it mirrors."""
        out = svc.khipu_metrics()
        # legacy surface, verbatim
        assert out["bestBlockNumber"] == N_BLOCKS
        assert {"account", "storage", "evmcode"} <= set(out["stores"])
        for legacy in ("cacheHitRate", "cacheReadCount"):
            assert legacy in out["stores"]["account"]
        assert {
            "depth", "inFlight", "windowsSealed", "windowsCollected",
            "occupancy", "driverStallSeconds", "collectorBusySeconds",
            "collectorDeaths", "syncFallbackWindows",
        } <= set(out["pipeline"])
        assert {"fusedFallbacks", "journalDepth", "faults"} <= set(
            out["robustness"]
        )
        # the superset sections
        reg = out["registry"]
        assert reg["khipu_pipeline_windows_sealed"] == (
            out["pipeline"]["windowsSealed"]
        )
        assert reg["khipu_pipeline_depth"] == out["pipeline"]["depth"]
        assert reg["khipu_window_fused_fallbacks"] == (
            out["robustness"]["fusedFallbacks"]
        )
        assert reg["khipu_best_block_number"] == N_BLOCKS
        assert "phaseLatency" in out
        json.dumps(out)  # the whole document stays JSON-serializable

    def test_metrics_text_agrees_with_snapshot(self, svc):
        """khipu_metrics_text serves the SAME values the structured
        snapshot carries — one source of truth, two encodings."""
        out = svc.khipu_metrics()
        text = svc.khipu_metrics_text()
        lines = text.splitlines()
        assert f"khipu_best_block_number {N_BLOCKS}" in lines
        sealed = out["pipeline"]["windowsSealed"]
        assert f"khipu_pipeline_windows_sealed {sealed}" in lines
        pending = out["pendingTxs"]
        assert f"khipu_pending_txs {pending}" in lines


# ------------------------------------- recorded-replay registry smoke


class TestRecordedReplayRegistrySmoke:
    def test_chrome_valid_and_families_unique(self, tmp_path):
        """A recorded replay end to end — the chrome trace it writes is
        valid JSON with events, and EVERY family in the registry
        snapshot appears exactly once (one # TYPE line, >=1 sample
        line) in the khipu_metrics_text exposition."""
        import re

        from khipu_tpu.observability.registry import REGISTRY

        chrome = tmp_path / "replay_trace.json"
        stats, _spans = _recorded_replay(12, 4, chrome_out=str(chrome))
        assert stats.blocks == 12
        doc = json.loads(chrome.read_text())
        assert doc["traceEvents"]
        # phase histograms observed real latencies during the run
        assert sum(
            h.value["count"] for h in recorder.PHASE_HISTOGRAMS.values()
        ) > 0
        # the device-resident-commit pin: collect-phase d2h stays at
        # (at most) the 32 B/block rootcheck — the staged pipeline must
        # never pull node bytes back to host on the critical path. The
        # host-hasher smoke run moves ZERO device bytes in collect; the
        # device path is pinned <=256 B/block by TestDeviceMirrorCommit.
        assert LEDGER.blocks == 12
        by_phase = LEDGER.phase_bytes_per_block()
        assert by_phase.get("collect", {}).get("d2h", 0) <= 64, by_phase

        snap = REGISTRY.snapshot()
        assert snap
        text = REGISTRY.prometheus_text()
        lines = text.splitlines()
        type_lines = [ln for ln in lines if ln.startswith("# TYPE ")]
        # families and TYPE headers are in bijection
        assert len(type_lines) == len(snap)
        for name in snap:
            headers = [
                ln for ln in type_lines
                if ln.startswith(f"# TYPE {name} ")
            ]
            assert len(headers) == 1, name
            pat = re.compile(
                rf"^{re.escape(name)}(_bucket|_sum|_count)?(\{{| )"
            )
            assert any(
                pat.match(ln) for ln in lines if not ln.startswith("#")
            ), name
