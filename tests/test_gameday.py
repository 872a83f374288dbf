"""Gameday harness (khipu_tpu/chaos/scenario.py, invariants.py, the
merge/extend composition layer in chaos/plan.py — docs/gameday.md).

The headline: a pairwise hazard matrix — every ordered pair of hazard
kinds x seeds, 120 composed runs over the windowed replay pipeline —
where every run recovers to a BIT-EXACT chain and the sweep genuinely
exercises both outcomes (killed > 20 AND survived > 20), with the
schedule and the fired-fault log deterministic under one seed. Plus
the composition primitives that make it sound: ``merge_plans``
preserves per-(rule, site) RNG independence (merged schedule == union
of the parts'), the scenario engine fires milestone-keyed events
exactly once in order, watchdog trips carry the scenario event id as
a ``scenario`` label, every chaos seam in the tree is registered AND
exercised (meta-test), and the named reorg-during-rebalance
regression: a fork battle fencing the primary mid-stream must not
perturb the epoch fence — the ring lands at exactly the old or the
new epoch.
"""

import ast
import dataclasses
import threading
from pathlib import Path

import pytest

from khipu_tpu.base.crypto.keccak import keccak256
from khipu_tpu.base.crypto.secp256k1 import (
    privkey_to_pubkey,
    pubkey_to_address,
)
from khipu_tpu.chaos import (
    KNOWN_SEAMS,
    FaultPlan,
    FaultRule,
    InjectedDeath,
    InjectedFault,
    InvariantReport,
    InvariantResult,
    Scenario,
    ScenarioEngine,
    ScenarioEvent,
    active,
    check_epoch,
    check_roots_bit_exact,
    clear_current_event,
    current_event_id,
    derive,
    gameday_stats,
    known_seam,
    merge_plans,
    quiet_deaths,
    record_run,
)
from khipu_tpu.cluster import Rebalancer, ShardedNodeClient
from khipu_tpu.cluster.ring import _point
from khipu_tpu.config import SyncConfig, fixture_config
from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
from khipu_tpu.domain.transaction import Transaction, sign_transaction
from khipu_tpu.observability.registry import MetricsRegistry
from khipu_tpu.observability.telemetry import TelemetryConfig, Watchdog
from khipu_tpu.storage.datasource import (
    MemoryBlockDataSource,
    MemoryKeyValueDataSource,
    MemoryNodeDataSource,
)
from khipu_tpu.storage.storages import Storages
from khipu_tpu.sync.chain_builder import ChainBuilder
from khipu_tpu.sync.reorg import ReorgManager
from khipu_tpu.sync.replay import CollectorDied, ReplayDriver, ReplayStats

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _no_sticky_scenario():
    """current_event_id() is sticky by design (the watchdog may trip
    after the hazard); don't let it leak into other test modules'
    watchdog assertions."""
    yield
    clear_current_event()


REPO = Path(__file__).resolve().parents[1]
CFG = dataclasses.replace(
    fixture_config(chain_id=1),
    sync=SyncConfig(commit_window_blocks=1, parallel_tx=False),
)
KEYS = [(i + 1).to_bytes(32, "big") for i in range(4)]
ADDRS = [pubkey_to_address(privkey_to_pubkey(k)) for k in KEYS]
ETH = 10**18
ALLOC = {a: 1000 * ETH for a in ADDRS}
GEN = GenesisSpec(alloc=ALLOC)
MINER_A = b"\xaa" * 20
MINER_B = b"\xbb" * 20
N_BLOCKS = 12

_noop = lambda s: None  # noqa: E731 - plan sleep stub


def _tx(i, nonce, to, value):
    return sign_transaction(
        Transaction(nonce, 10**9, 21_000, to, value), KEYS[i], chain_id=1
    )


def _build(n, diverge_at=None, value_off=0):
    """Consensus-true transfer chain; from ``diverge_at`` the coinbase
    and tx values flip (test_reorg's fork-building idiom), so the
    suffix is a genuinely different branch."""
    builder = ChainBuilder(Blockchain(Storages(), CFG), CFG, GEN)
    blocks, nonces = [], [0, 0, 0, 0]
    for k in range(n):
        i = k % 4
        diverged = diverge_at is not None and k >= diverge_at
        blocks.append(builder.add_block(
            [_tx(i, nonces[i], ADDRS[(i + 1) % 4],
                 100 + k + (value_off if diverged else 0))],
            coinbase=MINER_B if diverged else MINER_A,
            timestamp=10 * (k + 1),
        ))
        nonces[i] += 1
    return builder.blockchain, blocks


@pytest.fixture(scope="module")
def chain():
    """12 transfer blocks for the matrix — enough window boundaries
    for a depth-2 pipeline to be mid-flight whenever a hazard lands."""
    return _build(N_BLOCKS)[1]


@pytest.fixture(scope="module")
def reference(chain):
    """Uninterrupted serial replay — the bit-exact oracle."""
    bc = _fresh(CFG)
    ReplayDriver(bc, CFG).replay(chain)
    return bc


@pytest.fixture(scope="module")
def fork_chains():
    """(base 8, fork 10 diverging at 5) for the reorg regression."""
    _, base = _build(8)
    fork_bc, fork = _build(10, diverge_at=5, value_off=1000)
    return {"base": base, "fork": fork, "fork_bc": fork_bc}


def _fresh(cfg):
    bc = Blockchain(Storages(), cfg)
    bc.load_genesis(GEN)
    return bc


def _windowed_cfg():
    # adaptive_commit off so the collector seams sit on the configured
    # path (the test_chaos sweep convention); degrade off so a stage
    # death surfaces as CollectorDied and the run is counted "killed"
    return dataclasses.replace(
        CFG,
        sync=SyncConfig(
            parallel_tx=False,
            commit_window_blocks=2,
            pipeline_depth=2,
            degrade_on_collector_death=False,
            collector_join_timeout=5.0,
            adaptive_commit=False,
        ),
    )


# --------------------------------------------------------- merge_plans


class TestMergePlans:
    """Satellite: composition preserves per-(rule, site) RNG
    independence — the property the gameday's single shared plan
    stands on."""

    @staticmethod
    def _drive(plan):
        for i in range(300):
            plan.fire("storage.kv.get")
            plan.fire("kesque.append" if i % 3 else "kesque.roll")
        return {(s, h, k) for (s, h, k, _i) in plan.fired}

    @staticmethod
    def _part_a():
        return FaultPlan(seed=7, rules=[
            FaultRule("storage.kv.get", "latency", prob=0.31,
                      latency_s=0.0),
            FaultRule("kesque.*", "latency", prob=0.2, latency_s=0.0),
        ], sleep=_noop)

    @staticmethod
    def _part_b():
        return FaultPlan(seed=9, rules=[
            FaultRule("storage.kv.get", "latency", prob=0.4,
                      latency_s=0.0),
        ], sleep=_noop)

    def test_merged_schedule_is_union_of_parts(self):
        union = self._drive(self._part_a()) | self._drive(self._part_b())
        merged = merge_plans(self._part_a(), self._part_b())
        assert self._drive(merged) == union
        # and both parts genuinely contributed
        assert self._drive(self._part_a()) < union

    def test_naive_concat_aliases_the_second_plans_streams(self):
        """The bug merge_plans exists to fix: concatenating rules under
        one seed re-keys part B's RNG streams, silently changing which
        hits B fires on."""
        union = self._drive(self._part_a()) | self._drive(self._part_b())
        naive = FaultPlan(
            seed=7,
            rules=list(self._part_a().rules) + list(self._part_b().rules),
            sleep=_noop,
        )
        assert self._drive(naive) != union

    def test_extend_draws_identically_to_upfront_construction(self):
        rules = [
            FaultRule("storage.kv.get", "latency", prob=0.3,
                      latency_s=0.0),
            FaultRule("kesque.append", "latency", prob=0.5,
                      latency_s=0.0),
        ]
        up = FaultPlan(seed=5, rules=list(rules), sleep=_noop)
        ex = FaultPlan(seed=5, rules=rules[:1], sleep=_noop)
        ex.extend(rules[1:])
        self._drive(up)
        self._drive(ex)
        assert up.fired == ex.fired

    def test_merged_plan_extends_under_first_parts_key_sequence(self):
        """Rules armed onto a merged plan (what the scenario engine
        does mid-run) draw exactly as if they had been appended to the
        FIRST part — merging never shifts the engine's hazards."""
        late = FaultRule("ledger.batch", "latency", prob=0.5,
                         latency_s=0.0)

        def drive(plan, idx):
            for _ in range(200):
                plan.fire("ledger.batch")
            return {(s, h) for (s, h, _k, i) in plan.fired if i == idx}

        merged = merge_plans(self._part_a(), self._part_b())
        merged.extend([late])
        solo = FaultPlan(
            seed=7, rules=list(self._part_a().rules) + [late], sleep=_noop
        )
        assert drive(merged, len(merged.rules) - 1) == drive(
            solo, len(solo.rules) - 1
        )


# ----------------------------------------------------- scenario engine


class TestScenarioEngine:
    def teardown_method(self):
        clear_current_event()

    def test_event_validation(self):
        with pytest.raises(ValueError, match="unknown scenario"):
            ScenarioEvent("e", 0, "explode", "storage.kv.get")
        with pytest.raises(ValueError, match="needs a site"):
            ScenarioEvent("e", 0, "die")
        with pytest.raises(ValueError, match="not a registered"):
            ScenarioEvent("e", 0, "die", "made.up.seam")
        with pytest.raises(ValueError, match="negative"):
            ScenarioEvent("e", -1, "join")
        with pytest.raises(ValueError, match="duplicate"):
            Scenario(0, [ScenarioEvent("e", 0, "join"),
                         ScenarioEvent("e", 1, "fork")])

    def test_schedule_is_height_sorted_and_insertion_stable(self):
        sc = Scenario(3, [
            ScenarioEvent("late", 9, "die", "collector.persist"),
            ScenarioEvent("first", 2, "join"),
            ScenarioEvent("also-first", 2, "fork"),
        ])
        assert [e[0] for e in sc.schedule()] == [
            "first", "also-first", "late",
        ]
        # pure function of construction inputs: rebuild == rebuild
        again = Scenario(3, [
            ScenarioEvent("late", 9, "die", "collector.persist"),
            ScenarioEvent("first", 2, "join"),
            ScenarioEvent("also-first", 2, "fork"),
        ])
        assert sc.schedule() == again.schedule()

    def test_seam_event_arms_after_current_hit_count(self):
        plan = FaultPlan(seed=0, sleep=_noop)
        for _ in range(3):
            plan.fire("storage.node.get")
        engine = ScenarioEngine(Scenario(0, [
            ScenarioEvent("kill", 4, "die", "storage.node.get",
                          {"after_hits": 1}),
        ]), plan)
        assert engine.step(3) == []  # not due yet
        fired = engine.step(4)
        assert [e.event_id for e in fired] == ["kill"]
        assert engine.done() and engine.remaining() == 0
        plan.fire("storage.node.get")  # hit 4: inside the grace window
        with pytest.raises(InjectedDeath):
            plan.fire("storage.node.get")  # hit 5: armed rule fires
        assert engine.step(9) == []  # an event fires exactly once

    def test_hooks_receive_event_and_missing_hook_is_rejected(self):
        got = []
        engine = ScenarioEngine(
            Scenario(0, [ScenarioEvent("f", 1, "fork",
                                       params={"ancestor": 5})]),
            FaultPlan(seed=0, sleep=_noop),
            hooks={"fork": got.append},
        )
        engine.step(1)
        assert got[0].event_id == "f" and got[0].params["ancestor"] == 5
        with pytest.raises(ValueError, match="no hook registered"):
            ScenarioEngine(
                Scenario(0, [ScenarioEvent("j", 0, "join")]),
                FaultPlan(seed=0, sleep=_noop),
            )

    def test_current_event_id_is_sticky_until_cleared(self):
        plan = FaultPlan(seed=0, sleep=_noop)
        engine = ScenarioEngine(Scenario(0, [
            ScenarioEvent("a", 1, "latency", "storage.kv.get",
                          {"latency_s": 0.0}),
            ScenarioEvent("b", 2, "latency", "storage.kv.get",
                          {"latency_s": 0.0}),
        ]), plan)
        engine.step(1)
        assert current_event_id() == "a"
        engine.step(2)
        assert current_event_id() == "b"  # last fired wins
        clear_current_event()
        assert current_event_id() is None
        assert engine.events_by_kind == {"latency": 2}

    def test_quiet_deaths_swallows_only_injected_death(self):
        seen = []
        prev = threading.excepthook
        threading.excepthook = lambda args: seen.append(args.exc_type)
        try:
            with quiet_deaths():
                def die():
                    raise InjectedDeath("fail-stop")

                def boom():
                    raise ValueError("real bug")

                for target in (die, boom):
                    t = threading.Thread(target=target)
                    t.start()
                    t.join()
            assert seen == [ValueError]
            # the previous hook is restored on exit
            assert threading.excepthook is not prev
        finally:
            threading.excepthook = prev


# ------------------------------------------------- invariants plumbing


class TestInvariantReport:
    def test_report_collects_failures_and_raises(self):
        report = InvariantReport()
        report.add(InvariantResult("ryw", True))
        bad = report.add(InvariantResult("roots", False, "hash mismatch"))
        assert not bad and not report.ok
        assert report.failures == [bad]
        assert report.summary() == {"ryw": True, "roots": False}
        with pytest.raises(AssertionError, match="hash mismatch"):
            report.raise_if_failed()

    def test_record_run_feeds_registry_families(self):
        before = gameday_stats().runs
        report = InvariantReport()
        report.add(InvariantResult("roots", True))
        record_run({"die": 2}, report)
        stats = gameday_stats()
        assert stats.runs == before + 1
        names = {s[0] for s in stats.samples()}
        assert {
            "khipu_gameday_runs_total",
            "khipu_gameday_events_total",
            "khipu_gameday_invariant_checks_total",
            "khipu_gameday_invariant_failures_total",
            "khipu_gameday_last_p99_ms",
        } <= names


# ------------------------------------------- watchdog scenario label


class TestWatchdogScenarioLabel:
    """Satellite: a watchdog trip during a gameday run is attributable
    to the hazard that preceded it — khipu_watchdog_trips_total grows
    a scenario="<event id>" labeled sample, while the unlabeled
    per-kind family (what dashboards and the bench smokes pin) stays
    byte-identical in shape."""

    def teardown_method(self):
        clear_current_event()

    def test_trip_carries_scenario_event_id_label(self):
        depth = {"d": 0}
        dog = Watchdog(
            config=TelemetryConfig(enabled=True, journal_runaway_depth=2),
            journal_depth=lambda: depth["d"],
            registry=MetricsRegistry(),
        )
        engine = ScenarioEngine(Scenario(1, [
            ScenarioEvent("gd.slow", 0, "latency", "storage.node.get",
                          {"latency_s": 0.0}),
        ]), FaultPlan(seed=1, sleep=_noop))
        engine.step(0)
        assert current_event_id() == "gd.slow"
        depth["d"] = 5
        assert dog.check_once(now=1.0) == ["journal_runaway"]
        kind, tags = dog.events[-1]
        assert kind == "journal_runaway"
        assert tags["scenario"] == "gd.slow"
        assert dog.scenario_trips[("journal_runaway", "gd.slow")] == 1

        text = dog.registry.prometheus_text()
        # base per-kind sample unchanged (the smoke-pinned shape)...
        assert 'khipu_watchdog_trips_total{kind="journal_runaway"} 1' \
            in text
        # ...plus the appended scenario-labeled sample
        labeled = [
            line for line in text.splitlines()
            if line.startswith("khipu_watchdog_trips_total{")
            and 'scenario="gd.slow"' in line
        ]
        assert len(labeled) == 1
        assert 'kind="journal_runaway"' in labeled[0]
        assert labeled[0].endswith(" 1")

    def test_trip_outside_a_scenario_stays_unlabeled(self):
        clear_current_event()
        depth = {"d": 9}
        dog = Watchdog(
            config=TelemetryConfig(enabled=True, journal_runaway_depth=2),
            journal_depth=lambda: depth["d"],
            registry=MetricsRegistry(),
        )
        assert dog.check_once(now=1.0) == ["journal_runaway"]
        assert dog.scenario_trips == {}
        kind, tags = dog.events[-1]
        assert kind == "journal_runaway" and "scenario" not in tags
        assert "scenario=" not in dog.registry.prometheus_text()


# ------------------------------------------------------ seam audit


def _seam_call_sites():
    """AST-walk every ``fault_point``/``fault_value`` call in
    khipu_tpu/: literal sites exactly, f-string sites by their literal
    prefix. A non-literal site name is itself a failure — the registry
    audit cannot see through one."""
    exact, prefixes = set(), set()
    for path in sorted((REPO / "khipu_tpu").rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text(encoding="utf-8"))):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = fn.id if isinstance(fn, ast.Name) else getattr(
                fn, "attr", ""
            )
            if name not in ("fault_point", "fault_value"):
                continue
            if not node.args:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                exact.add(arg.value)
            elif isinstance(arg, ast.JoinedStr) and arg.values:
                head = arg.values[0]
                prefix = (
                    head.value
                    if isinstance(head, ast.Constant)
                    and isinstance(head.value, str) else ""
                )
                assert prefix, (
                    f"{path}: parameterised seam with no literal prefix"
                )
                prefixes.add(prefix)
            else:
                raise AssertionError(
                    f"{path}: seam name is not a (f-)string literal"
                )
    return exact, prefixes


class TestSeamAudit:
    """Satellite meta-test: a chaos seam cannot ship unregistered or
    unexercised. The registry (chaos.plan.KNOWN_SEAMS) is the single
    source of truth the scenario DSL validates against, so a hole here
    is a hazard a gameday could never script."""

    def test_every_call_site_is_registered(self):
        exact, prefixes = _seam_call_sites()
        assert exact, "seam walk found nothing — the audit is broken"
        unregistered = sorted(s for s in exact if not known_seam(s))
        assert not unregistered, (
            f"fault seams missing from KNOWN_SEAMS: {unregistered}"
        )
        for prefix in sorted(prefixes):
            assert known_seam(prefix + "x"), (
                f"parameterised seam {prefix}* has no wildcard entry "
                "in KNOWN_SEAMS"
            )

    def test_registry_has_no_stale_entries(self):
        exact, prefixes = _seam_call_sites()
        for seam in sorted(KNOWN_SEAMS):
            if seam.endswith("*"):
                stem = seam[:-1]
                assert any(
                    p.startswith(stem) or stem.startswith(p)
                    for p in prefixes
                ), f"KNOWN_SEAMS entry {seam} matches no call site"
            else:
                assert seam in exact, (
                    f"KNOWN_SEAMS entry {seam} matches no call site"
                )

    def test_every_seam_is_exercised_by_some_test(self):
        corpus = (REPO / "scenarios.py").read_text(encoding="utf-8")
        corpus += "".join(
            p.read_text(encoding="utf-8")
            for p in sorted((REPO / "tests").glob("*.py"))
        )
        unexercised = sorted(
            seam for seam in KNOWN_SEAMS
            if (seam[:-1] if seam.endswith("*") else seam) not in corpus
        )
        assert not unexercised, (
            f"chaos seams referenced by no test or scenario: {unexercised}"
        )


# --------------------------------------- previously-unexercised seams


class _FakeShard:
    """Minimal BridgeClient stand-in (tests/test_cluster.py shape)."""

    def __init__(self):
        self.store = {}

    def get_node_data(self, hashes):
        return {h: self.store[h] for h in hashes if h in self.store}

    def put_node_data(self, nodes):
        self.store.update(nodes)
        return len(nodes)

    def stream_node_data(self, ranges, cursor, count):
        snap = dict(self.store)
        keys = sorted(
            k for k in snap
            if cursor < k and any(lo <= _point(k) < hi
                                  for lo, hi in ranges)
        )
        page = keys[:count]
        done = len(keys) <= count
        nxt = page[-1] if page else bytes(cursor)
        return done, nxt, [(k, snap[k]) for k in page]

    def ping(self, payload=b""):
        return payload

    def close(self):
        pass


def _make_cluster(members, extra=(), **kwargs):
    shards = {ep: _FakeShard() for ep in (*members, *extra)}
    kwargs.setdefault("replication", 2)
    kwargs.setdefault("vnodes", 8)
    kwargs.setdefault("max_retries", 1)
    kwargs.setdefault("sleep", _noop)
    cl = ShardedNodeClient(
        list(members), channel_factory=lambda ep: shards[ep], **kwargs
    )
    return cl, shards


class TestSeamCoverage:
    """Targeted exercises for the seams the audit found dark: the
    storage put/get seams, the replicate fan-out, and the raw segment
    chunk data seam."""

    def test_kv_put_raise_is_fail_stop(self):
        src = MemoryKeyValueDataSource()
        with active(FaultPlan(seed=3, rules=[
                FaultRule("storage.kv.put", "raise", times=1)])):
            with pytest.raises(InjectedFault):
                src.update([], {b"k1": b"v1"})
            assert src.get(b"k1") is None  # nothing half-applied
            src.update([], {b"k1": b"v1"})  # fire budget spent: lands
        assert src.get(b"k1") == b"v1"

    def test_node_put_die_is_fail_stop(self):
        src = MemoryNodeDataSource()
        value = b"trie node rlp bytes"
        key = keccak256(value)
        with active(FaultPlan(seed=5, rules=[
                FaultRule("storage.node.put", "die", times=1)])):
            with pytest.raises(InjectedDeath):
                src.update([], {key: value})
            assert src.get(key) is None
        src.update([], {key: value})
        assert src.get(key) == value

    def test_block_get_latency_delays_without_corrupting(self):
        slept = []
        src = MemoryBlockDataSource()
        src.put(3, b"block three rlp")
        with active(FaultPlan(seed=4, rules=[
                FaultRule("storage.block.get", "latency",
                          latency_s=0.25)], sleep=slept.append)):
            assert src.get(3) == b"block three rlp"
        assert slept == [0.25]
        assert src.best_block_number == 3

    def test_replicate_raise_is_retryable_and_places_all(self):
        cl, shards = _make_cluster(["s0", "s1", "s2"])
        data = {
            keccak256(v): v
            for v in (b"gameday replicate %d" % i for i in range(40))
        }
        try:
            with active(FaultPlan(seed=2, rules=[
                    FaultRule("cluster.replicate", "raise", times=1)])):
                with pytest.raises(InjectedFault):
                    cl.replicate(data)
                # fail-stop at the seam: no shard saw a partial batch
                assert all(not s.store for s in shards.values())
                placed = cl.replicate(data)
            assert placed == 2 * len(data)  # replication=2
            assert cl.fetch(list(data)) == data
        finally:
            cl.close()

    def test_client_call_seam_fires_before_the_wire(self):
        """``bridge.call.*`` sits at the top of the client's ``_call``
        — a raise rule models an unreachable shard without a network:
        no server listens here, yet the seam fires first."""
        pytest.importorskip("grpc")
        from khipu_tpu.bridge import BridgeClient

        client = BridgeClient("127.0.0.1:9", deadline=0.5)
        try:
            with active(FaultPlan(seed=9, rules=[
                    FaultRule("bridge.call.Ping", "raise",
                              times=None)])):
                with pytest.raises(InjectedFault):
                    client.ping()
        finally:
            client.close()

    def test_compact_raise_leaves_store_serving(self, tmp_path):
        st = Storages(engine="kesque", data_dir=str(tmp_path))
        bc = Blockchain(st, CFG)
        bc.load_genesis(GEN)
        root = bc.get_header_by_number(0).state_root
        store = st.kesque_engine.store("account")
        oracle = {k: store.get(k) for k in store.keys()}
        assert oracle
        try:
            with active(FaultPlan(seed=6, rules=[
                    FaultRule("kesque.compact", "raise", times=1)])):
                with pytest.raises(InjectedFault):
                    st.kesque_engine.compact(root)
                # fail-stop before the freeze: every record intact
                for k, v in oracle.items():
                    assert store.get(k) == v
                report = st.kesque_engine.compact(root)
            assert report.corrupt == 0
            for k in store.keys():
                assert store.get(k) == oracle[k]
        finally:
            st.stop()

    def test_ingest_raise_then_retry_completes(self, tmp_path):
        """``kesque.ingest`` fires per fetched chunk inside the pull
        workers; a raise surfaces through the pool and the retry
        re-ships the whole manifest (nothing landed before the seam)."""
        from khipu_tpu.sync.fast_sync import segment_snapshot_ingest

        src = Storages(engine="kesque", data_dir=str(tmp_path / "src"))
        dst = Storages(engine="kesque", data_dir=str(tmp_path / "dst"))
        data = {
            keccak256(v): v
            for v in (b"gameday ingest node %d" % i for i in range(64))
        }
        src.kesque_engine.store("account").append_batch([], data)
        eng = src.kesque_engine
        try:
            with active(FaultPlan(seed=8, rules=[
                    FaultRule("kesque.ingest", "raise", times=1)])):
                with pytest.raises(InjectedFault):
                    segment_snapshot_ingest(
                        dst, eng.list_segments, eng.read_chunk,
                        workers=1,
                    )
                report = segment_snapshot_ingest(
                    dst, eng.list_segments, eng.read_chunk, workers=1,
                )
            assert report.records == len(data)
            assert report.corrupt_frames == 0
            dstore = dst.kesque_engine.store("account")
            for k, v in data.items():
                assert dstore.get(k) == v
        finally:
            src.stop()
            dst.stop()

    def test_corrupt_segment_chunk_dies_at_receiver_scan(self, tmp_path):
        """``bridge.segment.raw`` corrupt seam end to end over a real
        gRPC loopback: the per-frame CRC fence means a receiver that
        scans before admitting (the rebalancer/ingest contract) rejects
        ANY bit-flipped chunk."""
        pytest.importorskip("grpc")
        from khipu_tpu.bridge import BridgeClient, BridgeServer
        from khipu_tpu.storage.segment import scan_frames

        st = Storages(engine="kesque", data_dir=str(tmp_path))
        data = {
            keccak256(v): v
            for v in (b"gameday segment node %d" % i for i in range(64))
        }
        st.kesque_engine.store("account").append_batch([], data)
        server = BridgeServer(Blockchain(st, CFG), CFG)
        port = server.start(port=0)
        client = BridgeClient(f"127.0.0.1:{port}", deadline=5.0)
        try:
            name, manifest = client.engine_info()
            assert name == "kesque" and manifest
            topic, seq, _size = manifest[0]
            raw, _nxt, _done = client.stream_segments(topic, seq, 0,
                                                      1 << 20)
            frames, end = scan_frames(raw)
            assert frames and end == len(raw)  # clean: whole frames
            with active(FaultPlan(seed=21, rules=[
                    FaultRule("bridge.segment.raw", "corrupt")])):
                bad, _n, _d = client.stream_segments(topic, seq, 0,
                                                     1 << 20)
            assert bad != raw  # the data seam really fired
            _frames, end_bad = scan_frames(bad)
            assert end_bad != len(bad)  # CRC fence: chunk rejected
        finally:
            client.close()
            server.stop()


# ------------------------------------- reorg-during-rebalance fence


class TestReorgDuringRebalance:
    def test_reorg_fences_while_rebalancer_streams(self, fork_chains):
        """Named regression for the gameday's nastiest pairing: a fork
        battle retracting served blocks WHILE a shard join streams.
        The reorg's fence (journal recovery pass, overlay
        invalidation) must not perturb the epoch fence — the join
        stays in flight against the committed epoch, writes made
        mid-switch land in BOTH epochs' owners, and the ring commits
        at exactly old+1 afterwards."""
        cl, shards = _make_cluster(["s0", "s1"], extra=("s2",))
        rb = Rebalancer(cl, batch=32)
        data = {
            keccak256(v): v
            for v in (b"reorg x rebalance %d" % i for i in range(300))
        }
        cl.replicate(data)
        e0 = cl.ring.epoch

        gate = threading.Event()
        streaming = threading.Event()

        def slow_stream(self, ranges, cursor, count,
                        _orig=_FakeShard.stream_node_data):
            streaming.set()
            assert gate.wait(30), "test gate never released"
            return _orig(self, ranges, cursor, count)

        for ep in ("s0", "s1"):  # either source replica may serve
            shards[ep].stream_node_data = slow_stream.__get__(shards[ep])

        join_box = {}

        def run_join():
            try:
                join_box["streamed"] = rb.join("s2")
            except BaseException as e:  # surfaced by the asserts below
                join_box["error"] = e

        join_t = threading.Thread(target=run_join, daemon=True)
        join_t.start()
        try:
            assert streaming.wait(30), "join never reached the stream"
            assert rb.in_transition and cl.ring.epoch == e0

            # the fork battle, mid-stream: an 8-block primary adopts
            # the heavier 10-block branch diverging at 5
            bc = _fresh(CFG)
            driver = ReplayDriver(bc, CFG)
            stats = ReplayStats()
            for b in fork_chains["base"]:
                driver._execute_and_insert(b, stats)
            mgr = ReorgManager(bc, CFG, driver=driver)
            adopted = mgr.switch(5, fork_chains["fork"][5:])
            assert adopted == 5
            assert bc.best_block_number == 10
            assert check_roots_bit_exact(bc, fork_chains["fork_bc"]).ok

            # the switch (and its fence/recovery pass) left the shard
            # plane's epoch fence alone: still the committed epoch,
            # still streaming
            assert cl.ring.epoch == e0 and rb.in_transition

            # a write landed mid-switch goes to BOTH epochs' owners
            extra_val = b"written during the fork battle"
            extra_key = keccak256(extra_val)
            cl.replicate({extra_key: extra_val})
        finally:
            gate.set()
        join_t.join(timeout=60)
        assert not join_t.is_alive(), "join wedged behind the reorg"
        assert "error" not in join_box, join_box.get("error")
        assert join_box["streamed"] > 0

        # exactly-old-or-new, landed at new
        assert check_epoch(rb, e0, e0 + 1).ok
        assert cl.ring.epoch == e0 + 1
        assert set(cl.ring.members) == {"s0", "s1", "s2"}
        # every key (including the mid-switch write) still fetchable
        want = dict(data)
        want[extra_key] = extra_val
        keys = sorted(want)
        got = {}
        for i in range(0, len(keys), 128):
            got.update(cl.fetch(keys[i:i + 128]))
        assert got == want
        cl.close()


# -------------------------------------------- pairwise hazard matrix


# Hazard vocabulary for the matrix: four seeded deaths at distinct
# collector stage boundaries (each is a different crash window of the
# windowed pipeline) plus a benign slow-disk hazard, so pairs compose
# fail-stop x fail-stop AND fail-stop x gray-failure.
HAZARDS = {
    "seal_die": ("collector.seal", "die"),
    "pack_die": ("collector.pack", "die"),
    "persist_die": ("collector.persist", "die"),
    "save_die": ("collector.save", "die"),
    "slow_node": ("storage.node.get", "latency"),
}
MATRIX_SEEDS = range(6)


def _hazard_params(name, kind, seed, salt):
    if kind == "latency":
        return {"latency_s": 0.0, "prob": 0.2, "times": None}
    # the arm depth decides killed vs survived: deep enough and the
    # run outlives the rule — both outcomes MUST occur across the
    # sweep (asserted below), or the matrix proves nothing
    return {"after_hits": derive(seed, salt, 8), "times": 1}


def _run_matrix_cell(chain, a, b, seed):
    """One composed run: hazard ``a`` at height h1, hazard ``b`` at a
    later height, both armed through the scenario engine onto ONE
    plan, over the windowed replay pipeline. Returns (blockchain,
    engine, plan, deaths)."""
    site_a, kind_a = HAZARDS[a]
    site_b, kind_b = HAZARDS[b]
    h1 = 2 + derive(seed, f"{a}>{b}:h1", 4)
    h2 = h1 + 1 + derive(seed, f"{a}>{b}:h2", 4)
    scenario = Scenario(seed, [
        ScenarioEvent("hz.a", h1, kind_a, site_a,
                      _hazard_params("a", kind_a, seed, f"{a}>{b}:a")),
        ScenarioEvent("hz.b", h2, kind_b, site_b,
                      _hazard_params("b", kind_b, seed, f"{a}>{b}:b")),
    ])
    plan = FaultPlan(seed=seed, sleep=_noop)
    engine = ScenarioEngine(scenario, plan)
    cfg = _windowed_cfg()
    bc = _fresh(cfg)
    deaths = 0
    with quiet_deaths(), active(plan):
        guard = 0
        while bc.best_block_number < N_BLOCKS:
            guard += 1
            assert guard < 64, f"matrix cell {a}>{b}@{seed} wedged"
            engine.step(bc.best_block_number)
            start = bc.best_block_number
            try:
                ReplayDriver(bc, cfg).replay(chain[start:start + 2])
            except CollectorDied:
                deaths += 1
                ReplayDriver(bc, cfg).recover()
                assert bc.storages.window_journal.pending() == []
        engine.step(bc.best_block_number)
    assert engine.done(), engine.remaining()
    return bc, engine, plan, deaths


class TestHazardMatrix:
    def test_pairwise_hazard_matrix_120_runs_bit_exact(self, chain,
                                                       reference):
        """Tentpole acceptance: every ordered pair of hazard kinds x 6
        seeds (20 x 6 = 120 composed runs). Whatever the pair kills,
        journal recovery resumes to the BIT-EXACT chain; the sweep
        exercises both outcomes (killed > 20 AND survived > 20); every
        run's outcome feeds the khipu_gameday_* families."""
        pairs = [
            (a, b) for a in HAZARDS for b in HAZARDS if a != b
        ]
        assert len(pairs) == 20
        runs = killed = survived = 0
        for a, b in pairs:
            for seed in MATRIX_SEEDS:
                bc, engine, _plan, deaths = _run_matrix_cell(
                    chain, a, b, seed
                )
                runs += 1
                if deaths:
                    killed += 1
                else:
                    survived += 1
                result = check_roots_bit_exact(bc, reference)
                assert result.ok, (
                    f"{a}>{b}@{seed}: {result.detail} "
                    f"(fired {engine.fired})"
                )
                report = InvariantReport()
                report.add(result)
                record_run(engine.events_by_kind, report)
        assert runs == 120
        assert killed > 20 and survived > 20, (killed, survived)
        assert gameday_stats().runs >= runs

    def test_matrix_cells_are_deterministic(self, chain):
        """Same (pair, seed) => identical event schedule, identical
        fired-fault log, identical final root — the replayability
        claim a gameday postmortem depends on."""
        for a, b, seed in [
            ("persist_die", "save_die", 3),
            ("slow_node", "seal_die", 1),
        ]:
            outcomes = []
            for _ in range(2):
                bc, engine, plan, deaths = _run_matrix_cell(
                    chain, a, b, seed
                )
                outcomes.append((
                    engine.scenario.schedule(),
                    list(engine.fired),
                    list(plan.fired),
                    deaths,
                    bc.get_header_by_number(
                        bc.best_block_number
                    ).state_root,
                ))
            assert outcomes[0] == outcomes[1]
