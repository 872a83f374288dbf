"""Elastic membership (khipu_tpu/cluster/rebalance.py): epoch-fenced
ring transitions, exact movement planning, crash-safe live join/retire
over fake transports, the 120-seed InjectedDeath sweep across every
``rebalance.*`` chaos seam, and the ISSUE-11 acceptance scenario —
join-4th-mid-sync, kill-mid-stream, rejoin, cutover, retire-an-original
under live load with zero wrong reads."""

import threading
import time

import pytest

from khipu_tpu.base.crypto.keccak import keccak256
from khipu_tpu.chaos import (
    FaultPlan,
    FaultRule,
    InjectedDeath,
    active,
)
from khipu_tpu.cluster import (
    HashRing,
    Rebalancer,
    RebalanceError,
    RebalanceAborted,
    ShardedNodeClient,
    movement_plan,
)
from khipu_tpu.cluster.rebalance import moved_fraction
from khipu_tpu.cluster.ring import RING_SIZE, _point


def _val(i: int) -> bytes:
    return b"mpt node rlp bytes #%d" % i


def _key(v: bytes) -> bytes:
    return keccak256(v)


def _dataset(n: int):
    return {_key(_val(i)): _val(i) for i in range(n)}


# ------------------------------------------------- fake transport


class FakeShard:
    """In-memory BridgeClient stand-in with the rebalance surface:
    cursor-paged ``stream_node_data`` over the store, content-addressed
    ``put_node_data``."""

    def __init__(self, store=None, fail=False):
        self.store = dict(store or {})
        self.fail = fail
        self.stream_calls = 0
        self.on_stream = None  # test hook, runs before each page
        self.corrupt_stream = False  # flip bytes in streamed pages

    def get_node_data(self, hashes):
        if self.fail:
            raise ConnectionError("shard down")
        return {h: self.store[h] for h in hashes if h in self.store}

    def put_node_data(self, nodes):
        if self.fail:
            raise ConnectionError("shard down")
        self.store.update(nodes)
        return len(nodes)

    def stream_node_data(self, ranges, cursor, count):
        self.stream_calls += 1
        if self.on_stream is not None:
            self.on_stream(self)
        if self.fail:
            raise ConnectionError("shard down")
        snap = dict(self.store)  # live writers mutate concurrently
        keys = sorted(
            k for k in snap
            if cursor < k
            and any(lo <= _point(k) < hi for lo, hi in ranges)
        )
        page = keys[:count]
        done = len(keys) <= count
        nxt = page[-1] if page else bytes(cursor)
        pairs = [(k, snap[k]) for k in page]
        if self.corrupt_stream and pairs:
            k, v = pairs[0]
            pairs[0] = (k, b"evil " + v)  # wire corruption
        return done, nxt, pairs

    def ping(self, payload=b""):
        if self.fail:
            raise ConnectionError("shard down")
        return payload

    def close(self):
        pass


def make_cluster(members, data=None, extra=(), **kwargs):
    """Client over ``members`` + a Rebalancer; ``extra`` endpoints get
    FakeShards in the transport map but stay outside the ring (join
    candidates)."""
    shards = {ep: FakeShard() for ep in (*members, *extra)}
    kwargs.setdefault("replication", 2)
    kwargs.setdefault("vnodes", 8)  # keeps snapshot rebuilds cheap
    kwargs.setdefault("max_retries", 1)
    kwargs.setdefault("sleep", lambda s: None)
    cl = ShardedNodeClient(
        list(members),
        channel_factory=lambda ep: shards[ep],
        **kwargs,
    )
    rb = Rebalancer(cl, batch=64)
    if data:
        cl.replicate(data)
    return cl, rb, shards


# ---------------------------------------------------- transitions


class TestRingTransition:
    def test_begin_stages_next_epoch_without_commit(self):
        ring = HashRing(["a", "b"], replication=2, vnodes=8)
        e0 = ring.epoch
        old, new = ring.begin_transition(["a", "b", "c"])
        assert (old.epoch, new.epoch) == (e0, e0 + 1)
        assert ring.epoch == e0  # committed epoch unchanged
        assert ring.in_transition
        assert ring.members == ("a", "b")  # placement unchanged

    def test_only_one_transition_open(self):
        ring = HashRing(["a", "b"], replication=2, vnodes=8)
        ring.begin_transition(["a", "b", "c"])
        with pytest.raises(RuntimeError):
            ring.begin_transition(["a", "b", "d"])

    def test_no_op_transition_rejected(self):
        ring = HashRing(["a", "b"], replication=2, vnodes=8)
        with pytest.raises(ValueError):
            ring.begin_transition(["b", "a", "a"])

    def test_read_chain_new_then_old_write_chains_union(self):
        ring = HashRing(["a", "b", "c"], replication=2, vnodes=8)
        old, new = ring.begin_transition(["a", "b", "c", "d"])
        for i in range(200):
            k = _key(_val(i))
            pt = _point(k)
            rc = ring.read_chain(k)
            wc = ring.write_chains(k)
            # new-epoch owners first, then any old owner not already in
            expect = list(new.chain_at(pt))
            for ep in old.chain_at(pt):
                if ep not in expect:
                    expect.append(ep)
            assert rc == expect
            # writes land in the union of both worlds
            assert set(wc) == set(old.chain_at(pt)) | set(
                new.chain_at(pt)
            )
            assert len(wc) == len(set(wc))

    def test_commit_is_atomic_cutover(self):
        ring = HashRing(["a", "b"], replication=2, vnodes=8)
        _, new = ring.begin_transition(["a", "b", "c"])
        committed = ring.commit_transition()
        assert committed is new
        assert ring.epoch == new.epoch
        assert not ring.in_transition
        assert set(ring.members) == {"a", "b", "c"}
        with pytest.raises(RuntimeError):
            ring.commit_transition()

    def test_abort_leaves_committed_ring_untouched(self):
        ring = HashRing(["a", "b"], replication=2, vnodes=8)
        before = {
            _key(_val(i)): ring.replicas_for(_key(_val(i)))
            for i in range(100)
        }
        ring.begin_transition(["a", "b", "c"])
        assert ring.abort_transition() is True
        assert ring.abort_transition() is False  # nothing open now
        assert ring.epoch == 1 and not ring.in_transition
        for k, chain in before.items():
            assert ring.replicas_for(k) == chain

    def test_direct_membership_change_auto_aborts(self):
        ring = HashRing(["a", "b"], replication=2, vnodes=8)
        ring.begin_transition(["a", "b", "c"])
        assert ring.add("x") is True
        assert not ring.in_transition
        assert ring.transition_aborts == 1
        ring.begin_transition(["a", "b", "x", "c"])
        assert ring.remove("x") is True
        assert not ring.in_transition
        assert ring.transition_aborts == 2

    def test_epoch_monotone_across_membership_changes(self):
        ring = HashRing(["a"], replication=1, vnodes=8)
        seen = [ring.epoch]
        ring.add("b")
        seen.append(ring.epoch)
        ring.begin_transition(["a", "b", "c"])
        ring.commit_transition()
        seen.append(ring.epoch)
        ring.remove("c")
        seen.append(ring.epoch)
        assert seen == sorted(seen) and len(set(seen)) == len(seen)


class TestChainShortCircuit:
    def test_single_member_walks_one_point(self):
        """Regression (ISSUE 11 satellite): ``chain_at`` short-circuits
        at ``len(members)`` distinct endpoints — a 1-member ring with
        replication=2 must not walk all vnode points hunting for a
        second endpoint that cannot exist."""
        ring = HashRing(["only"], replication=2, vnodes=64)

        class CountingOwners(list):
            reads = 0

            def __getitem__(self, i):
                CountingOwners.reads += 1
                return list.__getitem__(self, i)

        ring._snap.owners = CountingOwners(ring._snap.owners)
        assert ring.replicas_for(_key(_val(1))) == ["only"]
        assert CountingOwners.reads == 1

    def test_chain_capped_by_membership_mid_transition(self):
        ring = HashRing(["a"], replication=2, vnodes=8)
        old, new = ring.begin_transition(["a", "b"])
        assert old.chain_at(123) == ["a"]
        assert len(new.chain_at(123)) == 2


# -------------------------------------------------- movement plan


class TestMovementPlan:
    def test_join_moves_bounded_fraction_of_keys(self):
        """Property (ISSUE 11 satellite): adding 1 endpoint to an
        N-member ring remaps at most ``1.5/(N+1)`` of 10k keys."""
        n = 4
        ring = HashRing(
            [f"s{i}" for i in range(n)], replication=1, vnodes=64
        )
        keys = [_key(_val(i)) for i in range(10_000)]
        before = {k: ring.primary_for(k) for k in keys}
        old, new = ring.begin_transition(
            [f"s{i}" for i in range(n)] + ["joiner"]
        )
        moved = sum(
            1 for k in keys if new.replicas_for(k) != [before[k]]
        )
        assert moved / len(keys) <= 1.5 / (n + 1)
        # the plan's analytic fraction agrees with the sampled one
        frac = moved_fraction(movement_plan(old, new))
        assert abs(frac - moved / len(keys)) < 0.05

    def test_remove_restores_exact_prior_ownership(self):
        ring = HashRing(["a", "b", "c"], replication=2, vnodes=64)
        keys = [_key(_val(i)) for i in range(2_000)]
        before = {k: ring.replicas_for(k) for k in keys}
        ring.add("d")
        ring.remove("d")
        for k in keys:
            assert ring.replicas_for(k) == before[k]

    def test_plan_ranges_exactly_cover_gaining_keys(self):
        """movement_plan is exact, not sampled: a key falls inside some
        MovedRange iff its new chain gained an endpoint."""
        ring = HashRing(["a", "b", "c"], replication=2, vnodes=8)
        old, new = ring.begin_transition(["a", "b", "c", "d"])
        plan = movement_plan(old, new)
        for i in range(3_000):
            k = _key(_val(i))
            pt = _point(k)
            oc = old.chain_at(pt)
            gainers = [
                ep for ep in new.chain_at(pt) if ep not in oc
            ]
            hit = [
                r for r in plan if r.lo <= pt < r.hi
            ]
            if gainers:
                assert len(hit) == 1
                assert list(hit[0].gainers) == gainers
                assert list(hit[0].sources) == oc
            else:
                assert hit == []

    def test_plan_ranges_disjoint_and_in_ring(self):
        ring = HashRing(["a", "b"], replication=1, vnodes=16)
        old, new = ring.begin_transition(["a", "b", "c"])
        plan = sorted(movement_plan(old, new), key=lambda r: r.lo)
        for r in plan:
            assert 0 <= r.lo < r.hi <= RING_SIZE
        for r1, r2 in zip(plan, plan[1:]):
            assert r1.hi <= r2.lo


# ----------------------------------------------- join and retire


class TestJoinRetire:
    def test_join_streams_then_cuts_over(self):
        data = _dataset(300)
        cl, rb, shards = make_cluster(["a", "b", "c"], data,
                                      extra=("d",))
        e0 = cl.ring.epoch
        streamed = rb.join("d")
        assert streamed > 0
        assert set(cl.ring.members) == {"a", "b", "c", "d"}
        assert cl.ring.epoch == e0 + 1
        assert not cl.ring.in_transition
        assert rb.completed == 1 and rb.state == "idle"
        # every key the new epoch assigns to d actually landed on d
        for k, v in data.items():
            if "d" in cl.ring.replicas_for(k):
                assert shards["d"].store[k] == v
        # full readback, bit-exact
        assert cl.fetch(list(data)) == data
        assert cl.metrics["d"].rebalanced == streamed
        assert cl._full_ring.members == cl.ring.members

    def test_retire_drains_then_drops(self):
        data = _dataset(300)
        cl, rb, shards = make_cluster(["a", "b", "c"], data)
        rb.retire("a")
        assert set(cl.ring.members) == {"b", "c"}
        assert not cl.ring.in_transition
        # the retired shard is gone from the configured ring too
        assert set(cl._full_ring.members) == {"b", "c"}
        # all keys still fully replicated across the survivors
        for k, v in data.items():
            for ep in cl.ring.replicas_for(k):
                assert shards[ep].store[k] == v
        assert cl.fetch(list(data)) == data

    def test_join_then_retire_roundtrip_ownership(self):
        data = _dataset(200)
        cl, rb, _ = make_cluster(["a", "b", "c"], data, extra=("d",))
        before = {k: cl.ring.replicas_for(k) for k in data}
        rb.join("d")
        rb.retire("d")
        for k in data:
            assert cl.ring.replicas_for(k) == before[k]
        assert cl.fetch(list(data)) == data

    def test_join_validates_membership(self):
        cl, rb, _ = make_cluster(["a", "b"], _dataset(10))
        with pytest.raises(ValueError):
            rb.join("a")

    def test_retire_validates_membership_and_last_member(self):
        cl, rb, _ = make_cluster(["a", "b"], _dataset(10))
        with pytest.raises(ValueError):
            rb.retire("zz")
        cl2, rb2, _ = make_cluster(["solo"], replication=1)
        with pytest.raises(ValueError):
            rb2.retire("solo")

    def test_corrupt_stream_aborts_to_committed_epoch(self):
        data = _dataset(100)
        cl, rb, shards = make_cluster(["a", "b", "c"], data,
                                      extra=("d",))

        for ep in ("a", "b", "c"):
            shards[ep].corrupt_stream = True
        e0 = cl.ring.epoch
        with pytest.raises(RebalanceError):
            rb.join("d")
        assert cl.ring.epoch == e0
        assert not cl.ring.in_transition
        assert set(cl.ring.members) == {"a", "b", "c"}
        assert rb.aborts == 1 and rb.state == "idle"

    def test_member_death_mid_stream_aborts(self):
        data = _dataset(200)
        cl, rb, shards = make_cluster(["a", "b", "c"], data,
                                      extra=("d",))
        fired = []

        def kill_b(shard):
            if not fired:
                fired.append(1)
                cl.mark_dead("b")

        for ep in ("a", "b", "c"):
            shards[ep].on_stream = kill_b
        e_members = set(cl.ring.members)
        with pytest.raises(RebalanceAborted):
            rb.join("d")
        assert rb.aborts == 1
        assert not cl.ring.in_transition
        assert set(cl.ring.members) == e_members - {"b"}
        # the committed (post-death) ring still serves every key
        assert cl.fetch(list(data)) == data

    def test_second_rebalance_while_pending_rejected(self):
        cl, rb, _ = make_cluster(["a", "b"], _dataset(10),
                                 extra=("c",))
        rb._begin("join", "c", ("a", "b", "c"))
        with pytest.raises(RuntimeError):
            rb.join("c")


# ------------------------------------------------- crash recovery


def _die(site, seed=0, after=0):
    return FaultPlan(seed=seed, rules=[
        FaultRule(site=site, kind="die", after=after, times=1)
    ])


class TestCrashRecovery:
    def test_death_mid_stream_then_resume(self):
        data = _dataset(300)
        cl, rb, _ = make_cluster(["a", "b", "c"], data, extra=("d",))
        e0 = cl.ring.epoch
        with active(_die("rebalance.stream", after=1)):
            with pytest.raises(InjectedDeath):
                rb.join("d")
        # crash left the committed epoch serving and a transition open
        assert cl.ring.epoch == e0
        assert cl.fetch(list(data)) == data
        assert rb.recover() == "resumed"
        assert set(cl.ring.members) == {"a", "b", "c", "d"}
        assert cl.ring.epoch == e0 + 1
        assert cl.fetch(list(data)) == data

    def test_death_before_plan_then_rollback_is_bookkeeping(self):
        data = _dataset(50)
        cl, rb, _ = make_cluster(["a", "b"], data, extra=("c",))
        with active(_die("rebalance.plan")):
            with pytest.raises(InjectedDeath):
                rb.join("c")
        assert not cl.ring.in_transition  # died before staging
        assert rb.recover() == "rolled_back"
        assert set(cl.ring.members) == {"a", "b"}
        assert rb.recover() == "idle"

    def test_dead_target_rolls_back_and_records_debt(self):
        data = _dataset(300)
        cl, rb, shards = make_cluster(["a", "b", "c"], data,
                                      extra=("d",))
        e0 = cl.ring.epoch
        with active(_die("rebalance.stream", after=2)):
            with pytest.raises(InjectedDeath):
                rb.join("d")
        assert rb.keys_streamed > 0  # at least one page landed on d
        shards["d"].fail = True  # the joiner died with us
        assert rb.recover() == "rolled_back"
        assert cl.ring.epoch == e0
        assert set(cl.ring.members) == {"a", "b", "c"}
        assert rb.aborts == 1
        # the half-streamed keys became anti-entropy debt for d
        assert cl._missed.get("d")
        assert cl.fetch(list(data)) == data

    def test_death_at_cutover_then_resume_completes(self):
        data = _dataset(200)
        cl, rb, _ = make_cluster(["a", "b", "c"], data, extra=("d",))
        e0 = cl.ring.epoch
        with active(_die("rebalance.cutover")):
            with pytest.raises(InjectedDeath):
                rb.join("d")
        # the cutover seam fires BEFORE commit: old epoch authoritative
        assert cl.ring.epoch == e0
        assert cl.fetch(list(data)) == data
        assert rb.recover() == "resumed"
        assert cl.ring.epoch == e0 + 1
        assert cl.fetch(list(data)) == data

    def test_die_sweep_never_serves_wrong_bytes(self):
        """ISSUE 11 acceptance: 120 seeded InjectedDeath runs across
        every ``rebalance.*`` seam; after recover() the cluster is at
        exactly the old or the new epoch (never between) and every key
        reads back bit-exact."""
        sites = (
            "rebalance.plan", "rebalance.stream",
            "rebalance.cutover", "rebalance.retire",
        )
        data = _dataset(120)
        runs = 0
        for site in sites:
            for seed in range(30):
                runs += 1
                cl, rb, shards = make_cluster(
                    ["a", "b", "c"], data, extra=("d",)
                )
                kind = "retire" if site == "rebalance.retire" else "join"
                target = "a" if kind == "retire" else "d"
                old_members = set(cl.ring.members)
                new_members = (
                    old_members - {target} if kind == "retire"
                    else old_members | {target}
                )
                e0 = cl.ring.epoch
                plan = _die(site, seed=seed, after=seed % 4)
                died = False
                with active(plan):
                    try:
                        getattr(rb, kind)(target)
                    except InjectedDeath:
                        died = True
                    except RebalanceError:
                        pass
                # no injected plan any more: settle the wreckage
                outcome = rb.recover()
                assert not cl.ring.in_transition, (site, seed)
                members = set(cl.ring.members)
                if members == old_members:
                    assert cl.ring.epoch == e0, (site, seed)
                else:
                    assert members == new_members, (site, seed)
                    assert cl.ring.epoch == e0 + 1, (site, seed)
                # bit-exact reads from whichever epoch won
                assert cl.fetch(list(data)) == data, (site, seed)
                if died:
                    # "idle" only when death hit BEFORE any state was
                    # created (the rebalance.retire entry seam)
                    assert outcome in (
                        "resumed", "rolled_back", "idle"
                    ), (site, seed)
        assert runs == 120


# --------------------------------------------------- acceptance


class TestAcceptanceLiveLoad:
    def test_join_kill_rejoin_cutover_retire_under_load(self):
        """3-shard cluster under live read/write load: join a 4th
        mid-sync, kill it mid-stream (InjectedDeath), rejoin via
        recover(), cut over, then retire an original — zero wrong
        reads, read-your-writes holds throughout, final ownership
        equals a fresh ring of the survivors."""
        data = _dataset(250)
        cl, rb, shards = make_cluster(["a", "b", "c"], data,
                                      extra=("d",))
        errors = []
        stop = threading.Event()
        written = dict(data)
        wlock = threading.Lock()

        def writer():
            i = 100_000
            while not stop.is_set():
                v = _val(i)
                k = _key(v)
                try:
                    cl.replicate({k: v})
                    got = cl.fetch([k])
                    if got != {k: v}:  # read-your-writes
                        errors.append(("ryw", k.hex()[:12], got))
                except Exception as e:
                    errors.append(("write", type(e).__name__, str(e)))
                with wlock:
                    written[k] = v
                i += 1
                # a bounded write rate: each stream page re-reads the
                # whole store, so a writer that spins outruns a
                # rebalance starved of the CPU (six xdist workers) and
                # the test never ends
                time.sleep(0.001)

        def reader():
            n = 0
            while not stop.is_set():
                with wlock:
                    items = list(written.items())
                k, v = items[n % len(items)]
                try:
                    got = cl.fetch([k])
                    if got != {k: v}:
                        errors.append(("read", k.hex()[:12], got))
                except Exception as e:
                    errors.append(("read", type(e).__name__, str(e)))
                n += 1

        threads = [
            threading.Thread(target=writer, daemon=True),
            threading.Thread(target=reader, daemon=True),
        ]
        for t in threads:
            t.start()
        try:
            # join the 4th shard and kill the rebalance mid-stream
            with active(_die("rebalance.stream", after=1)):
                with pytest.raises(InjectedDeath):
                    rb.join("d")
            # rejoin: the staged epoch is still open, targets answer
            assert rb.recover() == "resumed"
            assert set(cl.ring.members) == {"a", "b", "c", "d"}
            # retire an ORIGINAL member under the same load
            rb.retire("a")
            assert set(cl.ring.members) == {"b", "c", "d"}
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert errors == []
        assert not cl.ring.in_transition
        # final ownership == a fresh ring of exactly the survivors
        fresh = HashRing(["b", "c", "d"], replication=2, vnodes=8)
        with wlock:
            snapshot = dict(written)
        for k in list(snapshot)[:500]:
            assert cl.ring.replicas_for(k) == fresh.replicas_for(k)
        # every key ever written reads back bit-exact
        assert cl.fetch(list(snapshot)) == snapshot
        assert rb.completed == 2  # the resumed join + the retire


# ----------------------------------------------- observability


class TestObservability:
    def test_cluster_registry_families_pinned(self):
        """Regression (ISSUE 11 satellite): the anti-entropy debt
        gauges are exported as first-class registry families."""
        from khipu_tpu.observability.registry import REGISTRY

        cl, rb, _ = make_cluster(["a", "b"], _dataset(5))
        cl._record_missed("a", [b"\x01" * 32])
        text = REGISTRY.prometheus_text()
        assert "khipu_cluster_missed_keys" in text
        assert "khipu_cluster_missed_dropped_total" in text
        assert "khipu_cluster_epoch" in text
        for fam in (
            "khipu_rebalance_epoch",
            "khipu_rebalance_in_transition",
            "khipu_rebalance_keys_streamed_total",
            "khipu_rebalance_keys_placed_total",
            "khipu_rebalance_completed_total",
            "khipu_rebalance_aborts_total",
            "khipu_rebalance_moved_fraction",
        ):
            assert fam in text, fam

    def test_metrics_snapshot_carries_rebalance_block(self):
        cl, rb, _ = make_cluster(["a", "b"], _dataset(20),
                                 extra=("c",))
        rb.join("c")
        snap = cl.metrics_snapshot()
        assert snap["epoch"] == cl.ring.epoch
        assert snap["inTransition"] is False
        assert snap["rebalance"]["completed"] == 1
        assert snap["rebalance"]["state"] == "idle"
        assert snap["rebalance"]["keysStreamed"] == rb.keys_streamed

    def test_rebalance_pressure_signal(self):
        from khipu_tpu.serving import rebalance_pressure

        cl, rb, _ = make_cluster(["a", "b"], _dataset(10))
        sig = rebalance_pressure(rb)
        assert sig.signal_name == "rebalance"
        assert sig() == 0.0  # idle: the signal costs nothing
        cl.ring.begin_transition(["a", "b", "c"])
        assert sig() == pytest.approx(0.88)
        cl.ring.abort_transition()
        assert sig() == 0.0

    def test_watchdog_rebalance_stuck_edge_triggered(self):
        from khipu_tpu.config import TelemetryConfig
        from khipu_tpu.observability.telemetry import (
            WATCHDOG_KINDS,
            Watchdog,
        )

        assert "rebalance_stuck" in WATCHDOG_KINDS
        state = {"open": False, "prog": 0}
        dog = Watchdog(
            TelemetryConfig(enabled=True, stall_after_s=5.0),
            pipeline={},
            rebalance=lambda: (state["open"], state["prog"]),
        )
        # clean run: nothing trips, the kind exports as zero
        assert dog.check_once(now=0.0) == []
        assert dog.trips["rebalance_stuck"] == 0
        assert (
            "khipu_watchdog_trips_total", "counter",
            {"kind": "rebalance_stuck"}, 0,
        ) in dog._registry_samples()
        # transition opens and progress goes flat: one trip per episode
        state["open"] = True
        assert dog.check_once(now=10.0) == []  # arms
        assert dog.check_once(now=16.0) == ["rebalance_stuck"]
        assert dog.check_once(now=30.0) == []  # edge, not level
        # progress re-arms the detector
        state["prog"] = 42
        assert dog.check_once(now=31.0) == []
        assert dog.check_once(now=37.0) == ["rebalance_stuck"]
        # closing the transition re-arms too
        state["open"] = False
        assert dog.check_once(now=50.0) == []
        assert dog.trips["rebalance_stuck"] == 2
