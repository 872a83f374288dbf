"""Fast-sync state download + checkpoint/resume + compactor tests
(parity targets FastSyncService.scala:100, FastSyncStateStorage.scala:24,
KesqueCompactor.scala:32, tools/DataChecker.scala:122)."""

import pytest

from khipu_tpu.base.crypto.secp256k1 import (
    privkey_to_pubkey,
    pubkey_to_address,
)
from khipu_tpu.config import fixture_config
from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
from khipu_tpu.domain.transaction import (
    Transaction,
    contract_address,
    sign_transaction,
)
from khipu_tpu.storage.compactor import compact, verify_reachable
from khipu_tpu.storage.datasource import MemoryNodeDataSource
from khipu_tpu.storage.known_nodes import KnownNodesStorage
from khipu_tpu.storage.datasource import MemoryKeyValueDataSource
from khipu_tpu.storage.storages import Storages
from khipu_tpu.sync.chain_builder import ChainBuilder
from khipu_tpu.sync.fast_sync import (
    FastSyncStateStorage,
    StateSyncer,
    SyncState,
)

CFG = fixture_config(chain_id=1)
KEYS = [(i + 1).to_bytes(32, "big") for i in range(4)]
ADDRS = [pubkey_to_address(privkey_to_pubkey(k)) for k in KEYS]
ETH = 10**18

# contract with two storage slots AND deployed runtime code, so the
# sync crosses all three stores (state, storage, evmcode)
_RUNTIME = bytes.fromhex("60005460005260206000f3")
_SSTORES = bytes.fromhex("602a600055600b600155")
_COPY = bytes(
    [0x60, len(_RUNTIME), 0x60, len(_SSTORES) + 12, 0x60, 0x00, 0x39,
     0x60, len(_RUNTIME), 0x60, 0x00, 0xF3]
)
INIT = _SSTORES + _COPY + _RUNTIME


def build_source_chain():
    bc = Blockchain(Storages(), CFG)
    builder = ChainBuilder(
        bc, CFG, GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS})
    )
    builder.add_block(
        [sign_transaction(Transaction(0, 10**9, 200_000, None, 0, INIT), KEYS[0], chain_id=1)],
        coinbase=b"\xaa" * 20,
    )
    head = builder.add_block(
        [sign_transaction(Transaction(1, 10**9, 21_000, ADDRS[1], 5 * ETH), KEYS[0], chain_id=1)],
        coinbase=b"\xaa" * 20,
    )
    return bc, head


def make_fetch(source_storages):
    def fetch(hashes):
        out = {}
        for h in hashes:
            for store in (
                source_storages.account_node_storage,
                source_storages.storage_node_storage,
                source_storages.evmcode_storage,
            ):
                v = store.get(h)
                if v is not None:
                    out[h] = v
                    break
        return out

    return fetch


class TestStateSyncer:
    def test_full_state_download(self):
        src_bc, head = build_source_chain()
        root = head.header.state_root
        target = Storages()
        syncer = StateSyncer(
            target,
            FastSyncStateStorage(MemoryKeyValueDataSource()),
            make_fetch(src_bc.storages),
        )
        state = syncer.start(root)
        assert state.downloaded_nodes > 0
        assert target.app_state.fast_sync_done
        # the synced state is complete and readable
        report = verify_reachable(
            target.account_node_storage,
            target.storage_node_storage,
            target.evmcode_storage,
            root,
        )
        assert report.missing == 0
        assert report.storage_nodes > 0 and report.code_blobs > 0
        tgt_bc = Blockchain(target, CFG)
        assert tgt_bc.get_account(ADDRS[1], root).balance == 1005 * ETH
        caddr = contract_address(ADDRS[0], 0)
        world = tgt_bc.get_world_state(root)
        assert world.get_storage(caddr, 0) == 42
        assert world.get_storage(caddr, 1) == 11
        assert world.get_code(caddr) != b""

    def test_crash_resume(self):
        src_bc, head = build_source_chain()
        root = head.header.state_root
        target = Storages()
        state_store = FastSyncStateStorage(MemoryKeyValueDataSource())

        calls = {"n": 0}
        base_fetch = make_fetch(src_bc.storages)

        def crashing_fetch(hashes):
            calls["n"] += 1
            if calls["n"] == 2:
                raise ConnectionError("peer died")
            return base_fetch(hashes)

        syncer = StateSyncer(
            target, state_store, crashing_fetch,
            batch_size=4, checkpoint_every=1,
        )
        with pytest.raises(ConnectionError):
            syncer.start(root)
        checkpoint = state_store.get_sync_state()
        assert checkpoint is not None and checkpoint.downloaded_nodes > 0

        # resume from the persisted checkpoint (fresh syncer = restart)
        resumed = StateSyncer(
            target, state_store, base_fetch, batch_size=4
        )
        final = resumed.start(root)
        assert final.downloaded_nodes >= checkpoint.downloaded_nodes
        assert state_store.get_sync_state() is None  # purged on finish
        assert verify_reachable(
            target.account_node_storage,
            target.storage_node_storage,
            target.evmcode_storage,
            root,
        ).missing == 0

    def test_corrupt_node_rejected(self):
        src_bc, head = build_source_chain()
        root = head.header.state_root
        base_fetch = make_fetch(src_bc.storages)

        def corrupting_fetch(hashes):
            out = dict(base_fetch(hashes))
            for h in list(out)[:1]:
                out[h] = out[h] + b"\x00"  # content-address mismatch
            return out

        syncer = StateSyncer(
            Storages(),
            FastSyncStateStorage(MemoryKeyValueDataSource()),
            corrupting_fetch,
        )
        with pytest.raises(RuntimeError, match="no progress|unavailable"):
            syncer.start(root)

    def test_sync_state_codec(self):
        s = SyncState(b"\x11" * 32, [(0, b"\xaa" * 32), (2, b"\xbb" * 32)], 7)
        assert SyncState.decode(s.encode()) == s


class TestCompactor:
    def test_compact_copies_exactly_reachable(self):
        src_bc, head = build_source_chain()
        root = head.header.state_root
        dsts = [MemoryNodeDataSource() for _ in range(3)]
        report = compact(
            src_bc.storages.account_node_storage,
            src_bc.storages.storage_node_storage,
            src_bc.storages.evmcode_storage,
            root,
            *dsts,
        )
        assert report.missing == 0
        # the compacted generation serves the full state on its own
        again = verify_reachable(*dsts, root)
        assert again.missing == 0
        assert again.total == report.total
        # stale generations hold MORE nodes than the pivot needs
        # (superseded roots from earlier blocks stay in the archive)
        assert src_bc.storages.account_node_storage.source.count > report.state_nodes

    def test_verify_reachable_detects_loss(self):
        src_bc, head = build_source_chain()
        root = head.header.state_root
        # clone then delete one node from the clone's account store
        dsts = [MemoryNodeDataSource() for _ in range(3)]
        compact(
            src_bc.storages.account_node_storage,
            src_bc.storages.storage_node_storage,
            src_bc.storages.evmcode_storage,
            root,
            *dsts,
        )
        victim = next(iter(dsts[0]._map))
        del dsts[0]._map[victim]
        assert verify_reachable(*dsts, root).missing >= 1


class TestKnownNodes:
    def test_roundtrip(self):
        s = KnownNodesStorage(MemoryKeyValueDataSource())
        assert s.get_known_nodes() == set()
        s.update_known_nodes(to_add={"enode://a@1:30303", "enode://b@2:30303"})
        s.update_known_nodes(to_remove={"enode://a@1:30303"})
        assert s.get_known_nodes() == {"enode://b@2:30303"}

    def test_sync_with_device_mirror(self):
        """Verified nodes admit into the word-major device mirror at
        download time; completion re-verifies the WHOLE snapshot on
        resident tiles (config #5 integration)."""
        from khipu_tpu.storage.device_mirror import DeviceNodeMirror

        src_bc, head = build_source_chain()
        root = head.header.state_root
        target = Storages()
        mirror = DeviceNodeMirror(capacity_rows_per_class=1024)
        syncer = StateSyncer(
            target,
            FastSyncStateStorage(MemoryKeyValueDataSource()),
            make_fetch(src_bc.storages),
            mirror=mirror,
        )
        state = syncer.start(root)  # raises if snapshot verify fails
        assert mirror.resident_count > 0
        assert mirror.verify() == 0
        # the mirror's resident copy of the root matches the store
        assert mirror.get(root) == target.account_node_storage.get(root)


# ------------------------------------------------ SyncStats and spans


def _account_trie(accounts: int, seed: int = 5):
    """(root, {hash: encoding}) of a state trie of plain accounts."""
    import numpy as np

    from khipu_tpu.domain.account import Account, address_key
    from khipu_tpu.trie.bulk import bulk_build, host_hasher

    rng = np.random.default_rng(seed)
    pairs = [
        (address_key(rng.bytes(16) + i.to_bytes(4, "big")),
         Account(nonce=i % 7, balance=10**9 + i).encode())
        for i in range(accounts)
    ]
    return bulk_build(pairs, hasher=host_hasher)


class _UnreliablePeer:
    """Serves ``nodes``; every ``forge_every``-th hash asked for the
    first time is answered with a forged value once, every
    ``withhold_every``-th is left out of the answer once."""

    def __init__(self, nodes, forge_every=97, withhold_every=61):
        self.nodes = nodes
        self.forge_every, self.withhold_every = forge_every, withhold_every
        self.asked = set()
        self.forged = self.withheld = self.requested = 0

    def fetch(self, hashes):
        out = {}
        self.requested += len(hashes)
        for h in hashes:
            first = h not in self.asked
            self.asked.add(h)
            n = len(self.asked)
            if first and n % self.forge_every == 0:
                self.forged += 1
                out[h] = self.nodes[h] + b"\x00"
            elif first and n % self.withhold_every == 0:
                self.withheld += 1
            else:
                out[h] = self.nodes[h]
        return out


class TestSyncStats:
    def test_phases_tile_the_loop_and_counts_match_the_peer(self):
        from khipu_tpu.sync.fast_sync import SYNC_PHASES

        root, nodes = _account_trie(3000)
        peer = _UnreliablePeer(nodes)
        syncer = StateSyncer(
            Storages(), FastSyncStateStorage(MemoryKeyValueDataSource()),
            peer.fetch, batch_size=50,
        )
        syncer.start(root)
        st = syncer.stats
        assert peer.forged > 5 and peer.withheld > 5
        assert st.rejected == peer.forged
        assert st.retried == peer.forged + peer.withheld
        assert st.requested == peer.requested
        assert st.nodes == {"state": len(nodes), "storage": 0, "code": 0}
        assert st.batches >= st.requested / 50
        assert st.checkpoints == st.batches // 10
        assert st.checkpoint_bytes > 32 * st.checkpoints
        assert st.pending == 0 and st.pending_max >= 16
        assert set(st.phases) == set(SYNC_PHASES)
        mirror_phases = {"admit", "flush", "verify"}  # no mirror here
        assert all((st.phases[p] > 0) == (p not in mirror_phases)
                   for p in SYNC_PHASES)
        covered = sum(st.phases.values())
        assert 0.95 * st.loop_seconds <= covered <= st.loop_seconds

    def test_a_loop_that_ends_by_exception_is_still_booked(self):
        root, nodes = _account_trie(200)
        calls = []

        def fetch(hashes):
            calls.append(len(hashes))
            if len(calls) == 3:
                import time

                time.sleep(0.05)
                raise ConnectionError("peer pool gave up")
            return {h: nodes[h] for h in hashes}

        syncer = StateSyncer(
            Storages(), FastSyncStateStorage(MemoryKeyValueDataSource()),
            fetch, batch_size=8,
        )
        with pytest.raises(ConnectionError):
            syncer.start(root)
        st = syncer.stats
        assert st.batches == 2
        assert st.phases["fetch"] >= 0.05  # the open phase was closed
        assert st.loop_seconds >= sum(st.phases.values()) >= 0.05
        first = st.loop_seconds
        # a second start() on the same syncer adds to the same stats
        syncer.fetch = lambda hashes: {h: nodes[h] for h in hashes}
        syncer.start(root)
        assert syncer.stats.loop_seconds > first
        assert syncer.stats.nodes["state"] >= len(nodes)  # re-downloads

    def test_newest_syncer_owns_the_registry_slot(self):
        from khipu_tpu.observability.registry import REGISTRY

        def served():
            fams = REGISTRY.families()
            return {
                name: {tuple(lb.values()): v for lb, v in samples}
                for name, (_k, _h, samples) in fams.items()
                if name.startswith("khipu_fastsync_")
                # the peer pool's are the process's own counters
                # (sync/fast_sync_service.py), no syncer's
                and not name.startswith("khipu_fastsync_peer")
            }

        root, nodes = _account_trie(300)
        fetch = lambda hashes: {h: nodes[h] for h in hashes}
        first = StateSyncer(
            Storages(), FastSyncStateStorage(MemoryKeyValueDataSource()),
            fetch, batch_size=20)
        first.start(root)
        got = served()
        assert got["khipu_fastsync_nodes_total"][("state",)] == len(nodes)
        assert got["khipu_fastsync_batches_total"][()] == first.stats.batches
        assert set(got) == {
            "khipu_fastsync_phase_seconds_total",
            "khipu_fastsync_nodes_total", "khipu_fastsync_batches_total",
            "khipu_fastsync_retried_total", "khipu_fastsync_rejected_total",
            "khipu_fastsync_checkpoint_bytes_total",
            "khipu_fastsync_checkpoint_full_total",
            "khipu_fastsync_loop_seconds", "khipu_fastsync_pending",
            "khipu_fastsync_requested_total",
            "khipu_fastsync_checkpoints_total",
            "khipu_fastsync_pending_max",
        }
        # every count SyncStats keeps is served: none is kept for a test
        st = first.stats
        assert got["khipu_fastsync_requested_total"][()] == st.requested
        assert got["khipu_fastsync_checkpoints_total"][()] \
            == st.checkpoints > 0
        assert got["khipu_fastsync_pending_max"][()] == st.pending_max > 0
        assert got["khipu_fastsync_phase_seconds_total"][("parse",)] \
            == first.stats.phases["parse"]
        second = StateSyncer(
            Storages(), FastSyncStateStorage(MemoryKeyValueDataSource()),
            fetch, batch_size=20)
        got = served()  # built, not started: the slot is already its
        assert got["khipu_fastsync_nodes_total"][("state",)] == 0
        assert got["khipu_fastsync_loop_seconds"][()] == 0
        assert "khipu_fastsync_pending" in REGISTRY.prometheus_text()
        assert second.stats is not first.stats

    def test_span_tree_of_a_batch_and_nothing_with_the_tracer_off(self):
        from khipu_tpu.observability.trace import Tracer, use_tracer
        from khipu_tpu.storage.device_mirror import DeviceNodeMirror

        root, nodes = _account_trie(300)
        fetch = lambda hashes: {h: nodes[h] for h in hashes}

        def sync(tracer):
            syncer = StateSyncer(
                Storages(),
                FastSyncStateStorage(MemoryKeyValueDataSource()),
                fetch, batch_size=20, checkpoint_every=1,
                mirror=DeviceNodeMirror(capacity_rows_per_class=1024),
            )
            with use_tracer(tracer):
                syncer.start(root)
            return tracer.snapshot(), syncer.stats

        spans, stats = sync(Tracer())
        assert spans == []  # off: the ring stays empty
        assert all(v > 0 for v in stats.phases.values())  # mirror's too
        assert sum(stats.phases.values()) >= 0.95 * stats.loop_seconds
        on = Tracer()
        on.enable()
        spans, _ = sync(on)
        batches = [s for s in spans if s.name == "fastsync.batch"]
        assert len(batches) > 10
        one = batches[3]
        assert set(one.tags) == {"batch", "nodes", "pending"}
        kids = [s.name for s in spans if s.parent == one.sid]
        # one span per interval: the admit phase is the mirror's span
        assert kids == [
            "fastsync.queue", "fastsync.fetch", "fastsync.verify",
            "fastsync.parse", "fastsync.store", "mirror.admit",
            "fastsync.checkpoint",
        ]
        cp = next(s for s in spans if s.name == "fastsync.checkpoint")
        assert cp.tags["nbytes"] > 0 and "pending" in cp.tags
        by_name = {s.name: s for s in spans}
        assert not {"fastsync.admit", "fastsync.flush",
                    "fastsync.mirror_verify"} & set(by_name)
        # after the loop, at the top: the closing flush and verify
        assert by_name["mirror.flush"].parent is None
        verify = by_name["mirror.verify"]
        assert verify.parent is None
        per_class = [s for s in spans if s.name == "mirror.verify_class"]
        assert per_class and all(s.parent == verify.sid for s in per_class)
        assert all(set(s.tags) == {"nblocks", "rows", "tiles"}
                   for s in per_class)
        assert sum(s.tags["rows"] for s in per_class) == len(nodes)


# ------------------------- the checkpoint in O(what changed) (PR 26)


def _contract_trie(accounts: int, contracts: int, seed: int = 9):
    """(root, {hash: value}) of a state trie in which ``contracts`` of
    the accounts own a storage trie and code of their own: the dict
    holds state nodes, storage nodes and code blobs."""
    import numpy as np

    from khipu_tpu.base.crypto.keccak import keccak256
    from khipu_tpu.base.rlp import rlp_encode
    from khipu_tpu.domain.account import Account, address_key
    from khipu_tpu.trie.bulk import bulk_build, host_hasher

    rng = np.random.default_rng(seed)
    nodes, pairs = {}, []
    for i in range(accounts):
        acc = Account(nonce=i % 7, balance=10**9 + i)
        if i < contracts:
            slots = [(keccak256(s.to_bytes(32, "big")),
                      rlp_encode(rng.bytes(20)))
                     for s in range(3 + i % 40)]
            storage_root, storage = bulk_build(slots, hasher=host_hasher)
            code = b"\x60" + rng.bytes(24 + i)
            nodes.update(storage)
            nodes[keccak256(code)] = code
            acc = Account(1, i, storage_root, keccak256(code))
        pairs.append((address_key(rng.bytes(16) + i.to_bytes(4, "big")),
                      acc.encode()))
    root, state = bulk_build(pairs, hasher=host_hasher)
    nodes.update(state)
    return root, nodes


def _resume_point(root, nodes, remaining=None):
    """A truthful sync stopped ``remaining`` downloads before its end
    (None: where its leaf phase begins, no node left has a child):
    (what it took off its queue so far, what its queue then holds), in
    the order a StateSyncer works in whatever its batch size."""
    from khipu_tpu.sync.fast_sync import STATE_NODE, _children_of

    order, seen, grown = [(STATE_NODE, root)], set(), []
    for kind, h in order:  # grows while it is walked
        for child in _children_of(kind, nodes[h]):
            if child[1] not in seen:
                seen.add(child[1])
                order.append(child)
        grown.append(len(order))
    if remaining is None:
        remaining = len(order) - 1 - max(
            i for i in range(len(order)) if grown[i] > grown[i - 1])
    done = len(order) - remaining
    return order[:done], order[done:grown[done - 1]]


def _store_nodes(target: Storages, items, nodes) -> None:
    for kind, h in items:
        (target.account_node_storage, target.storage_node_storage,
         target.evmcode_storage)[kind].update([], {h: nodes[h]})


def _checkpoint_source(kind: str, tmp_path):
    if kind == "memory":
        return MemoryKeyValueDataSource()
    from khipu_tpu.storage.kesque import KesqueEngine

    return KesqueEngine(str(tmp_path / "ck")).kv_source("appstate")


def _held(source) -> int:
    """Bytes stored under the checkpoint's keys."""
    return sum(len(source.get(k)) for k in source.keys()
               if k.startswith(FastSyncStateStorage.KEY))


def _synced(target: Storages, root: bytes) -> bool:
    return verify_reachable(
        target.account_node_storage, target.storage_node_storage,
        target.evmcode_storage, root, verify_hashes=True,
    ).missing == 0


class _ReadBackStorage(FastSyncStateStorage):
    """After every checkpoint, what a restarted node would resume from
    (a fresh storage over the same source) against the live queue."""

    def __init__(self, source):
        super().__init__(source)
        # per checkpoint: (the live SyncState, bytes held, what it wrote)
        self.history = []

    def checkpoint(self, target_root, downloaded_nodes, pending, taken,
                   added):
        wrote = super().checkpoint(
            target_root, downloaded_nodes, pending, taken, added)
        live = SyncState(target_root, list(pending), downloaded_nodes)
        assert FastSyncStateStorage(self.source).get_sync_state() == live
        self.history.append((live, _held(self.source), wrote))
        return wrote


class _Killed(Exception):
    pass


class _DyingSource(MemoryKeyValueDataSource):
    """Dies in its ``die_at``-th ``update`` after ``cut`` of that
    call's frames (removes in order, then upserts in order) landed:
    what a log-structured engine keeps of a torn append."""

    def __init__(self, die_at: int, cut):
        super().__init__()
        self.die_at, self.cut, self.updates = die_at, cut, 0

    def update(self, to_remove, to_upsert):
        self.updates += 1
        if self.updates != self.die_at:
            return super().update(to_remove, to_upsert)
        removes = [([k], {}) for k in to_remove]
        frames = removes + [([], {k: v}) for k, v in to_upsert.items()]
        for frame in frames[: self.cut(len(removes), len(frames))]:
            super().update(*frame)
        raise _Killed(f"update {self.updates}")


# how much of the fatal update lands: (removes, frames) -> frames kept
_CUTS = {
    "nothing": lambda removes, frames: 0,
    "one-frame": lambda removes, frames: 1,
    "removes-only": lambda removes, frames: removes,
    "all-but-the-last": lambda removes, frames: frames - 1,
    "everything": lambda removes, frames: frames,
}


class TestIncrementalCheckpoint:
    @pytest.mark.parametrize("engine", ["memory", "kesque"])
    @pytest.mark.parametrize("batch_size", [4, 20, 50])
    @pytest.mark.parametrize("checkpoint_every", [1, 3, 10])
    def test_every_checkpoint_reads_back_as_the_live_queue(
            self, tmp_path, engine, batch_size, checkpoint_every):
        root, nodes = _contract_trie(700, 40)
        peer = _UnreliablePeer(nodes, forge_every=37, withhold_every=23)
        source = _checkpoint_source(engine, tmp_path)
        store = _ReadBackStorage(source)  # compares at every checkpoint
        target = Storages()
        syncer = StateSyncer(target, store, peer.fetch,
                             batch_size=batch_size,
                             checkpoint_every=checkpoint_every)
        syncer.start(root)
        st = syncer.stats
        assert peer.forged > 5 and peer.withheld > 5
        assert st.nodes["storage"] > 40 and st.nodes["code"] == 40
        # the cadence is the parent's: one every `checkpoint_every`
        assert len(store.history) == st.checkpoints \
            == st.batches // checkpoint_every > 1
        assert st.checkpoint_full == 1  # the first; none after it
        assert [w.full for _, _, w in store.history].count(True) == 1
        assert st.checkpoint_bytes == sum(
            w.nbytes for _, _, w in store.history)
        # what is held is the live queue, the part of the front's own
        # record that it has passed, and one interval's lag: no pile
        largest = max(max(w.appended for _, _, w in store.history),
                      len(store.history[0][0].pending))
        lag = 2 * checkpoint_every * batch_size
        assert all(held <= 36 * (len(s.pending) + largest + lag) + 64
                   for s, held, _ in store.history)
        assert sum(w.removed for _, _, w in store.history) > 0 \
            or st.checkpoints < 4
        assert _held(source) == 0 and source.keys() == []  # purged
        assert _synced(target, root)

    @pytest.mark.parametrize("cut", sorted(_CUTS))
    @pytest.mark.parametrize("checkpoint_every,batch_size",
                             [(1, 4), (3, 7), (2, 20)])
    def test_a_kill_in_any_update_resumes_within_one_interval(
            self, cut, checkpoint_every, batch_size):
        root, nodes = _contract_trie(120, 8, seed=3)
        fetch = lambda hashes: {h: nodes[h] for h in hashes}
        requested = []

        def sync(source, target):
            syncer = StateSyncer(
                target, FastSyncStateStorage(source), fetch,
                batch_size=batch_size, checkpoint_every=checkpoint_every)
            try:
                syncer.start(root)
            finally:
                requested.append(syncer.stats.requested)
            return syncer.stats

        never = _DyingSource(0, None)
        every = _ReadBackStorage(never)  # the states a clean run keeps
        clean = StateSyncer(
            Storages(), every, fetch, batch_size=batch_size,
            checkpoint_every=checkpoint_every)
        clean.start(root)
        clean = clean.stats
        states = [None] + [s for s, _, _ in every.history]
        updates = never.updates  # every checkpoint, and the purge
        assert updates == len(states) > 4
        for die_at in range(1, updates + 1):
            source = _DyingSource(die_at, _CUTS[cut])
            target = Storages()
            del requested[:]
            with pytest.raises(_Killed):
                sync(source, target)
            at_kill = FastSyncStateStorage(source).get_sync_state()
            resumed = sync(source, target)  # a fresh syncer: a restart
            assert _synced(target, root) and source.keys() == []
            assert resumed.checkpoint_full == (at_kill is None)
            if die_at < updates:  # a checkpoint's write, not the purge
                # the state of the fatal checkpoint or of the one
                # before it, whole: never older, never a mix...
                landed = _CUTS[cut] is _CUTS["everything"]
                assert at_kill == states[die_at - (not landed)] \
                    or at_kill == states[die_at]
                # ...so at most one interval is asked for twice
                assert sum(requested) <= clean.requested \
                    + checkpoint_every * batch_size

    @pytest.mark.parametrize("engine", ["memory", "kesque"])
    def test_a_record_of_the_parent_format_resumes(self, tmp_path, engine):
        root, nodes = _contract_trie(300, 10)
        had, queue = _resume_point(root, nodes, remaining=200)
        assert len(queue) > 20
        # what the parent kept: one key, the whole state, no head
        source = _checkpoint_source(engine, tmp_path)
        source.put(b"fast-sync-state", SyncState(
            root, queue, downloaded_nodes=len(had)).encode())
        target = Storages()
        _store_nodes(target, had, nodes)
        syncer = StateSyncer(
            target, FastSyncStateStorage(source),
            lambda hashes: {h: nodes[h] for h in hashes},
            batch_size=10, checkpoint_every=2)
        final = syncer.start(root)
        st = syncer.stats
        assert st.requested == 200  # resumed, not restarted
        assert final.downloaded_nodes == len(had) + 200
        assert st.checkpoints > 3 and st.checkpoint_full == 0
        assert source.keys() == [] and _synced(target, root)

    @pytest.mark.parametrize("torn", ["head", "head-and-delta"])
    def test_kesque_torn_tail_resumes_from_the_checkpoint_before(
            self, tmp_path, torn):
        import os

        from khipu_tpu.storage.kesque import KesqueEngine
        from khipu_tpu.storage.segment import scan_frames

        root, nodes = _contract_trie(300, 10)
        calls = []

        def dying_fetch(hashes):
            calls.append(len(hashes))
            if len(calls) == 18:  # two batches past the third checkpoint
                raise ConnectionError("power cut")
            return {h: nodes[h] for h in hashes}

        engine = KesqueEngine(str(tmp_path / "ck"))
        store = _ReadBackStorage(engine.kv_source("appstate"))
        target = Storages()
        with pytest.raises(ConnectionError):
            StateSyncer(target, store, dying_fetch, batch_size=10,
                        checkpoint_every=5).start(root)
        assert len(store.history) == 3
        engine.stop()
        # the last append was [delta, head]: cut inside the head's
        # frame, or inside the delta's (the head's frame gone with it)
        seg_dir = os.path.join(str(tmp_path / "ck"), "kesque", "appstate")
        path = os.path.join(seg_dir, max(
            n for n in os.listdir(seg_dir) if n.endswith(".kseg")))
        with open(path, "rb") as f:
            frames, end = scan_frames(f.read())
        assert end == os.path.getsize(path)
        (delta_off, delta), (head_off, head) = frames[-2:]
        assert FastSyncStateStorage.HEAD_KEY in head
        assert FastSyncStateStorage.KEY + b"/\x00" in delta
        os.truncate(path, end - 3 if torn == "head"
                    else (delta_off + head_off) // 2)
        engine = KesqueEngine(str(tmp_path / "ck"))
        source = engine.kv_source("appstate")
        before = store.history[1][0]  # the second checkpoint's state
        assert FastSyncStateStorage(source).get_sync_state() == before
        syncer = StateSyncer(
            target, FastSyncStateStorage(source),
            lambda hashes: {h: nodes[h] for h in hashes},
            batch_size=10, checkpoint_every=5)
        syncer.start(root)
        assert syncer.stats.requested \
            == len(nodes) - before.downloaded_nodes
        assert syncer.stats.checkpoint_full == 0
        assert source.keys() == [] and _synced(target, root)
        engine.stop()

    @pytest.mark.parametrize("engine", ["memory", "kesque"])
    def test_purge_and_a_new_target_leave_nothing_of_the_old(
            self, tmp_path, engine):
        root_a, nodes_a = _contract_trie(300, 10, seed=1)
        root_b, nodes_b = _contract_trie(200, 5, seed=2)
        source = _checkpoint_source(engine, tmp_path)
        calls = []

        def dying_fetch(hashes):
            calls.append(len(hashes))
            if len(calls) == 30:
                raise ConnectionError("the pivot went stale")
            return {h: nodes_a[h] for h in hashes}

        with pytest.raises(ConnectionError):
            StateSyncer(Storages(), FastSyncStateStorage(source),
                        dying_fetch, batch_size=5,
                        checkpoint_every=2).start(root_a)
        old = FastSyncStateStorage(source).get_sync_state()
        assert old.target_root == root_a and len(source.keys()) > 3
        # a new pivot: the old checkpoint is ignored, then replaced
        target = Storages()
        store = _ReadBackStorage(source)
        syncer = StateSyncer(
            target, store, lambda hashes: {h: nodes_b[h] for h in hashes},
            batch_size=5, checkpoint_every=2)
        syncer.start(root_b)
        assert syncer.stats.requested == len(nodes_b)  # from its root
        assert syncer.stats.checkpoint_full == 1
        assert all(s.target_root == root_b for s, _, _ in store.history)
        # the base that replaced it took the old head and deltas along
        first, held, wrote = store.history[0]
        assert wrote.full
        assert held == wrote.nbytes == len(first.encode())
        assert source.keys() == [] and _synced(target, root_b)
        # purge alone, by a storage object that never read the source
        FastSyncStateStorage(source).put_sync_state(old)
        resumed = FastSyncStateStorage(source)
        assert resumed.get_sync_state() == old
        resumed.checkpoint(root_a, old.downloaded_nodes + 7,
                           old.pending[7:] + old.pending[:2], 7,
                           old.pending[:2])
        assert len(source.keys()) == 3  # base, delta, head
        FastSyncStateStorage(source).purge()
        assert source.keys() == []
        assert FastSyncStateStorage(source).get_sync_state() is None

    def test_leaf_phase_checkpoints_cost_what_changed(self):
        """The cost guard, in counts: a resumed sync with a wide
        frontier writes tens of bytes a checkpoint, not the frontier
        (the parent wrote ~35 B x the queue every time, three orders of
        magnitude more)."""
        import numpy as np

        from khipu_tpu.domain.account import Account
        from khipu_tpu.trie.bulk import bulk_build, host_hasher

        # 20,480 accounts, five under each of the 4,096 three-nibble
        # prefixes: every leaf at one depth, so the leaf phase is exact
        rng = np.random.default_rng(26)
        root, nodes = bulk_build([
            (((i // 5) << 4 | i % 5).to_bytes(2, "big") + rng.bytes(30),
             Account(nonce=i % 7, balance=10**9 + i).encode())
            for i in range(20_480)], hasher=host_hasher)
        had, queue = _resume_point(root, nodes)  # where it begins
        assert len(had) == 1 + 16 + 256 + 4096 and len(queue) == 20_480
        source = MemoryKeyValueDataSource()
        base = FastSyncStateStorage(source).put_sync_state(
            SyncState(root, queue, downloaded_nodes=len(had)))
        assert base > 35 * len(queue)
        target = Storages()
        _store_nodes(target, had, nodes)
        peer = _UnreliablePeer(nodes, forge_every=997, withhold_every=499)
        store = _ReadBackStorage(source)
        syncer = StateSyncer(target, store, peer.fetch, batch_size=50)
        syncer.start(root)
        st = syncer.stats
        assert peer.forged > 5 and peer.withheld > 5
        assert st.nodes["state"] == len(queue) and st.checkpoint_full == 0
        assert st.pending_max <= len(queue)  # nothing joins but retries
        assert st.checkpoints == st.batches // 10 >= 40
        assert st.checkpoint_bytes / st.checkpoints < 1_000
        # every hash is written once, when it joins the queue: all this
        # sync's checkpoints together are a fraction of ONE of the
        # parent's, and what is held never grows past the base
        assert st.checkpoint_bytes < base / 10
        assert max(held for _, held, _ in store.history) \
            <= base + 36 * st.retried + 100
        assert source.keys() == [] and _synced(target, root)
