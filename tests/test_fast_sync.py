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
