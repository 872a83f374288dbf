"""Fast-sync orchestration over real RLPx loopback peers.

The verdict-7 scenario: pivot selection by MEDIAN best number over >= N
peers, and a bounded-concurrency multi-peer node-download pool feeding
StateSyncer — with one of three serving peers STALLING mid-download
(request timeout -> blacklist -> work redistributed to the live peers).

Parity: FastSyncService.scala:184-273 (pivot), :537-667 (scheduler).
"""

import dataclasses
import threading
import time

import pytest

from khipu_tpu.base.crypto.secp256k1 import (
    privkey_to_pubkey,
    pubkey_to_address,
)
from khipu_tpu.config import SyncConfig, fixture_config
from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
from khipu_tpu.domain.transaction import Transaction, sign_transaction
from khipu_tpu.network.host_service import HostService
from khipu_tpu.network.messages import (
    ETH_OFFSET,
    GET_NODE_DATA,
    Status,
)
from khipu_tpu.network.peer import PeerManager
from khipu_tpu.storage.compactor import verify_reachable
from khipu_tpu.storage.storages import Storages
from khipu_tpu.sync.chain_builder import ChainBuilder
from khipu_tpu.sync.fast_sync_service import FastSyncError, FastSyncService
from khipu_tpu.sync.replay import ReplayDriver

SENDER_KEY = (11).to_bytes(32, "big")
SENDER = pubkey_to_address(privkey_to_pubkey(SENDER_KEY))
ALLOC = {SENDER: 10**24}

CFG = dataclasses.replace(
    fixture_config(chain_id=1),
    sync=SyncConfig(
        parallel_tx=False, tx_workers=2, commit_window_blocks=1,
        min_peers_to_choose_pivot=3, pivot_block_offset=3,
        nodes_per_request=16, peer_request_timeout=1.0,
    ),
)


def build_and_import(n_blocks=20):
    builder = ChainBuilder(
        Blockchain(Storages(), CFG), CFG, GenesisSpec(alloc=ALLOC)
    )
    blocks = []
    for n in range(1, n_blocks + 1):
        txs = [
            sign_transaction(
                Transaction(
                    n - 1, 10**9, 21_000,
                    bytes.fromhex("%040x" % (0xCAFE + n)), 1 + n,
                ),
                SENDER_KEY, chain_id=1,
            )
        ]
        blocks.append(builder.add_block(txs, coinbase=b"\xaa" * 20))
    bc = Blockchain(Storages(), CFG)
    bc.load_genesis(GenesisSpec(alloc=ALLOC))
    ReplayDriver(bc, CFG).replay(blocks)
    return bc, blocks


def make_status_factory(bc):
    def make():
        best = bc.best_block_number
        return Status(
            protocol_version=63, network_id=1,
            total_difficulty=bc.get_total_difficulty(best) or 0,
            best_hash=bc.get_hash_by_number(best),
            genesis_hash=bc.get_hash_by_number(0),
        )
    return make


@pytest.fixture
def cluster():
    """One source chain, three serving peers (one stallable), one
    syncing client connected to all three over RLPx loopback."""
    managers = []
    bc, blocks = build_and_import(20)
    stall = threading.Event()

    servers = []
    for i in range(3):
        priv = (0x5E0 + i).to_bytes(32, "big")
        m = PeerManager(priv, f"khipu-tpu/server{i}", make_status_factory(bc))
        HostService(bc).install(m)
        if i == 2:
            # peer 2 can be switched into a stall: accepts the request,
            # never answers (the reader thread sleeps through the
            # client's timeout window)
            real = m.handlers[ETH_OFFSET + GET_NODE_DATA]

            def stalling(body, _real=real):
                if stall.is_set():
                    time.sleep(5.0)
                    return None
                return _real(body)

            m.handlers[ETH_OFFSET + GET_NODE_DATA] = stalling
        port = m.listen()
        servers.append((m, port, privkey_to_pubkey(priv)))
        managers.append(m)

    syncer_bc = Blockchain(Storages(), CFG)
    syncer_bc.load_genesis(GenesisSpec(alloc=ALLOC))
    client = PeerManager(
        (0xC11).to_bytes(32, "big"), "khipu-tpu/syncer",
        make_status_factory(syncer_bc),
    )
    managers.append(client)
    for m, port, pub in servers:
        client.connect("127.0.0.1", port, pub)

    yield bc, blocks, syncer_bc, client, stall
    for m in managers:
        m.stop()


class TestFastSyncService:
    def test_pivot_is_median_minus_offset(self, cluster):
        bc, blocks, syncer_bc, client, stall = cluster
        svc = FastSyncService(syncer_bc, CFG, client)
        pivot = svc.choose_pivot()
        # all peers serve the same chain: median best = 20, offset 3
        assert pivot.number == 17
        assert pivot.state_root == blocks[16].header.state_root

    def test_pivot_requires_min_peers(self, cluster):
        bc, blocks, syncer_bc, client, stall = cluster
        # drop to 2 peers: below the configured minimum of 3
        client.peers[0].disconnect()
        svc = FastSyncService(syncer_bc, CFG, client)
        with pytest.raises(FastSyncError, match="peers"):
            svc.choose_pivot()

    def test_full_fast_sync_with_stalling_peer(self, cluster):
        bc, blocks, syncer_bc, client, stall = cluster
        logs = []
        svc = FastSyncService(syncer_bc, CFG, client, log=logs.append)
        stall.set()  # peer 2 stalls every node-data request
        state = svc.run()

        # the stalling peer was blacklisted and the download finished
        # from the other two
        assert svc.pool.blacklisted == 1
        assert client.blacklist.is_blacklisted(client.peers[2].remote_pub)
        assert state.downloaded_nodes > 20

        pivot_n = 20 - CFG.sync.pivot_block_offset
        # block data backfilled to the pivot
        assert syncer_bc.best_block_number == pivot_n
        assert (
            syncer_bc.get_hash_by_number(pivot_n)
            == blocks[pivot_n - 1].hash
        )
        # the downloaded state trie is COMPLETE at the pivot root
        root = blocks[pivot_n - 1].header.state_root
        report = verify_reachable(
            syncer_bc.storages.account_node_storage,
            syncer_bc.storages.storage_node_storage,
            syncer_bc.storages.evmcode_storage,
            root,
        )
        assert report.missing == 0
        # spot-check an account through the world at the pivot
        w = syncer_bc.get_world_state(root)
        assert w.get_balance(SENDER) > 0
        assert syncer_bc.storages.app_state.fast_sync_done


# ------------------------- wide batches, the node's mirror, stop/resume

WIDE_ALLOC = dict(ALLOC)
WIDE_ALLOC.update({(0xD00D0000 + i).to_bytes(20, "big"): 10**18 + i
                   for i in range(600)})
WIDE_CFG = dataclasses.replace(
    CFG, sync=dataclasses.replace(CFG.sync, pivot_block_offset=2))


@pytest.fixture
def wide():
    """A state of some 600 accounts behind three honest peers: wide
    enough that one syncer batch has a chunk for every peer."""
    bc = Blockchain(Storages(), WIDE_CFG)
    builder = ChainBuilder(bc, WIDE_CFG, GenesisSpec(alloc=WIDE_ALLOC))
    blocks = [builder.add_block([], coinbase=b"\xaa" * 20) for _ in range(4)]
    managers, servers = [], []
    for i in range(3):
        priv = (0x71DE + i).to_bytes(32, "big")
        m = PeerManager(priv, f"khipu-tpu/wide{i}", make_status_factory(bc))
        HostService(bc).install(m)
        servers.append((m.listen(), privkey_to_pubkey(priv)))
        managers.append(m)

    def node(dial_timeout=5.0):
        syncer_bc = Blockchain(Storages(), WIDE_CFG)
        syncer_bc.load_genesis(GenesisSpec(alloc=WIDE_ALLOC))
        client = PeerManager((0xC12 + len(managers)).to_bytes(32, "big"),
                             "khipu-tpu/syncer",
                             make_status_factory(syncer_bc))
        managers.append(client)
        for port, pub in servers:
            client.connect("127.0.0.1", port, pub, timeout=dial_timeout)
        return syncer_bc, client

    yield blocks, node
    for m in managers:
        m.stop()


def _trie_nodes(storages):
    return (set(storages.account_node_storage.source.keys())
            | set(storages.storage_node_storage.source.keys()))


class TestWideBatchesAndTheMirror:
    def test_one_batch_has_a_chunk_for_every_live_peer(self, wide):
        from khipu_tpu.observability.trace import tracer
        from khipu_tpu.sync import fast_sync_service as fss

        blocks, node = wide
        syncer_bc, client = node()
        svc = FastSyncService(syncer_bc, WIDE_CFG, client)
        before = (sum(c.value for c in fss.PEER_REQUESTS.values()),
                  fss.PEER_REQUEST_SECONDS.value, fss.PEER_BYTES.value,
                  fss.PEER_IN_FLIGHT_SECONDS.value)
        tracer.enable()
        tracer.reset()
        try:
            state = svc.run()
            spans = tracer.snapshot()
        finally:
            tracer.disable()
        assert svc.pool.width() == 3
        # one batch is a request a live peer wide, asked for by run()
        # for this run: the syncer's own number stands
        assert svc.syncer.batch_size == WIDE_CFG.sync.nodes_per_request
        batches = [s for s in spans if s.name == "fastsync.batch"]
        assert max(s.tags["nodes"] for s in batches) == (
            3 * WIDE_CFG.sync.nodes_per_request)
        # the pool's worker threads ended with the run
        assert svc.pool._workers is None
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("fastsync-pool")]
        requests = [s for s in spans if s.name == "fastsync.pool.request"]
        by_peer = {}
        for s in requests:
            by_peer[s.tags["peer"]] = by_peer.get(s.tags["peer"], 0) + 1
            assert s.tags["outcome"] == "ok" and s.tags["bytes"] > 0
            assert 0 < s.tags["blobs"] <= s.tags["hashes"] <= 16
        # every live peer was asked, and more than once
        assert set(by_peer) == {p.remote_pub[:4].hex() for p in client.peers}
        assert min(by_peer.values()) >= 2
        # requests of one batch overlap in time: more than one in flight
        fetches = [s for s in spans if s.name == "fastsync.fetch"]
        in_flight = sum(s.t1 - s.t0 for s in requests) / sum(
            s.t1 - s.t0 for s in fetches)
        assert in_flight > 1.0
        # the process's counters moved by the same requests
        after = (sum(c.value for c in fss.PEER_REQUESTS.values()),
                 fss.PEER_REQUEST_SECONDS.value, fss.PEER_BYTES.value,
                 fss.PEER_IN_FLIGHT_SECONDS.value)
        assert after[0] - before[0] == len(requests)
        assert after[2] - before[2] == sum(s.tags["bytes"] for s in requests)
        assert after[1] > before[1]
        assert after[3] - before[3] == pytest.approx(
            after[1] - before[1], rel=0.2)
        assert {"fastsync.pivot", "fastsync.backfill"} <= {
            s.name for s in spans}
        assert state.downloaded_nodes == svc.syncer.stats.nodes["state"] > 600

    def test_a_mirror_holds_and_verifies_every_stored_trie_node(self, wide):
        from khipu_tpu.storage.device_mirror import DeviceNodeMirror

        blocks, node = wide
        fresh = Blockchain(Storages(), WIDE_CFG)  # what genesis alone has
        fresh.load_genesis(GenesisSpec(alloc=WIDE_ALLOC))
        syncer_bc, client = node()
        had = _trie_nodes(syncer_bc.storages)
        mirror = DeviceNodeMirror({1: 2048, 2: 1024, 3: 1024, 4: 1024})
        svc = FastSyncService(syncer_bc, WIDE_CFG, client, mirror=mirror)
        assert svc.syncer.mirror is mirror
        state = svc.run()
        stored = _trie_nodes(syncer_bc.storages) - had
        assert stored and all(mirror.contains(h) for h in stored)
        assert mirror.resident_count == state.downloaded_nodes
        assert mirror.verify() == 0
        root = blocks[1].header.state_root  # the pivot's: 4 - 2
        assert mirror.contains(root)
        assert mirror.get(root) == syncer_bc.storages.account_node_storage.get(
            root)
        # without a mirror the service behaves as before: none is built
        assert FastSyncService(fresh, WIDE_CFG, client).syncer.mirror is None

    def test_a_stopped_run_checkpoints_and_the_next_service_resumes(
            self, wide):
        from khipu_tpu.sync.fast_sync import FastSyncStateStorage, SyncStopped

        blocks, node = wide
        syncer_bc, client = node()
        svc = FastSyncService(syncer_bc, WIDE_CFG, client)
        calls, inner = [], svc.syncer.fetch

        def fetch(hashes):
            calls.append(len(hashes))
            if len(calls) == 3:
                svc.stop()  # a shutdown, while the third batch is out
            return inner(hashes)

        svc.syncer.fetch = fetch
        with pytest.raises(SyncStopped, match="pending"):
            svc.run()
        first = dict(svc.syncer.stats.nodes)
        assert len(calls) == 3 and 0 < first["state"] < 600
        assert svc.pool._workers is None  # a stopped run closes its pool
        kept = FastSyncStateStorage(
            syncer_bc.storages.app_state.source).get_sync_state()
        # the checkpoint is where the run stopped, not ten batches back
        assert kept.downloaded_nodes == first["state"] and kept.pending
        assert not syncer_bc.storages.app_state.fast_sync_done
        again = FastSyncService(syncer_bc, WIDE_CFG, client)
        state = again.run()
        # nothing was fetched twice: the two runs add up to the trie
        assert (first["state"] + again.syncer.stats.nodes["state"]
                == state.downloaded_nodes)
        assert verify_reachable(
            syncer_bc.storages.account_node_storage,
            syncer_bc.storages.storage_node_storage,
            syncer_bc.storages.evmcode_storage,
            blocks[1].header.state_root).missing == 0
        assert syncer_bc.storages.app_state.fast_sync_done
        assert syncer_bc.best_block_number == 2

    @pytest.mark.parametrize("answer", [
        lambda blobs: [[b"\x01" * 40, b"\x02"]] + blobs[1:],  # nested list
        lambda blobs: b"\xff" * 40,  # a string where the list should be
    ], ids=["nested-list", "not-a-list"])
    def test_a_malformed_answer_blacklists_its_peer_and_the_sync_completes(
            self, wide, answer):
        from khipu_tpu.network.messages import NODE_DATA
        from khipu_tpu.sync import fast_sync_service as fss

        blocks, node = wide
        syncer_bc, client = node()
        # the second peer's answers are no NodeData; the wire carries
        # them as they are (Peer.request checks nothing)
        liar = client.peers[1]
        real = liar.request

        def request(code, body, want, timeout):
            got = real(code, body, want, timeout=timeout)
            if want == ETH_OFFSET + NODE_DATA:
                return answer(got)
            return got

        liar.request = request
        before = (fss.PEER_REQUESTS["garbage"].value,
                  fss.PEERS_BLACKLISTED.value)
        logs = []
        svc = FastSyncService(syncer_bc, WIDE_CFG, client, log=logs.append)
        state = svc.run()  # does not raise: a bad peer, not a bad sync
        assert svc.pool.blacklisted == 1
        assert client.blacklist.is_blacklisted(liar.remote_pub)
        assert fss.PEER_REQUESTS["garbage"].value - before[0] == 1
        assert fss.PEERS_BLACKLISTED.value - before[1] == 1
        assert any("garbage" in line for line in logs)
        assert state.downloaded_nodes > 600
        assert verify_reachable(
            syncer_bc.storages.account_node_storage,
            syncer_bc.storages.storage_node_storage,
            syncer_bc.storages.evmcode_storage,
            blocks[1].header.state_root).missing == 0
        assert syncer_bc.storages.app_state.fast_sync_done

    def test_a_dialled_connection_outlives_its_dial_timeout(self, wide):
        blocks, node = wide
        syncer_bc, client = node(dial_timeout=0.3)
        time.sleep(0.8)  # idle for longer than the dial was given
        assert [p.alive for p in client.peers] == [True] * 3
        svc = FastSyncService(syncer_bc, WIDE_CFG, client)
        assert svc.choose_pivot().number == 2


def test_the_board_hands_its_fast_sync_the_mirror_it_owns(tmp_path):
    from khipu_tpu.service_board import ServiceBoard

    rows = ((1, 2048), (4, 1024))
    cfg = dataclasses.replace(
        WIDE_CFG, sync=dataclasses.replace(
            WIDE_CFG.sync, fast_sync_mirror_rows=rows))
    for config, want in ((cfg, dict(rows)), (WIDE_CFG, None)):
        board = ServiceBoard(config, GenesisSpec(alloc=ALLOC))
        try:
            with pytest.raises(RuntimeError, match="start_network"):
                board.start_fast_sync()
            board.start_network(port=0)
            svc = board.start_fast_sync()
            if want is None:
                assert svc.mirror is None and board.fast_sync_mirror is None
                continue
            assert svc.mirror is board.fast_sync_mirror
            assert svc.mirror.capacity_by_class == want
            # a sync started again finds what the last one admitted
            assert board.start_fast_sync().mirror is svc.mirror
        finally:
            board.shutdown()


class _FakePeer:
    """Answers a node-data request with the blobs it holds, and counts."""

    def __init__(self, i, blobs):
        self.remote_pub = i.to_bytes(4, "big") * 16
        self.alive, self.blobs, self.requests = True, blobs, 0

    def request(self, code, body, want, timeout):
        self.requests += 1
        return [self.blobs[h] for h in body if h in self.blobs]


def test_more_peers_than_the_cap_are_asked_no_more_than_the_cap_at_once():
    from types import SimpleNamespace

    from khipu_tpu.base.crypto.keccak import keccak256
    from khipu_tpu.sync import fast_sync_service as fss

    cap, per_request = fss.MAX_CONCURRENT_REQUESTS, 4
    assert cap == 50
    values = [b"node-%d" % i for i in range((cap + 10) * per_request)]
    blobs = {keccak256(v): v for v in values}
    peers = [_FakePeer(i, blobs) for i in range(cap + 3)]
    manager = SimpleNamespace(
        peers=peers,
        blacklist=SimpleNamespace(is_blacklisted=lambda pub: False))
    pool = fss.PeerFetchPool(manager, nodes_per_request=per_request)
    try:
        # 53 live peers: the width, and so a syncer batch, stops at 50
        assert pool.width() == cap
        assert pool.fetch_nodes(list(blobs)) == blobs
    finally:
        pool.close()
    asked = [p for p in peers if p.requests]
    # 60 chunks over one round: 50 peers took them, 3 stood by
    assert len(asked) == cap
    assert sorted(p.requests for p in asked) == [1] * 40 + [2] * 10
    # a pool with no live peer is still one request wide
    manager.peers = []
    assert pool.width() == 1
