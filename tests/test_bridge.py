"""gRPC bridge tests (SURVEY §2.9 north-star channel): block batches
over real gRPC -> executed, persisted, roots returned; invalid blocks
rejected with a status error."""

import pytest

from khipu_tpu.base.crypto.secp256k1 import (
    privkey_to_pubkey,
    pubkey_to_address,
)
from khipu_tpu.config import fixture_config
from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
from khipu_tpu.domain.transaction import Transaction, sign_transaction
from khipu_tpu.storage.storages import Storages
from khipu_tpu.sync.chain_builder import ChainBuilder

grpc = pytest.importorskip("grpc")

from khipu_tpu.bridge import BridgeClient, BridgeServer  # noqa: E402

CFG = fixture_config(chain_id=1)
KEYS = [(i + 1).to_bytes(32, "big") for i in range(3)]
ADDRS = [pubkey_to_address(privkey_to_pubkey(k)) for k in KEYS]
ALLOC = {a: 10**21 for a in ADDRS}


def build_blocks(n=4):
    builder = ChainBuilder(
        Blockchain(Storages(), CFG), CFG, GenesisSpec(alloc=ALLOC)
    )
    return [
        builder.add_block(
            [sign_transaction(
                Transaction(i, 10**9, 21000, ADDRS[1], 5), KEYS[0],
                chain_id=1,
            )],
            coinbase=b"\xaa" * 20,
        )
        for i in range(n)
    ]


@pytest.fixture()
def bridge():
    bc = Blockchain(Storages(), CFG)
    bc.load_genesis(GenesisSpec(alloc=ALLOC))
    server = BridgeServer(bc, CFG)
    port = server.start()
    client = BridgeClient(f"127.0.0.1:{port}")
    yield client, bc
    client.close()
    server.stop()


class TestBridge:
    def test_ping(self, bridge):
        client, _ = bridge
        assert client.ping(b"khipu") == b"khipu"

    def test_execute_batch_and_query(self, bridge):
        client, bc = bridge
        blocks = build_blocks(4)
        results = client.execute_blocks(blocks)
        assert [n for n, _ in results] == [1, 2, 3, 4]
        for block, (n, root) in zip(blocks, results):
            assert root == block.header.state_root
        # server persisted the chain
        n, h = client.best_block()
        assert n == 4 and h == blocks[-1].hash
        assert client.get_state_root(4) == blocks[-1].header.state_root
        assert bc.get_account(ADDRS[1], blocks[-1].header.state_root)

    def test_incremental_batches(self, bridge):
        client, _ = bridge
        blocks = build_blocks(4)
        client.execute_blocks(blocks[:2])
        client.execute_blocks(blocks[2:])
        assert client.best_block()[0] == 4

    def test_one_driver_serves_every_batch(self):
        """Device state outlives a batch: the server builds ONE
        ReplayDriver on the first ExecuteBlocks and keeps it, so the
        device mirror is allocated once and the adaptive controller
        keeps one history (a controller per call restarted its dwell
        and flip count every few windows)."""
        import dataclasses

        from khipu_tpu.config import SyncConfig
        from khipu_tpu.sync.adaptive import ADAPTIVE_GAUGES

        cfg = dataclasses.replace(
            CFG, sync=SyncConfig(commit_window_blocks=2)
        )
        bc = Blockchain(Storages(), cfg)
        bc.load_genesis(GenesisSpec(alloc=ALLOC))
        server = BridgeServer(bc, cfg, device_commit=True)
        client = BridgeClient(f"127.0.0.1:{server.start()}")
        try:
            blocks = build_blocks(8)
            assert server._driver is None  # nothing built until asked
            client.execute_blocks(blocks[:4])
            driver = server._driver
            ctrl, mirror = driver._adaptive, driver._mirror
            assert ctrl is not None and ctrl.windows == 2
            client.execute_blocks(blocks[4:])
            assert server._driver is driver
            assert driver._adaptive is ctrl and driver._mirror is mirror
            assert ctrl.windows == 4  # one history across both batches
            assert ADAPTIVE_GAUGES["windows_observed"] == 4
            assert client.best_block()[0] == 8
        finally:
            client.close()
            server.stop()

    def test_server_keeps_the_replay_stats_of_its_batches(self):
        """The per-phase split of each ExecuteBlocks batch stays with
        the server (bounded); a refused batch leaves none; a tap on
        ``driver.replay`` (the benchmark's) still sees every batch and
        the server keeps what the tap hands back."""
        import dataclasses

        from khipu_tpu.bridge import REPLAY_STATS_KEPT
        from khipu_tpu.domain.block import Block

        bc = Blockchain(Storages(), CFG)
        bc.load_genesis(GenesisSpec(alloc=ALLOC))
        server = BridgeServer(bc, CFG)
        client = BridgeClient(f"127.0.0.1:{server.start()}")
        try:
            blocks = build_blocks(5)
            assert server.replay_stats() == []
            client.execute_blocks(blocks[:3])
            (first,) = server.replay_stats()
            assert first.blocks == 3 and first.seconds > 0
            tapped = []
            inner = server._driver.replay

            def replay(batch):
                tapped.append(inner(batch))
                return tapped[-1]

            server._driver.replay = replay
            client.execute_blocks(blocks[3:4])
            bad = Block(dataclasses.replace(
                blocks[4].header, state_root=b"\x13" * 32), blocks[4].body)
            with pytest.raises(grpc.RpcError):
                client.execute_blocks([bad])
            kept = server.replay_stats()
            assert [s.blocks for s in kept] == [3, 1]
            assert kept[0] is first and kept[1] is tapped[0]
            assert server._replay_stats.maxlen == REPLAY_STATS_KEPT
            # its reader: GetMetrics serves the slowest kept batch with
            # its phase split, so an outlier names its phase afterwards
            slow = max(kept, key=lambda s: s.seconds)
            # (the per-block path of this config books no phases)
            slow.phases.update(execute=0.25, collect=0.5)
            fams = {name: {tuple(sorted(lb.items())): v
                           for lb, v in samples}
                    for name, (_kind, _help, samples)
                    in client.get_metrics().items()
                    if name.startswith("khipu_bridge_replay_")}
            assert fams == {
                "khipu_bridge_replay_batches_kept": {(): 2},
                "khipu_bridge_replay_batch_seconds": {
                    (("which", "last"),): kept[-1].seconds,
                    (("which", "slowest"),): slow.seconds,
                },
                "khipu_bridge_replay_slowest_phase_seconds": {
                    (("phase", "execute"),): 0.25,
                    (("phase", "collect"),): 0.5,
                },
            }
        finally:
            client.close()
            server.stop()

    def test_invalid_block_aborts(self, bridge):
        import dataclasses

        from khipu_tpu.domain.block import Block

        client, _ = bridge
        blocks = build_blocks(1)
        bad = Block(
            dataclasses.replace(blocks[0].header, state_root=b"\x13" * 32),
            blocks[0].body,
        )
        with pytest.raises(grpc.RpcError) as e:
            client.execute_blocks([bad])
        assert e.value.code() == grpc.StatusCode.FAILED_PRECONDITION
        assert client.best_block()[0] == 0  # nothing persisted

    def test_malformed_batch_rejected(self, bridge):
        client, _ = bridge
        with pytest.raises(grpc.RpcError) as e:
            client._call("ExecuteBlocks", b"\xff\xff not rlp")
        assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT

    def test_unknown_root_empty(self, bridge):
        client, _ = bridge
        assert client.get_state_root(99) is None

    def test_stream_node_data_paged_and_range_filtered(self, bridge):
        """ISSUE 11: the rebalance bridge RPC — cursor-paged key
        streaming filtered by ring point ranges, values verifiable by
        content address."""
        from khipu_tpu.base.crypto.keccak import keccak256
        from khipu_tpu.cluster.ring import RING_SIZE, _point

        client, _ = bridge
        nodes = {
            keccak256(b"streamed node %d" % i): b"streamed node %d" % i
            for i in range(20)
        }
        assert client.put_node_data(nodes) == 20
        # full-ring range, small pages: every key comes back exactly
        # once, in cursor order, bit-exact
        got = {}
        cursor, pages = b"", 0
        while True:
            done, cursor, pairs = client.stream_node_data(
                [(0, RING_SIZE)], cursor, count=6
            )
            pages += 1
            for h, v in pairs:
                assert keccak256(v) == h
                assert h not in got
                got[h] = v
            if done:
                break
        assert pages >= 4  # 20 keys / 6 per page actually paged
        for h, v in nodes.items():
            assert got[h] == v  # superset: genesis nodes stream too
        # a half-ring range returns exactly the keys whose point falls
        # inside it
        half = [(0, RING_SIZE // 2)]
        done, _, pairs = client.stream_node_data(half, b"", count=1024)
        assert done
        in_half = {h for h in got if _point(h) < RING_SIZE // 2}
        assert {h for h, _ in pairs} == in_half


SERVER_SCRIPT = r"""
import sys
sys.path.insert(0, {repo!r})
from khipu_tpu.config import fixture_config
from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
from khipu_tpu.domain.transaction import Transaction, sign_transaction
from khipu_tpu.storage.storages import Storages
from khipu_tpu.sync.chain_builder import ChainBuilder
from khipu_tpu.base.crypto.secp256k1 import privkey_to_pubkey, pubkey_to_address
from khipu_tpu.bridge import BridgeServer

CFG = fixture_config(chain_id=1)
KEYS = [(i + 1).to_bytes(32, "big") for i in range(3)]
ADDRS = [pubkey_to_address(privkey_to_pubkey(k)) for k in KEYS]
ALLOC = {{a: 10**21 for a in ADDRS}}
bc = Blockchain(Storages(), CFG)
builder = ChainBuilder(bc, CFG, GenesisSpec(alloc=ALLOC))
for i in range(4):
    builder.add_block(
        [sign_transaction(Transaction(i, 10**9, 21000, ADDRS[1], 5),
                          KEYS[0], chain_id=1)],
        coinbase=b"\xaa" * 20,
    )
server = BridgeServer(bc, CFG)
port = server.start()
root = bc.get_header_by_number(4).state_root
print(f"{{port}} {{root.hex()}}", flush=True)
sys.stdin.readline()  # parent closes stdin to stop us
"""


class TestServedNodeCache:
    def test_cross_process_heal(self):
        """P6 (DistributedNodeStorage role): a SEPARATE PROCESS serves
        its node cache over the bridge's GetNodeData; this process,
        with an EMPTY local store, walks the remote state trie through
        RemoteReadThroughNodeStorage — every node heals across the
        process boundary, content-address verified."""
        import os
        import subprocess
        import sys

        from khipu_tpu.storage.datasource import MemoryKeyValueDataSource
        from khipu_tpu.storage.node_storage import NodeStorage
        from khipu_tpu.storage.remote import RemoteReadThroughNodeStorage
        from khipu_tpu.trie.mpt import MerklePatriciaTrie
        from khipu_tpu.domain.account import Account, address_key

        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        proc = subprocess.Popen(
            [sys.executable, "-c", SERVER_SCRIPT.format(repo=repo)],
            stdout=subprocess.PIPE,
            stdin=subprocess.PIPE,
            text=True,
        )
        try:
            line = proc.stdout.readline().split()
            port, root = int(line[0]), bytes.fromhex(line[1])
            client = BridgeClient(f"127.0.0.1:{port}")
            local = RemoteReadThroughNodeStorage(
                NodeStorage(MemoryKeyValueDataSource()),
                client.get_node_data,
            )
            trie = MerklePatriciaTrie(local, root_hash=root)
            raw = trie.get(address_key(ADDRS[1]))
            assert raw is not None, "remote account unreadable"
            acc = Account.decode(raw)
            assert acc.balance == 10**21 + 4 * 5
            assert local.healed > 0  # nodes really crossed processes
            # a second read serves locally (healed nodes persisted)
            healed_before = local.healed
            assert trie.get(address_key(ADDRS[1])) == raw
            assert local.healed == healed_before
            client.close()
        finally:
            proc.stdin.close()
            proc.wait(timeout=10)
