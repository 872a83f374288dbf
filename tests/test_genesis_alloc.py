"""A genesis whose alloc carries code and storage (geth's shape), and
the always-on counters of where node reads are answered from.

``load_genesis`` bulk-builds one storage trie per contract; what it
stores must be what the incremental path stores (``BlockWorldState``
writes of the same accounts, one by one, then persist), byte for byte:
the state root, each contract's storage root, and what a node serves
from it over HTTP."""

import dataclasses
import json
import urllib.request

import numpy as np
import pytest

from khipu_tpu.config import fixture_config
from khipu_tpu.domain.account import (
    EMPTY_CODE_HASH,
    EMPTY_STORAGE_ROOT,
    Account,
)
from khipu_tpu.domain.blockchain import (
    Blockchain,
    GenesisAccount,
    GenesisSpec,
)
from khipu_tpu.observability import trace
from khipu_tpu.observability.registry import REGISTRY
from khipu_tpu.service_board import ServiceBoard
from khipu_tpu.storage.datasource import MemoryNodeDataSource
from khipu_tpu.storage.node_storage import NodeStorage
from khipu_tpu.storage.storages import Storages
from khipu_tpu.trie.mpt import EMPTY_TRIE_HASH

CFG = fixture_config(chain_id=1)
SEEDS = [7, 2_147_483_777, 4_000_000_007]
# the root the parent of this change gives {bytes([i + 1]) * 20:
# 10**18 + i for i in range(40)}, and the genesis hash over it
INT_ALLOC_ROOT = (
    "48f02fe2d03e1811fb536b713e6d298f4e1591e20a70dca13101344c1bbb0e44")
INT_ALLOC_HASH = (
    "920957bd76e430b4ca520f183cf12fd6b34f9b1cad5a3499ec629ff744b955da")


def seeded_alloc(seed):
    """Plain balances, contracts with storage (one slot of each holding
    zero), a contract with code and no storage, an account with storage
    and no code, and one with a nonce of its own."""
    rng = np.random.default_rng(seed)
    address = lambda: rng.integers(0, 256, 20, dtype=np.uint8).tobytes()
    word = lambda: int.from_bytes(
        rng.integers(0, 256, int(rng.integers(1, 33)),
                     dtype=np.uint8).tobytes(), "big") or 1
    alloc = {address(): 10**18 + int(rng.integers(0, 1 << 40))
             for _ in range(60)}
    for n_slots in (1, 3, 40, 300):
        storage = {word(): word() for _ in range(n_slots)}
        storage[word()] = 0  # a zero value is an absent slot
        alloc[address()] = GenesisAccount(
            balance=int(rng.integers(0, 1 << 60)),
            code=rng.integers(0, 256, 50, dtype=np.uint8).tobytes(),
            storage=storage)
    alloc[address()] = GenesisAccount(code=b"\x60\x00\x00")
    alloc[address()] = GenesisAccount(balance=5, storage={1: 2})
    alloc[address()] = GenesisAccount(balance=9, nonce=7)
    return alloc


def incremental(alloc):
    """The same accounts through ``BlockWorldState``; (storages, root)."""
    storages = Storages()
    world = Blockchain(storages, CFG).get_world_state(EMPTY_TRIE_HASH)
    start = CFG.blockchain.account_start_nonce
    for addr, entry in alloc.items():
        if isinstance(entry, int):
            world.save_account(addr, Account(nonce=start, balance=entry))
            continue
        world.save_account(addr, Account(
            nonce=start if entry.nonce is None else entry.nonce,
            balance=entry.balance))
        if entry.code:
            world.save_code(addr, entry.code)
        for slot, value in entry.storage.items():
            world.save_storage(addr, slot, value)
    root = world.persist(storages.account_node_storage,
                         storages.storage_node_storage,
                         storages.evmcode_storage)
    return storages, root


def rpc(port, method, *params):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}",
        data=json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                         "params": list(params)}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())["result"]


@pytest.mark.parametrize("seed", SEEDS)
def test_bulk_genesis_stores_what_the_incremental_path_stores(seed):
    alloc = seeded_alloc(seed)
    chain = Blockchain(Storages(), CFG)
    genesis = chain.load_genesis(GenesisSpec(alloc=alloc))
    theirs, root = incremental(alloc)
    assert genesis.header.state_root == root
    oracle = Blockchain(theirs, CFG)
    for addr, entry in alloc.items():
        got = chain.get_account(addr, root)
        assert got == oracle.get_account(addr, root)
        if isinstance(entry, int):
            assert got.storage_root == EMPTY_STORAGE_ROOT
            assert got.code_hash == EMPTY_CODE_HASH
    # every node the incremental path stored is there, byte for byte
    for mine, other in ((chain.storages.storage_node_storage,
                         theirs.storage_node_storage),
                        (chain.storages.account_node_storage,
                         theirs.account_node_storage)):
        assert other.source.count > 0
        for key in other.source.keys():
            assert mine.get(key) == other.source.get(key)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_node_serves_genesis_storage_and_code(seed):
    alloc = seeded_alloc(seed)
    board = ServiceBoard(CFG, GenesisSpec(alloc=alloc))
    try:
        port = board.start_rpc(port=0)
        hx = lambda b: "0x" + b.hex()
        for addr, entry in alloc.items():
            if isinstance(entry, int):
                assert int(rpc(port, "eth_getBalance", hx(addr),
                               "latest"), 16) == entry
                continue
            assert rpc(port, "eth_getCode", hx(addr),
                       "latest") == hx(entry.code)
            for slot, value in list(entry.storage.items())[:12]:
                assert int(rpc(port, "eth_getStorageAt", hx(addr),
                               hex(slot), "latest"), 16) == value
            assert int(rpc(port, "eth_getTransactionCount", hx(addr),
                           "latest"), 16) == (entry.nonce or 0)
    finally:
        board.shutdown()


def test_an_int_alloc_still_gives_todays_root():
    alloc = {bytes([i + 1]) * 20: 10**18 + i for i in range(40)}
    genesis = Blockchain(Storages(), CFG).load_genesis(
        GenesisSpec(alloc=alloc))
    assert genesis.header.state_root.hex() == INT_ALLOC_ROOT
    assert genesis.hash.hex() == INT_ALLOC_HASH
    # a record that is only a balance is the same leaf
    as_records = {a: GenesisAccount(balance=b) for a, b in alloc.items()}
    again = Blockchain(Storages(), CFG).load_genesis(
        GenesisSpec(alloc=as_records))
    assert again.hash == genesis.hash


def test_zero_slots_are_absent_and_code_needs_no_storage():
    addr, bare = b"\x11" * 20, b"\x22" * 20
    chain = Blockchain(Storages(), CFG)
    genesis = chain.load_genesis(GenesisSpec(alloc={
        addr: GenesisAccount(storage={1: 0, 2: 0}),
        bare: GenesisAccount(code=b"\x00"),
    }))
    root = genesis.header.state_root
    assert chain.get_account(addr, root).storage_root == EMPTY_STORAGE_ROOT
    got = chain.get_account(bare, root)
    assert got.storage_root == EMPTY_STORAGE_ROOT and got.has_code
    world = chain.get_world_state(root)
    assert world.get_code(bare) == b"\x00"
    assert world.get_storage(addr, 1) == 0


def test_genesis_load_is_one_span_with_three_children():
    ring = trace.Tracer()
    ring.enable(1 << 10)
    alloc = seeded_alloc(1)
    with trace.use_tracer(ring):
        Blockchain(Storages(), CFG).load_genesis(GenesisSpec(alloc=alloc))
    spans = {s.name: s for s in ring.snapshot()}
    load = spans["genesis.load"]
    contracts = [e for e in alloc.values() if not isinstance(e, int)]
    assert load.tags["accounts"] == len(alloc)
    assert load.tags["contracts"] == len(contracts)
    assert load.tags["slots"] == sum(
        sum(1 for v in e.storage.values() if v) for e in contracts)
    assert load.tags["nodes"] > len(alloc)
    for child in ("genesis.storage_tries", "genesis.account_trie",
                  "genesis.store"):
        assert spans[child].parent == load.sid


# ------------------------------------------------ where reads come from


class FakeMirror:
    def __init__(self, held):
        self.held = held

    def get(self, key):
        return self.held.get(key)


def test_node_storage_counts_where_a_read_was_answered_from():
    source = MemoryNodeDataSource()
    store = NodeStorage(source, cache_size=2)
    store.update([], {b"a" * 32: b"A", b"b" * 32: b"B", b"c" * 32: b"C"})
    store.mirror = FakeMirror({b"m" * 32: b"M"})
    assert store.get(b"c" * 32) == b"C"       # cached by the write
    assert store.source_seconds == 0.0        # nothing on the hit path
    assert store.get(b"a" * 32) == b"A"       # evicted: from the source
    assert store.get(b"a" * 32) == b"A"       # and cached by that read
    assert store.get(b"m" * 32) == b"M"       # only the mirror has it
    assert store.get(b"z" * 32) is None
    samples = {(labels["from"]): value
               for name, _kind, labels, value in store.registry_samples("x")
               if name == "khipu_nodestore_reads_total"}
    assert samples == {"cache": 2, "source": 1, "mirror": 1, "absent": 1}
    assert store.source_seconds > 0.0
    assert store._cache.read_count == 5


def test_the_registry_serves_the_read_counters_of_the_nodes_storages():
    alloc = {bytes([i + 1]) * 20: 1 + i for i in range(64)}
    cfg = dataclasses.replace(CFG, db=dataclasses.replace(
        CFG.db, cache_size=4))
    board = ServiceBoard(cfg, genesis=GenesisSpec(alloc=alloc))
    try:
        storages, chain = board.storages, board.blockchain
        beside = Storages(cache_size=4)  # a builder's store takes no slot
        Blockchain(beside, CFG).load_genesis(GenesisSpec(alloc=alloc))
        root = chain.get_header_by_number(0).state_root
        for addr in alloc:
            assert chain.get_account(addr, root) is not None
        snap = REGISTRY.snapshot()
        reads = snap["khipu_nodestore_reads_total"]
        assert reads['from="source",store="account"'] == \
            storages.account_node_storage.source_reads > 0
        assert reads['from="cache",store="account"'] == \
            storages.account_node_storage._cache.hits
        assert reads['from="absent",store="storage"'] == 0
        assert snap["khipu_nodestore_source_seconds_total"][
            'store="account"'] > 0
        text = REGISTRY.prometheus_text()
        assert ('khipu_nodestore_reads_total{from="source",'
                'store="account"}') in text
        assert ("# TYPE khipu_nodestore_source_seconds_total counter"
                in text)
    finally:
        board.shutdown()
    # a stopped node's stores are let go of, not served on
    assert "khipu_nodestore_reads_total" not in REGISTRY.snapshot()


def test_fused_dispatch_runs_as_many_rounds_as_the_dag_is_deep():
    from khipu_tpu.trie.bulk import bulk_build

    ring = trace.Tracer()
    ring.enable(1 << 10)
    pairs = [(bytes([i]) * 32, b"v" * 40) for i in range(64)]
    with trace.use_tracer(ring):
        root, _ = bulk_build(pairs, fused=True)
    assert root == bulk_build(pairs)[0]
    (dispatch,) = [s for s in ring.snapshot() if s.name == "fused.dispatch"]
    # root branch, four branches, leaves: the trip count is the DAG's
    # depth (the program's last input), not a power-of-two bucket of it
    assert dispatch.tags["rounds"] == 3
