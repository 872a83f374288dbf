"""Block-window commit tests (ledger/window.py): N blocks, one batched
level-synchronous resolve, per-block root checks — the north-star
commit pipeline (BASELINE configs #1/#4)."""

import dataclasses

import pytest

from khipu_tpu.base.crypto.secp256k1 import (
    privkey_to_pubkey,
    pubkey_to_address,
)
from khipu_tpu.config import SyncConfig, fixture_config
from khipu_tpu.domain.block import Block
from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
from khipu_tpu.domain.transaction import (
    Transaction,
    contract_address,
    sign_transaction,
)
from khipu_tpu.ledger.window import WindowMismatch
from khipu_tpu.storage.compactor import verify_reachable
from khipu_tpu.storage.storages import Storages
from khipu_tpu.sync.chain_builder import ChainBuilder
from khipu_tpu.sync.replay import ReplayDriver

CFG = fixture_config(chain_id=1)
KEYS = [(i + 1).to_bytes(32, "big") for i in range(4)]
ADDRS = [pubkey_to_address(privkey_to_pubkey(k)) for k in KEYS]
ETH = 10**18
MINER = b"\xaa" * 20

RUNTIME = bytes.fromhex("60005460005260206000f3")
_SS = bytes.fromhex("602a600055")
_COPY = bytes(
    [0x60, len(RUNTIME), 0x60, len(_SS) + 12, 0x60, 0, 0x39,
     0x60, len(RUNTIME), 0x60, 0, 0xF3]
)
INIT = _SS + _COPY + RUNTIME


def tx(i, nonce, to, value, gas=21000, payload=b""):
    return sign_transaction(
        Transaction(nonce, 10**9, gas, to, value, payload),
        KEYS[i], chain_id=1,
    )


@pytest.fixture(scope="module")
def chain():
    """5 blocks: deploy, cross-block call, second deploy + transfers."""
    builder = ChainBuilder(
        Blockchain(Storages(), CFG), CFG,
        GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}),
    )
    blocks = [
        builder.add_block(
            [tx(0, 0, None, 0, gas=300_000, payload=INIT)], coinbase=MINER
        )
    ]
    caddr = contract_address(ADDRS[0], 0)
    blocks.append(
        builder.add_block(
            [tx(0, 1, caddr, 0, gas=100_000), tx(1, 0, ADDRS[2], 123)],
            coinbase=MINER,
        )
    )
    blocks.append(
        builder.add_block(
            [tx(0, 2, None, 1000, gas=300_000, payload=INIT),
             tx(1, 1, ADDRS[3], 7)],
            coinbase=MINER,
        )
    )
    blocks.append(builder.add_block([tx(2, 0, ADDRS[0], 1)], coinbase=MINER))
    blocks.append(builder.add_block([tx(2, 1, ADDRS[0], 1)], coinbase=MINER))
    return blocks, caddr


def window_cfg(w, parallel=True):
    return dataclasses.replace(
        CFG, sync=SyncConfig(parallel_tx=parallel, commit_window_blocks=w)
    )


class TestWindowedReplay:
    def test_window1_device_path_uses_hasher(self, chain):
        """window=1 replay with a device hasher: the in-place root
        validation inside execute_block must flush with THAT hasher —
        not silently fall back to the eager host path (regression: the
        validate-then-persist fusion bypassed the batched commit)."""
        from khipu_tpu.trie.bulk import host_hasher

        calls = [0]

        def counting_hasher(msgs):
            calls[0] += 1
            return host_hasher(msgs)

        blocks, caddr = chain
        cfg = window_cfg(1)
        bc = Blockchain(Storages(), cfg)
        bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
        driver = ReplayDriver(bc, cfg, device_commit=True)
        driver.hasher = counting_hasher
        stats = driver.replay(blocks)
        assert stats.blocks == 5
        assert calls[0] > 0, "batched hasher never ran on the w=1 path"
        assert bc.get_header_by_number(5).hash == blocks[-1].hash

    @pytest.mark.parametrize("window", [2, 3, 5, 8])
    def test_windowed_equals_per_block(self, chain, window):
        """Any window size produces the identical chain state as the
        eager per-block path — and the persisted stores are complete
        (no node stranded in the staged dicts)."""
        blocks, caddr = chain
        cfg = window_cfg(window)
        bc = Blockchain(Storages(), cfg)
        bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
        stats = ReplayDriver(bc, cfg).replay(blocks)
        assert stats.blocks == 5
        head = blocks[-1].header
        assert bc.get_header_by_number(5).hash == blocks[-1].hash
        # persisted-store-only reads (no window session alive)
        fresh = Blockchain(bc.storages, cfg)
        world = fresh.get_world_state(head.state_root)
        assert world.get_storage(caddr, 0) == 42
        assert world.get_code(caddr) == RUNTIME
        report = verify_reachable(
            bc.storages.account_node_storage,
            bc.storages.storage_node_storage,
            bc.storages.evmcode_storage,
            head.state_root,
        )
        assert report.missing == 0

    def test_cross_block_reads_inside_window(self, chain):
        """Block 2 calls the contract block 1 deployed, with both inside
        ONE open window — the staged read-through is load-bearing."""
        blocks, _ = chain
        cfg = window_cfg(5, parallel=False)
        bc = Blockchain(Storages(), cfg)
        bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
        ReplayDriver(bc, cfg).replay(blocks)  # single 5-block window
        assert bc.get_header_by_number(5).hash == blocks[-1].hash

    def test_mismatch_pinpoints_block(self, chain):
        blocks, _ = chain
        cfg = window_cfg(4)
        bad = Block(
            dataclasses.replace(blocks[2].header, state_root=b"\x13" * 32),
            blocks[2].body,
        )
        bc = Blockchain(Storages(), cfg)
        bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
        with pytest.raises(WindowMismatch) as e:
            ReplayDriver(bc, cfg, validate_headers=False).replay(
                [blocks[0], blocks[1], bad]
            )
        assert e.value.number == 3

    def test_pre_byzantium_window_rejected(self, chain):
        blocks, _ = chain
        cfg = dataclasses.replace(
            fixture_config(chain_id=1, byzantium_block=10**9),
            sync=SyncConfig(commit_window_blocks=4),
        )
        bc = Blockchain(Storages(), cfg)
        bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
        with pytest.raises(ValueError, match="Byzantium"):
            ReplayDriver(bc, cfg, validate_headers=False).replay(blocks[:2])

    def test_balance_accounting_through_windows(self, chain):
        blocks, _ = chain
        cfg = window_cfg(3)
        bc = Blockchain(Storages(), cfg)
        bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
        ReplayDriver(bc, cfg).replay(blocks)
        root = blocks[-1].header.state_root
        # ADDRS[2]: +123 (block 2), then sent 1 wei twice with fees
        acc = bc.get_account(ADDRS[2], root)
        assert acc.balance == 1000 * ETH + 123 - 2 * (21000 * 10**9 + 1)
        assert acc.nonce == 2

    def test_epoch_reset_and_staged_prune(self, chain):
        """Pipelined session hygiene: collected windows drop their
        staged encodings (reads fall back through the resolved map to
        the persisted store), and the epoch reset rebuilds the session
        committer mid-replay without changing any result."""
        blocks, caddr = chain
        cfg = window_cfg(2)
        bc = Blockchain(Storages(), cfg)
        bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
        driver = ReplayDriver(bc, cfg)
        driver.session_epoch_blocks = 2  # reset after every window
        stats = driver.replay(blocks)
        assert stats.blocks == 5
        assert bc.get_header_by_number(5).hash == blocks[-1].hash
        # persisted-store-only reads still see everything
        fresh = Blockchain(bc.storages, cfg)
        world = fresh.get_world_state(blocks[-1].header.state_root)
        assert world.get_storage(caddr, 0) == 42
        report = verify_reachable(
            bc.storages.account_node_storage,
            bc.storages.storage_node_storage,
            bc.storages.evmcode_storage,
            blocks[-1].header.state_root,
        )
        assert report.missing == 0

    def test_collect_prunes_session_memory(self, chain):
        """After every window is persisted the committer's staged dict
        holds nothing (all placeholders resolved + pruned). Pruning now
        lands at the end of the persist stage (the staged collector
        split collect into rootcheck/admit + persist + save)."""
        from khipu_tpu.ledger.window import WindowCommitter

        blocks, _ = chain
        cfg = window_cfg(5)
        bc = Blockchain(Storages(), cfg)
        bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
        seen = []
        orig = WindowCommitter.persist

        def spy(self, job):
            r = orig(self, job)
            seen.append((len(self._staged), len(self._resolved_global)))
            return r

        WindowCommitter.persist = spy
        try:
            ReplayDriver(bc, cfg).replay(blocks)
        finally:
            WindowCommitter.persist = orig
        assert seen, "persist never ran"
        staged_left, resolved = seen[-1]
        assert staged_left == 0
        assert resolved > 0

    def test_mismatch_after_pipeline_overlap_persists_nothing(
        self, chain
    ):
        """A root mismatch in window N surfaces at collect(N) — after
        window N+1 already executed optimistically. Nothing from either
        window may reach the persisted block storage."""
        blocks, _ = chain
        cfg = window_cfg(2)
        bad = Block(
            dataclasses.replace(blocks[1].header, state_root=b"\x55" * 32),
            blocks[1].body,
        )
        bc = Blockchain(Storages(), cfg)
        bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
        with pytest.raises(WindowMismatch) as e:
            ReplayDriver(bc, cfg, validate_headers=False).replay(
                [blocks[0], bad, blocks[2], blocks[3]]
            )
        assert e.value.number == 2
        assert bc.get_header_by_number(1) is None
        assert bc.get_header_by_number(2) is None


def pipeline_cfg(w, depth, parallel=True):
    # adaptive_commit off: these tests assert the CONFIGURED commit
    # path and a fixed pipeline depth; the adaptive controller would
    # (correctly) fall back to host commit on the CPU backend and
    # resize the depth, defeating the assertions
    return dataclasses.replace(
        CFG,
        sync=SyncConfig(
            parallel_tx=parallel, commit_window_blocks=w,
            pipeline_depth=depth, adaptive_commit=False,
        ),
    )


def _fresh_chain(cfg):
    bc = Blockchain(Storages(), cfg)
    bc.load_genesis(GenesisSpec(alloc={a: 1000 * ETH for a in ADDRS}))
    return bc


class _DictStore:
    """Capture sink for compact(): records the reachable subgraph."""

    def __init__(self):
        self.nodes = {}

    def update(self, removes, upserts):
        self.nodes.update(upserts)


def _reachable(storages, root):
    """hash -> encoding of every node reachable from ``root`` — the
    bit-exactness comparand (two stores may differ in DEAD nodes the
    window split left behind; the live subgraph must be identical)."""
    from khipu_tpu.storage.compactor import compact

    acc, sto, code = _DictStore(), _DictStore(), _DictStore()
    report = compact(
        storages.account_node_storage,
        storages.storage_node_storage,
        storages.evmcode_storage,
        root, acc, sto, code,
    )
    assert report.missing == 0
    return acc.nodes, sto.nodes, code.nodes


class TestDeepPipeline:
    """Seal/collect ordering under the background collector
    (sync/replay._WindowCollector + ledger/window resolved-input
    tiles): depth sweep, cross-window bit-exactness, abort drains."""

    @pytest.mark.parametrize("depth", [1, 2, 4])
    def test_pipeline_depth_equals_per_block(self, chain, depth):
        """Any pipeline depth yields the identical persisted chain —
        collects run FIFO on the collector thread, roots all gate."""
        blocks, caddr = chain
        cfg = pipeline_cfg(2, depth)
        bc = _fresh_chain(cfg)
        stats = ReplayDriver(bc, cfg).replay(blocks)
        assert stats.blocks == 5
        assert bc.get_header_by_number(5).hash == blocks[-1].hash
        assert 0.0 <= stats.pipeline_occupancy <= 1.0
        assert "collect_bg" in stats.phases
        world = bc.get_world_state(blocks[-1].header.state_root)
        assert world.get_storage(caddr, 0) == 42
        report = verify_reachable(
            bc.storages.account_node_storage,
            bc.storages.storage_node_storage,
            bc.storages.evmcode_storage,
            blocks[-1].header.state_root,
        )
        assert report.missing == 0
        from khipu_tpu.sync.replay import PIPELINE_GAUGES

        assert PIPELINE_GAUGES["depth"] == depth
        assert PIPELINE_GAUGES["in_flight"] == 0

    @pytest.mark.slow  # ~60 s of XLA compile on a 1-core CPU host
    def test_cross_window_tiles_bit_exact_vs_finalize(self):
        """seal(N+1) while window N is STILL IN FLIGHT: refs into N
        ride the fused dispatch as resolved-input tiles. The collected
        state must be bit-exact with the one-window finalize() host
        path — same root AND byte-identical reachable node set."""
        import jax  # noqa: F401 — fused path needs a jax backend

        from khipu_tpu.domain.account import Account, address_key
        from khipu_tpu.ledger.window import WindowCommitter
        from khipu_tpu.trie.bulk import host_hasher
        from khipu_tpu.trie.deferred import _PLACEHOLDER_PREFIX
        from khipu_tpu.trie.mpt import EMPTY_TRIE_HASH

        def put_range(committer, rng):
            trie = committer.account_trie
            for i in rng:
                trie = trie.put(
                    address_key(i.to_bytes(20, "big")),
                    Account(nonce=i, balance=10**18 + i).encode(),
                )
            committer.account_trie = trie

        fused = WindowCommitter(
            Storages(), EMPTY_TRIE_HASH, hasher=host_hasher, fused=True
        )
        put_range(fused, range(30))
        job1 = fused.seal()
        # seal() is now the cheap driver close-out; the pack + dispatch
        # live in pack_and_dispatch (the collector's seal stage)
        fused.pack_and_dispatch(job1)
        assert job1.fused_job is not None, "fused path not taken"
        assert fused._inflight_rows, "window 1 not registered in flight"
        put_range(fused, range(30, 60))
        root_ref = fused.account_trie.force_hashed_root()
        job2 = fused.seal()
        fused.pack_and_dispatch(job2)  # packs against in-flight window 1
        # prove the cross-window mechanism was exercised: window 2's
        # packed encodings still embed window-1 placeholder bytes
        w1_phs = set(job1.to_resolve)
        refs = set()
        for enc in job2.to_resolve.values():
            pos = enc.find(_PLACEHOLDER_PREFIX)
            while pos >= 0:
                refs.add(enc[pos : pos + 32])
                pos = enc.find(_PLACEHOLDER_PREFIX, pos + 32)
        assert refs & w1_phs, "no cross-window refs — test is vacuous"
        fused.collect(job1)
        fused.collect(job2)
        assert not fused._inflight_rows
        real_root = fused._resolved_global[root_ref]

        host = WindowCommitter(
            Storages(), EMPTY_TRIE_HASH, hasher=host_hasher, fused=False
        )
        put_range(host, range(60))
        host_ref = host.account_trie.force_hashed_root()
        host.finalize()
        assert host._resolved_global[host_ref] == real_root
        assert _reachable(fused.storages, real_root) == _reachable(
            host.storages, real_root
        )

    def test_mid_pipeline_mismatch_drains_and_persists_nothing(
        self, chain
    ):
        """Corrupt root in the FIRST of five single-block windows at
        depth 4: the collector aborts, queued in-flight windows are
        dropped, the mismatch surfaces on the driver naming the block,
        and NO window persists to block storage."""
        blocks, _ = chain
        cfg = pipeline_cfg(1, 4)
        bad = Block(
            dataclasses.replace(
                blocks[0].header, state_root=b"\x66" * 32
            ),
            blocks[0].body,
        )
        bc = _fresh_chain(cfg)
        driver = ReplayDriver(bc, cfg, validate_headers=False)
        with pytest.raises(WindowMismatch) as e:
            driver.replay_windowed(
                iter([bad, blocks[1], blocks[2], blocks[3], blocks[4]]),
                1,
            )
        assert e.value.number == 1
        for n in range(1, 6):
            assert bc.get_header_by_number(n) is None
        from khipu_tpu.sync.replay import PIPELINE_GAUGES

        assert PIPELINE_GAUGES["in_flight"] == 0

    def test_live_placeholder_skipped_at_seal_names_index(self):
        """Satellite bugfix: a live placeholder with no staged encoding
        (the foreign-counter-range skip at seal) used to KeyError bare
        at collect; it must raise WindowPlaceholderError carrying the
        placeholder index."""
        from khipu_tpu.domain.account import Account, address_key
        from khipu_tpu.ledger.window import (
            WindowCommitter,
            WindowPlaceholderError,
        )
        from khipu_tpu.trie.deferred import _make_placeholder
        from khipu_tpu.trie.mpt import EMPTY_TRIE_HASH

        committer = WindowCommitter(Storages(), EMPTY_TRIE_HASH)
        trie = committer.account_trie
        for i in range(4):
            trie = trie.put(
                address_key(i.to_bytes(20, "big")),
                Account(nonce=i, balance=1).encode(),
            )
        committer.account_trie = trie
        job = committer.seal()
        ghost = _make_placeholder(10**9)  # a foreign session's index
        job.live[ghost] = 1
        with pytest.raises(WindowPlaceholderError) as e:
            committer.collect(job)
        assert e.value.index == 10**9
        assert str(10**9) in str(e.value)


class TestDeviceMirrorCommit:
    """Device-resident window commit (the mirror as commit target):
    bit-exactness vs the eager chain, the near-zero collect-phase d2h
    contract, and the retired-job device-buffer release."""

    def _device_replay(self, chain, cfg):
        from khipu_tpu.trie.bulk import host_hasher

        blocks, caddr = chain
        bc = _fresh_chain(cfg)
        driver = ReplayDriver(bc, cfg, device_commit=True)
        # fused seal path with the host keccak for the per-block root
        # gate (the interpreted device keccak is too slow on 1-core
        # CPU); the fused fixpoint program still runs on the backend
        driver.hasher = host_hasher
        return blocks, caddr, bc, driver

    def test_mirror_commit_bit_exact_and_collect_d2h_collapses(
        self, chain
    ):
        """THE tentpole contract: with the mirror as commit target the
        collect phase fetches only the per-block root digests off the
        device (32 B x blocks) — the bulk mapping fetch moved to the
        async persist stage — and the persisted chain is bit-exact."""
        from khipu_tpu.observability.profiler import D2H, LEDGER

        cfg = pipeline_cfg(2, 2, parallel=False)
        blocks, caddr, bc, driver = self._device_replay(chain, cfg)
        LEDGER.enable()
        LEDGER.reset()
        try:
            stats = driver.replay(blocks)
            per_phase = LEDGER.phase_bytes_per_block()
        finally:
            LEDGER.disable()
        assert stats.blocks == 5
        assert bc.get_header_by_number(5).hash == blocks[-1].hash
        # state correct through the mirror read path AND after spill
        world = bc.get_world_state(blocks[-1].header.state_root)
        assert world.get_storage(caddr, 0) == 42
        report = verify_reachable(
            bc.storages.account_node_storage,
            bc.storages.storage_node_storage,
            bc.storages.evmcode_storage,
            blocks[-1].header.state_root, verify_hashes=True,
        )
        assert report.missing == 0 and report.corrupt == 0
        # collect-phase d2h collapses to the 32 B/block rootcheck;
        # the big digest fetch now bills to the persist stage
        collect_d2h = per_phase.get("collect", {}).get(D2H, 0)
        assert 0 < collect_d2h <= 256, per_phase
        persist_d2h = per_phase.get("persist", {}).get(D2H, 0)
        assert persist_d2h > collect_d2h, per_phase
        # the mirror took the window admits and stayed claim-consistent
        mirror = driver._mirror
        assert mirror is not None
        assert mirror.resident_count > 0
        assert mirror.verify() == 0

    def test_retired_jobs_release_device_buffers(self, chain):
        """Satellite contract: every fused job frees its encoding
        buffers at mirror admit (collect stage) and its digest buffers
        once the window retires beyond the pipeline — HBM stays
        O(in-flight windows), not O(replayed chain)."""
        from khipu_tpu.trie import fused as fused_mod

        released, encs_released = [], []
        orig_release = fused_mod.FusedJob.release
        orig_encs = fused_mod.FusedJob.release_encs

        def spy_release(self):
            released.append(self)
            return orig_release(self)

        def spy_encs(self):
            encs_released.append(self)
            return orig_encs(self)

        fused_mod.FusedJob.release = spy_release
        fused_mod.FusedJob.release_encs = spy_encs
        try:
            cfg = pipeline_cfg(2, 2, parallel=False)
            blocks, _caddr, bc, driver = self._device_replay(chain, cfg)
            stats = driver.replay(blocks)
        finally:
            fused_mod.FusedJob.release = orig_release
            fused_mod.FusedJob.release_encs = orig_encs
        assert stats.blocks == 5
        # 5 blocks / window=2 -> 3 windows, each encs-released at admit
        # and fully released by the end-of-replay retire drain
        assert len(encs_released) == 3
        assert len(released) == 3
        for job in released:
            assert job.digests is None and job.encs is None
