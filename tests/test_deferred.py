"""Deferred (level-synchronous batched) trie commit tests: bit-exact
equality with the eager host MPT, and the device/mesh integrations
(SURVEY §2.8(c); round-3 brief items 1 and 6)."""

import random

import pytest

from khipu_tpu.base.crypto.keccak import keccak256
from khipu_tpu.storage.datasource import MemoryNodeDataSource
from khipu_tpu.trie.bulk import bulk_build, host_hasher
from khipu_tpu.trie.deferred import batch_commit
from khipu_tpu.trie.mpt import MerklePatriciaTrie


def eager_apply(trie, upserts, removes):
    for k in removes:
        trie = trie.remove(k)
    for k, v in upserts:
        trie = trie.put(k, v)
    return trie


class TestBatchCommit:
    def test_fresh_build_matches_eager(self):
        random.seed(1)
        pairs = [
            (keccak256(b"k%d" % i), b"value-%d" % i * (i % 7 + 1))
            for i in range(500)
        ]
        src = MemoryNodeDataSource()
        eager = eager_apply(MerklePatriciaTrie(src), pairs, [])
        deferred = batch_commit(MerklePatriciaTrie(src), pairs)
        assert deferred.root_hash == eager.root_hash
        # the change sets agree too (same node hashes)
        _, up_e = eager.changes()
        _, up_d = deferred.changes()
        assert up_e == up_d

    def test_incremental_update_matches_eager(self):
        """Block-commit shape: small dirty set against a large persisted
        trie, including removals and overwrites."""
        random.seed(2)
        base_pairs = [
            (keccak256(b"base%d" % i), b"acct-%d" % i) for i in range(2000)
        ]
        src = MemoryNodeDataSource()
        base = eager_apply(MerklePatriciaTrie(src), base_pairs, [])
        base = base.persist()

        for round_i in range(5):
            ups = [
                (keccak256(b"base%d" % random.randrange(2500)),
                 b"new-%d-%d" % (round_i, j))
                for j in range(50)
            ]
            rms = [
                keccak256(b"base%d" % random.randrange(2000))
                for _ in range(10)
            ]
            eager = eager_apply(base, ups, rms)
            deferred = batch_commit(base, ups, rms)
            assert deferred.root_hash == eager.root_hash, f"round {round_i}"
            # reads through the deferred trie resolve real hashes
            # (duplicate upsert keys: last write wins, like the eager fold)
            expected = dict(ups)
            for k, v in expected.items():
                if k not in rms:
                    assert deferred.get(k) == v
            base = deferred.persist()

    def test_persisted_deferred_trie_reopens(self):
        src = MemoryNodeDataSource()
        pairs = [(keccak256(b"p%d" % i), b"v%d" % i) for i in range(100)]
        t = batch_commit(MerklePatriciaTrie(src), pairs).persist()
        again = MerklePatriciaTrie(src, root_hash=t.root_hash)
        for k, v in pairs:
            assert again.get(k) == v

    def test_empty_batch_is_identity(self):
        src = MemoryNodeDataSource()
        base = eager_apply(
            MerklePatriciaTrie(src),
            [(keccak256(b"x"), b"y")], [],
        )
        out = batch_commit(base, [], [])
        assert out.root_hash == base.root_hash

    def test_caller_trie_untouched(self):
        src = MemoryNodeDataSource()
        base = eager_apply(MerklePatriciaTrie(src), [(keccak256(b"a"), b"1")], [])
        logs_before = {h: list(r) for h, r in base._logs.items()}
        batch_commit(base, [(keccak256(b"b"), b"2")])
        assert {h: list(r) for h, r in base._logs.items()} == logs_before


class TestWorldDeviceCommit:
    def test_replay_with_device_commit_identical_roots(self):
        """Full replay with every trie commit through the batched
        hasher: persisted roots must equal the eager-built headers."""
        from khipu_tpu.base.crypto.secp256k1 import (
            privkey_to_pubkey,
            pubkey_to_address,
        )
        from khipu_tpu.config import fixture_config
        from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
        from khipu_tpu.domain.transaction import (
            Transaction,
            sign_transaction,
        )
        from khipu_tpu.storage.storages import Storages
        from khipu_tpu.sync.chain_builder import ChainBuilder
        from khipu_tpu.sync.replay import ReplayDriver

        cfg = fixture_config(chain_id=1)
        keys = [(i + 1).to_bytes(32, "big") for i in range(3)]
        addrs = [pubkey_to_address(privkey_to_pubkey(k)) for k in keys]
        alloc = {a: 10**21 for a in addrs}
        builder = ChainBuilder(
            Blockchain(Storages(), cfg), cfg, GenesisSpec(alloc=alloc)
        )
        # include a contract so storage tries hit the deferred path too
        init = bytes.fromhex("602a600055600a600155")  # two SSTOREs
        blocks = [
            builder.add_block(
                [sign_transaction(Transaction(0, 10**9, 200_000, None, 0, init), keys[0], chain_id=1)],
                coinbase=b"\xaa" * 20,
            ),
            builder.add_block(
                [sign_transaction(Transaction(1, 10**9, 21_000, addrs[1], 5), keys[0], chain_id=1),
                 sign_transaction(Transaction(0, 10**9, 21_000, addrs[2], 7), keys[1], chain_id=1)],
                coinbase=b"\xaa" * 20,
            ),
        ]
        bc2 = Blockchain(Storages(), cfg)
        bc2.load_genesis(GenesisSpec(alloc=alloc))
        # device_commit=True -> ops.keccak batch path (jnp on CPU mesh,
        # Pallas on TPU); save_block raises if any root diverges
        ReplayDriver(bc2, cfg, device_commit=True).replay(blocks)
        assert bc2.get_header_by_number(2).hash == blocks[-1].hash


class TestShardedBulkBuild:
    def test_sharded_bulk_root_matches_host_10k(self):
        """Round-3 brief item 6 'Done =': multi-device CPU test, sharded
        bulk root == host-oracle root on a 10k-account trie."""
        import jax

        from khipu_tpu.parallel import device_mesh
        from khipu_tpu.parallel.keccak_sharded import sharded_hasher

        mesh = device_mesh(min(8, len(jax.devices())))
        pairs = [
            (keccak256(b"acct%d" % i), b"\x01" * 8 + b"%d" % i)
            for i in range(10_000)
        ]
        host_root, host_nodes = bulk_build(pairs, hasher=host_hasher)
        sh_root, sh_nodes = bulk_build(pairs, hasher=sharded_hasher(mesh))
        assert sh_root == host_root
        assert sh_nodes == host_nodes

    def test_sharded_batch_commit(self):
        """Incremental deferred commit with the mesh hasher."""
        import jax

        from khipu_tpu.parallel import device_mesh
        from khipu_tpu.parallel.keccak_sharded import sharded_hasher

        mesh = device_mesh(min(8, len(jax.devices())))
        src = MemoryNodeDataSource()
        base_pairs = [(keccak256(b"b%d" % i), b"v%d" % i) for i in range(300)]
        base = eager_apply(MerklePatriciaTrie(src), base_pairs, []).persist()
        ups = [(keccak256(b"b%d" % i), b"upd%d" % i) for i in range(0, 600, 3)]
        eager = eager_apply(base, ups, [])
        sharded = batch_commit(base, ups, hasher=sharded_hasher(mesh))
        assert sharded.root_hash == eager.root_hash


class TestFusedFinalize:
    """One-dispatch fixpoint finalize (trie/fused.py) vs the per-level
    loop — identical resolutions, roots, and persisted stores."""

    def _random_session(self, seed, n_base, n_up, n_rm):
        rng = random.Random(seed)
        src = MemoryNodeDataSource()
        base = MerklePatriciaTrie(src)
        keys = [keccak256(rng.randbytes(8)) for _ in range(n_base)]
        for k in keys:
            base = base.put(k, rng.randbytes(rng.randrange(1, 80)))
        base = base.persist()
        ups = [
            (keccak256(rng.randbytes(8)), rng.randbytes(rng.randrange(1, 80)))
            for _ in range(n_up)
        ] + [(rng.choice(keys), b"overwritten") for _ in range(5)]
        rms = rng.sample(keys, min(n_rm, len(keys)))
        return base, ups, rms

    # one seed: each distinct window shape costs a fresh XLA compile of
    # the fixpoint program (~30s on CPU); the windowed-replay test below
    # covers a second, independent shape
    @pytest.mark.parametrize("seed", [1])
    def test_fused_equals_level_loop(self, seed):
        from khipu_tpu.trie.deferred import DeferredMPT, finalize

        base, ups, rms = self._random_session(seed, 300, 200, 40)

        def session():
            d = DeferredMPT(
                base.source,
                _root_ref=base._root_ref,
                _logs={h: [c, e] for h, (c, e) in base._logs.items()},
                _staged=dict(base._staged),
            )
            for k in rms:
                d = d.remove(k)
            for k, v in ups:
                d = d.put(k, v)
            return d

        loop_trie, loop_map = finalize(
            session(), host_hasher, return_mapping=True
        )
        fused_trie, fused_map = finalize(
            session(), host_hasher, return_mapping=True, fused=True
        )
        assert fused_map and fused_map == loop_map
        assert fused_trie.root_hash == loop_trie.root_hash
        _, loop_up = loop_trie.changes()
        _, fused_up = fused_trie.changes()
        assert fused_up == loop_up
        # content addressing holds on every fused node
        for h, enc in fused_up.items():
            assert keccak256(enc) == h

    def test_ext_tile_shapes_are_bucketed(self):
        """The resolved-input tile is gathered with row lists padded
        to multiples of EXT_FLOOR and its total to a pow-2 of at least
        EXT_HELD_ROWS: windows whose cross-ref counts differ (1298 vs
        1303 on the chip) share their gather programs and ONE fused ext
        bucket, instead of compiling a handful of small programs per
        window."""
        import jax.numpy as jnp
        import numpy as np

        from khipu_tpu.trie.fused import (
            EXT_FLOOR,
            EXT_HELD_ROWS,
            HeldBuckets,
            gather_ext_tile,
        )

        rng = np.random.default_rng(4)
        t1 = jnp.asarray(rng.integers(0, 256, (512, 32), dtype=np.uint8))
        t2 = jnp.asarray(rng.integers(0, 256, (256, 32), dtype=np.uint8))
        totals = {}
        for n1, n2 in ((3, 0), (60, 0), (64, 0), (65, 0), (100, 5),
                       (128, 64), (70, 70), (120, 10), (125, 3)):
            r1 = rng.integers(0, 512, n1).astype(np.int32)
            r2 = rng.integers(0, 256, n2).astype(np.int32)
            sources = [(t1, r1)] + ([(t2, r2)] if n2 else [])
            tile, offsets = gather_ext_tile(sources, HeldBuckets())
            n = tile.shape[0]
            totals[(n1, n2)] = n
            assert n >= EXT_FLOOR and n & (n - 1) == 0  # pow-2 bucket
            assert all(o % EXT_FLOOR == 0 for o in offsets)
            got = np.asarray(tile)
            np.testing.assert_array_equal(
                got[offsets[0] : offsets[0] + n1], np.asarray(t1)[r1])
            if n2:
                np.testing.assert_array_equal(
                    got[offsets[1] : offsets[1] + n2], np.asarray(t2)[r2])
        # under EXT_HELD_ROWS the cross-ref count moves no signature
        assert set(totals.values()) == {EXT_HELD_ROWS}
        big = rng.integers(0, 512, EXT_HELD_ROWS + 1).astype(np.int32)
        tile, offsets = gather_ext_tile([(t1, big)], HeldBuckets())
        assert tile.shape[0] == 2 * EXT_HELD_ROWS and offsets == [0]

    def test_fused_windowed_replay_equals_host(self):
        """End to end: windowed replay with the fused committer produces
        the same chain as the eager per-block host path."""
        import dataclasses

        from khipu_tpu.base.crypto.secp256k1 import (
            privkey_to_pubkey,
            pubkey_to_address,
        )
        from khipu_tpu.config import SyncConfig, fixture_config
        from khipu_tpu.domain.block import Block
        from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
        from khipu_tpu.domain.transaction import (
            Transaction,
            sign_transaction,
        )
        from khipu_tpu.storage.storages import Storages
        from khipu_tpu.sync.chain_builder import ChainBuilder
        from khipu_tpu.sync.replay import ReplayDriver

        cfg = fixture_config(chain_id=1)
        key = (9).to_bytes(32, "big")
        sender = pubkey_to_address(privkey_to_pubkey(key))
        alloc = {sender: 10**21}
        builder = ChainBuilder(
            Blockchain(Storages(), cfg), cfg, GenesisSpec(alloc=alloc)
        )
        blocks = []
        for n in range(9):
            txs = [
                sign_transaction(
                    Transaction(
                        n * 2 + j, 10**9, 21_000,
                        bytes.fromhex("%040x" % (0xF00D + 7 * n + j)), 5,
                    ),
                    key, chain_id=1,
                )
                for j in range(2)
            ]
            blocks.append(builder.add_block(txs, coinbase=b"\xaa" * 20))
        blocks = [Block.decode(b.encode()) for b in blocks]

        cfg2 = dataclasses.replace(
            cfg, sync=SyncConfig(parallel_tx=False, commit_window_blocks=4)
        )
        bc = Blockchain(Storages(), cfg2)
        bc.load_genesis(GenesisSpec(alloc=alloc))
        driver = ReplayDriver(bc, cfg2, device_commit=True)
        driver.hasher = host_hasher  # device kernel interpreted on CPU is
        # slow; `fused` is forced below and runs the one-dispatch path
        stats = driver.replay(blocks)
        assert stats.blocks == 9
        assert bc.get_header_by_number(9).hash == blocks[-1].hash


def test_seal_scan_matches_resolution_inputs():
    """WindowCommitter.seal derives its placeholder DAG with a raw
    byte scan (no rlp decode); deferred.resolution_inputs derives it
    from decoded structures. The two scanners must agree on the same
    session — this pins them against silent divergence (they share the
    placeholder format and the embedded-ref rules)."""
    from khipu_tpu.domain.account import Account, address_key
    from khipu_tpu.ledger.window import WindowCommitter
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.trie.deferred import resolution_inputs
    from khipu_tpu.trie.mpt import EMPTY_TRIE_HASH

    committer = WindowCommitter(Storages(), EMPTY_TRIE_HASH)
    trie = committer.account_trie
    for i in range(40):
        acc = Account(nonce=i, balance=10**18 + i)
        trie = trie.put(address_key(i.to_bytes(20, "big")), acc.encode())
    committer.account_trie = trie
    want_resolve, want_deps, _ = resolution_inputs(trie)

    job = committer.seal()
    committer.pack_and_dispatch(job)  # seal() defers the pack scan
    assert set(job.to_resolve) == set(want_resolve)
    # seal pre-substitutes resolved placeholders; with none resolved
    # yet the encodings must be byte-identical too
    assert job.to_resolve == want_resolve
