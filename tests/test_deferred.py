"""Deferred (level-synchronous batched) trie commit tests: bit-exact
equality with the eager host MPT, and the device/mesh integrations
(SURVEY §2.8(c); round-3 brief items 1 and 6)."""

import random

import numpy as np
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


# ---------------------------------------------------------------------------
# update_many: a block's writes as one sorted batch. The per-key fold
# (remove, then put) is the oracle.

def _tall(rng, n):
    """``n`` 32-byte keys as a block's are, with values of account size."""
    return [(keccak256(rng.randbytes(4)), rng.randbytes(rng.randrange(40, 90)))
            for _ in range(n)]


def _squat(rng, n):
    """``n`` short keys over a few nibbles, so that keys end inside
    branches, share extensions and split leaves; every value is small
    enough for its node to stay inline."""
    return [
        (bytes(rng.randrange(3) * 16 + rng.randrange(3)
               for _ in range(rng.randrange(1, 5))),
         rng.randbytes(rng.randrange(1, 4)))
        for _ in range(n)
    ]


def _case_random(seed, maker, n_base, n_up, n_rm, blanks=0):
    def make():
        rng = random.Random(seed)
        base = maker(rng, n_base)
        ups = maker(rng, n_up) + [
            (rng.choice(base)[0], rng.randbytes(50)) for _ in range(n_up // 2)
        ] + [(rng.choice(base)[0], b"") for _ in range(blanks)]
        rng.shuffle(ups)
        rms = [rng.choice(base)[0] for _ in range(n_rm)] + [b"\x77absent"] * (
            n_rm > 0)
        return base, rms, ups
    return make


V = b"v" * 40  # a value whose leaf hashes
# the base trie's root is an extension 1,2,3,4,5 over a branch
EXT_BASE = [(b"\x12\x34\x56", V), (b"\x12\x34\x57", V)]
UPDATE_MANY_CASES = {
    "empty_trie": lambda: ([], [], _tall(random.Random(3), 40)),
    "empty_trie_short_keys": lambda: ([], [], _squat(random.Random(4), 40)),
    "empty_batch": lambda: (_tall(random.Random(5), 20), [], []),
    "batch_of_one": lambda: (
        _tall(random.Random(6), 50), [], _tall(random.Random(7), 1)),
    "duplicate_keys_last_wins": lambda: (
        _tall(random.Random(8), 10), [],
        [(b"\x01" * 32, b"first" * 9), (b"\x02" * 32, V),
         (b"\x01" * 32, b"last" * 9), (b"\x02" * 32, b""),
         (b"\x03" * 32, b""), (b"\x03" * 32, V)]),
    # one leaf at the root; keys past it, short of it, beside it, on it
    "split_leaf": lambda: (
        [(b"\x12\x34\x56", V)], [],
        [(b"\x12\x34\x57", V), (b"\x12\x35\x00", V), (b"\x12\x34", V),
         (b"\x12\x34\x56\x78", V)]),
    "split_leaf_and_overwrite": lambda: (
        [(b"\x12\x34\x56", V)], [],
        [(b"\x12\x34\x56", b"w" * 40), (b"\x12\x30", V), (b"\x92", V)]),
    # every key leaves the extension in its middle; what it led to
    # moves under a shorter one that no key enters
    "split_extension_untouched": lambda: (
        EXT_BASE, [], [(b"\x12\x44\x00", V), (b"\x12\x45\x00", V)]),
    # some leave it, one goes on into the shorter extension
    "split_extension_entered": lambda: (
        EXT_BASE, [], [(b"\x12\x44\x00", V), (b"\x12\x34\x58", V)]),
    # left at its last nibble: the child moves up, and is entered
    "split_extension_child_moves_up": lambda: (
        EXT_BASE, [], [(b"\x12\x34\x60", V), (b"\x12\x34\x58", V),
                       (b"\x12\x34\x56", b"w" * 40)]),
    # left at its first nibble, and by a key that ends inside it
    "split_extension_at_its_head": lambda: (
        EXT_BASE, [], [(b"\x92\x34\x56", V), (b"\x12", V), (b"", V)]),
    "all_below_extension": lambda: (
        EXT_BASE, [], [(b"\x12\x34\x58", V), (b"\x12\x34\x59\x01", V),
                       (b"\x12\x34\x59\x02", V)]),
    "inline_nodes": _case_random(11, _squat, 30, 30, 0),
    "inline_nodes_removes_mixed_in": _case_random(12, _squat, 40, 20, 8, 4),
    "removes_mixed_in": _case_random(13, _tall, 300, 60, 25),
    "blank_values": _case_random(14, _tall, 200, 40, 0, blanks=15),
    "block_shape": _case_random(15, _tall, 3000, 350, 0),
    "random_1": _case_random(21, _tall, 500, 120, 10, 5),
    "random_2": _case_random(22, _squat, 80, 60, 10, 5),
    "random_3": _case_random(23, _tall, 1, 200, 1, 1),
}


class _WriteOnce(dict):
    """A staged map that refuses to stage a key twice."""

    def __setitem__(self, key, value):
        assert key not in self, "a placeholder was staged twice"
        super().__setitem__(key, value)


@pytest.mark.parametrize("kind", ["eager", "deferred"])
@pytest.mark.parametrize("case", sorted(UPDATE_MANY_CASES))
def test_update_many_equals_the_per_key_fold(case, kind):
    from khipu_tpu.trie.deferred import (
        DeferredMPT,
        _is_placeholder,
        find_sites,
    )

    base, removes, upserts = UPDATE_MANY_CASES[case]()
    src = MemoryNodeDataSource()
    parent = eager_apply(MerklePatriciaTrie(src), base, []).persist()
    want = eager_apply(
        MerklePatriciaTrie(src, root_hash=parent.root_hash), upserts, removes)

    if kind == "eager":
        got = MerklePatriciaTrie(
            src, root_hash=parent.root_hash).update_many(removes, upserts)
    else:
        staged = _WriteOnce()
        session = DeferredMPT(
            src, root_hash=parent.root_hash, _staged=staged
        ).update_many(removes, upserts)
        created = session._counter[0]
        phs = [ph for ph in staged if _is_placeholder(ph)]
        assert len(phs) == created
        live = [ph for ph, rec in session._logs.items()
                if _is_placeholder(ph) and rec[0] > 0]
        if not removes and all(v for _k, v in upserts):
            # nothing is built that the batch's own root does not reach
            assert created == len(live)
        # children are ref'd before their parent: an own child's counter
        # is below its parent's (WindowCommitter._pack_sites counts on it)
        sites = find_sites([staged[ph] for ph in phs])
        own = sites.ctr[(sites.ctr >= 0) & (sites.ctr < created)]
        parents = np.array(
            [int.from_bytes(ph[-14:], "big") for ph in phs])[sites.node]
        parents = parents[(sites.ctr >= 0) & (sites.ctr < created)]
        assert (own < parents).all()
        got = session.commit(host_hasher)

    assert got.root_hash == want.root_hash
    removed_w, upserted_w = want.changes()
    removed_g, upserted_g = got.changes()
    assert sorted(removed_g) == sorted(removed_w)
    assert upserted_g == upserted_w
    # and what it built reads back
    final = dict(base)
    for k in removes:
        final.pop(k, None)
    for k, v in upserts:
        final[k] = v
    for k, v in final.items():
        assert got.get(k) == (v or None)


def test_window_commits_build_no_node_that_no_block_root_reaches():
    """Three blocks through ``WindowCommitter.commit_block`` (accounts,
    and storage tries written in several blocks): every root equals the
    eager host world's, and the sealed window packs nothing but the
    nodes its blocks' roots reach: the live ones, and those a later
    block of the window superseded."""
    from khipu_tpu.domain.account import Account
    from khipu_tpu.domain.block_header import (
        EMPTY_OMMERS_HASH,
        BlockHeader,
    )
    from khipu_tpu.ledger.window import WindowCommitter
    from khipu_tpu.ledger.world import BlockWorldState
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.trie.deferred import _PLACEHOLDER_PREFIX
    from khipu_tpu.trie.mpt import EMPTY_TRIE_HASH

    rng = random.Random(43)
    addrs = [rng.randbytes(20) for _ in range(400)]
    tokens = addrs[:3]

    def write(world):
        for a in rng.sample(addrs, 120):
            world.save_account(
                a, Account(nonce=rng.randrange(9), balance=rng.randrange(1, 10**18)))
        for t in tokens:
            world.save_account(t, world.get_account(t) or Account(nonce=1))
            for _ in range(40):
                # never 0: a removed key is a fold of its own, and what
                # that builds and drops again is not this test's matter
                world.save_storage(
                    t, rng.randrange(64), 1 + rng.getrandbits(200))

    host = Storages()
    committer = WindowCommitter(Storages(), EMPTY_TRIE_HASH, hasher=host_hasher)
    host_root, roots, state = EMPTY_TRIE_HASH, [], None
    for number in (1, 2, 3):
        state = rng.getstate()
        eager = BlockWorldState(
            MerklePatriciaTrie(host.account_node_storage, root_hash=host_root),
            host.storage_node_storage, host.evmcode_storage)
        write(eager)
        host_root = eager.persist(
            host.account_node_storage, host.storage_node_storage,
            host.evmcode_storage)
        roots.append(host_root)
        rng.setstate(state)  # the same writes again
        world = committer.make_world(None)
        write(world)
        parts = committer.commit_block(world, BlockHeader(
            parent_hash=b"\x00" * 32, ommers_hash=EMPTY_OMMERS_HASH,
            beneficiary=b"\x00" * 20, state_root=host_root,
            transactions_root=b"\x00" * 32, receipts_root=b"\x00" * 32,
            logs_bloom=b"\x00" * 256, difficulty=1, number=number,
            gas_limit=1, gas_used=0, unix_timestamp=number))
        # one descent a trie: well under the fold's nodes a put
        assert 0 < parts["created"] < 3 * (parts["accounts"] + parts["slots"])

    job = committer.seal()
    committer.pack_and_dispatch(job)
    packed = dict(job.to_resolve)
    reached, todo = set(), [ref for _h, ref in job.pending_blocks]
    while todo:
        ph = todo.pop()
        if ph in reached or ph not in packed:
            continue
        reached.add(ph)
        enc, pos = packed[ph], packed[ph].find(_PLACEHOLDER_PREFIX)
        while pos >= 0:
            todo.append(enc[pos:pos + 32])
            pos = enc.find(_PLACEHOLDER_PREFIX, pos + 32)
    assert set(job.live) <= set(packed)
    assert set(packed) == reached
    assert set(packed) - set(job.live), "no block superseded another's nodes"
    # collect checks each root against its header, and raises if not
    assert [root for _h, root in committer.collect(job)] == roots
