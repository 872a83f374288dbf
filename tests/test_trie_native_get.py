"""The trie's look-up as one C call (``csrc_ext/rlp_ext.c`` ``trie_get``)
against the Python walk it replaces (``MerklePatriciaTrie._py_get``,
which stays as the fallback and is the oracle here): same answer on
every key, present or absent; same resolve order over the decoded-node
cache, the session's staged nodes and live log entries, and the source;
same exception for a node the source lacks; the cache's bound kept; and
the counters that say which walk answered."""

import random
import threading

import pytest

from khipu_tpu.base import rlp
from khipu_tpu.base.crypto.keccak import keccak256
from khipu_tpu.base.nibbles import bytes_to_nibbles
from khipu_tpu.base.rlp import rlp_encode
from khipu_tpu.evm.dataword import to_minimal_bytes
from khipu_tpu.ledger.world import TrieStorage
from khipu_tpu.trie import mpt
from khipu_tpu.trie.deferred import DeferredMPT, _is_placeholder
from khipu_tpu.trie.mpt import (
    BLANK,
    MerklePatriciaTrie,
    MPTNodeMissingException,
    trie_read_samples,
)


class Source:
    """A node source that can carry the shared decoded-node cache."""

    def __init__(self):
        self.held = {}
        self.gets = 0

    def get(self, key):
        self.gets += 1
        return self.held.get(key)

    def put(self, key, value):
        self.held[key] = value


@pytest.fixture(autouse=True)
def native():
    """The extension, bound (in a checkout with no binary this waits
    for the background build instead of racing it)."""
    rlp._bind_rlp_ext(forwarded=True)
    assert rlp.native_ext is not None, "the extension did not build"
    return rlp.native_ext


def counters():
    out = {}
    for name, _kind, labels, value in trie_read_samples():
        out[name + "".join(f"[{v}]" for v in labels.values())] = value
    return out


def both(trie, key):
    """The native walk's answer, checked against the Python walk's."""
    got = trie.get(key)
    assert got == trie._py_get(key)
    return got


# ------------------------------------------------------- random tries

def hashed_keys(rng, n):
    return [keccak256(rng.randbytes(20)) for _ in range(n)]


def index_keys(rng, n):
    # the keys of a transaction or receipt trie: rlp(index)
    return [rlp_encode(to_minimal_bytes(i)) for i in range(n)]


def prefix_keys(rng, n):
    # every key a prefix of the next ones: values sit in branches
    stem = rng.randbytes(max(1, n // 4))
    keys = {stem[:i] for i in range(len(stem) + 1)}
    while len(keys) < n:
        cut = rng.randrange(len(stem) + 1)
        keys.add(stem[:cut] + rng.randbytes(rng.randint(1, 3)))
    return sorted(keys)[:n]


def build(keys, rng, small):
    source = Source()
    trie = MerklePatriciaTrie(source)
    values = {}
    for key in keys:
        size = rng.randint(1, 6) if small else rng.randint(1, 90)
        values[key] = rng.randbytes(size)
        trie = trie.put(key, values[key])
    return trie, values, source


def absent_keys(rng, keys):
    """Keys the trie lacks, diverging at every kind of node: a random
    key (a branch's blank child), a present key with its last nibbles
    changed (a leaf, or an extension's path), a present key cut short
    or made longer (a branch's own value, a leaf's path)."""
    have = set(keys)
    out = [rng.randbytes(len(keys[0]) or 1) for _ in range(50)]
    for key in rng.sample(keys, min(len(keys), 100)):
        if key:
            out.append(key[:-1] + bytes([key[-1] ^ 0x01]))
            out.append(key[:-1] + bytes([key[-1] ^ 0x10]))
            out.append(key[:-1])
            mid = len(key) // 2
            out.append(key[:mid] + bytes([key[mid] ^ 0x08]) + key[mid + 1:])
        out.append(key + b"\x00")
    return [k for k in out if k not in have]


@pytest.mark.parametrize("n,make_keys,small,seed", [
    (1, hashed_keys, False, 11),
    (2, hashed_keys, True, 12),
    (17, hashed_keys, False, 13),
    (300, hashed_keys, True, 14),
    (5_000, hashed_keys, False, 15),
    (50_000, hashed_keys, False, 2_147_483_777),
    (1, index_keys, True, 21),
    (130, index_keys, True, 22),
    (300, index_keys, False, 23),
    (40, prefix_keys, True, 31),
    (400, prefix_keys, False, 4_000_000_007),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_native_walk_agrees_with_the_python_walk(n, make_keys, small, seed):
    rng = random.Random(seed)
    keys = make_keys(rng, n)
    trie, values, source = build(keys, rng, small)
    probe = keys if n <= 5_000 else rng.sample(keys, 5_000)
    for key in probe:  # mid-session: nodes staged, none in the source
        assert both(trie, key) == values[key]
    for key in absent_keys(rng, keys):
        assert both(trie, key) is None
    assert source.gets == 0
    trie = trie.persist()  # from the source now, through the call-back
    reopened = MerklePatriciaTrie(source, root_hash=trie.root_hash)
    for key in probe:
        assert both(reopened, key) == values[key]
    for key in absent_keys(rng, keys):
        assert both(reopened, key) is None
    assert source.gets > 0


def test_the_empty_trie_holds_nothing():
    trie = MerklePatriciaTrie(Source())
    assert trie._root_ref == BLANK
    for key in (b"", b"\x00", keccak256(b"x")):
        assert both(trie, key) is None
    assert trie.get_hashed(b"x") is None


@pytest.mark.parametrize("wrap", [bytes, bytearray, memoryview],
                         ids=lambda w: w.__name__)
def test_any_buffer_is_a_key(wrap):
    rng = random.Random(5)
    keys = hashed_keys(rng, 64)
    trie, values, _ = build(keys, rng, False)
    for key in keys:
        assert trie.get(wrap(key)) == values[key]


def test_get_hashed_hashes_the_key_inside_the_call():
    rng = random.Random(6)
    slots = [rng.randrange(2 ** 256) for _ in range(200)] + [0, 1]
    trie = MerklePatriciaTrie(Source())
    for slot in slots:
        trie = trie.put(TrieStorage.key_bytes(slot),
                        rlp_encode(to_minimal_bytes(slot | 1)))
    for slot in slots:
        pre = slot.to_bytes(32, "big")
        assert trie.get_hashed(pre) == trie._py_get(keccak256(pre)) \
            == rlp_encode(to_minimal_bytes(slot | 1))
        assert TrieStorage(trie).load_original(slot) == slot | 1
    assert trie.get_hashed(b"\x07" * 32) is None
    assert TrieStorage(trie).load_original(2 ** 200 + 3) == 0
    # addresses (20 bytes) and anything else hash as well
    assert trie.get_hashed(b"\x01" * 20) == trie._py_get(
        keccak256(b"\x01" * 20))


# ------------------------------------------------- a deferred session

def deferred_session(seed, n_base=400, n_dirty=60):
    """A DeferredMPT mid-window: a persisted base trie, then puts and
    removes whose new nodes are placeholders in ``_staged``."""
    rng = random.Random(seed)
    keys = hashed_keys(rng, n_base)
    base, values, source = build(keys, rng, False)
    base = base.persist()
    trie = DeferredMPT(source, root_hash=base.root_hash)
    for key in rng.sample(keys, n_dirty):
        values[key] = rng.randbytes(rng.randint(1, 60))
        trie = trie.put(key, values[key])
    for key in hashed_keys(rng, n_dirty):
        values[key] = rng.randbytes(40)
        trie = trie.put(key, values[key])
    for key in rng.sample(sorted(values), n_dirty // 2):
        trie = trie.remove(key)
        values[key] = None
    return trie, values, source, rng


@pytest.mark.parametrize("seed", [41, 2_147_483_659, 3_999_999_979])
def test_deferred_session_reads_its_placeholders(seed):
    trie, values, _source, rng = deferred_session(seed)
    assert _is_placeholder(trie._root_ref)
    assert any(_is_placeholder(ph) for ph in trie._staged)
    for key, value in values.items():
        assert both(trie, key) == value
    for key in absent_keys(rng, sorted(values)):
        assert both(trie, key) is None


@pytest.mark.parametrize("seed", [43, 2_147_483_693])
def test_live_log_entries_answer_and_dead_ones_do_not(seed):
    trie, values, _source, _rng = deferred_session(seed)
    live = [ph for ph, rec in trie._logs.items()
            if rec[0] > 0 and _is_placeholder(ph)]
    assert live
    # the window pruned the staged map: the live log entries still serve
    trie._staged.clear()
    for key, value in values.items():
        assert both(trie, key) == value
    # a dead entry (count 0 or below) that kept its encoding does not:
    # the reference goes to the source, which has no such placeholder
    root = trie._root_ref
    trie._logs[root][0] = 0
    for walk in (trie.get, trie._py_get):
        with pytest.raises(MPTNodeMissingException) as err:
            walk(next(iter(values)))
        assert err.value.hash == root


def test_staged_nodes_are_not_cached():
    trie, values, _source, _rng = deferred_session(47)
    for key in values:
        trie.get(key)
    assert not any(_is_placeholder(ref) for ref in trie._dcache)
    shared = trie.source._mpt_dcache
    assert not any(_is_placeholder(ref) for ref in shared)


def test_an_inline_root_is_followed_in_place():
    for cls in (MerklePatriciaTrie, DeferredMPT):
        trie = cls(Source()).put(b"\x01\x23", b"v")
        assert isinstance(trie._root_ref, list)  # under 32 bytes
        assert both(trie, b"\x01\x23") == b"v"
        assert both(trie, b"\x01\x24") is None
        trie = trie.put(b"\x01\x45", b"w")  # a branch with inline leaves
        assert both(trie, b"\x01\x45") == b"w"
        assert both(trie, b"\x01") is None


@pytest.mark.parametrize("seed", [51, 2_147_483_713])
def test_after_remove(seed):
    rng = random.Random(seed)
    keys = hashed_keys(rng, 500) + index_keys(rng, 40)
    trie, values, source = build(keys, rng, False)
    trie = trie.persist()
    gone = rng.sample(keys, 270)
    for key in gone:
        trie = trie.remove(key)
        values[key] = None
    for key in keys:
        assert both(trie, key) == values[key]
    reopened = MerklePatriciaTrie(source,
                                  root_hash=trie.persist().root_hash)
    for key in keys:
        assert both(reopened, key) == values[key]


# ------------------------------------------------- the source's part

@pytest.mark.parametrize("depth", [0, 1, 2])
def test_a_node_the_source_lacks_raises_the_same(depth):
    rng = random.Random(61)
    keys = hashed_keys(rng, 2_000)
    trie, _values, source = build(keys, rng, False)
    trie = trie.persist()
    key = keys[7]
    # the ref `depth` hashed nodes below the root, on key's path
    ref, node = trie._root_ref, trie._resolve(trie._root_ref)
    nibbles = bytes_to_nibbles(key)
    for _ in range(depth):
        assert len(node) == 17
        ref, nibbles = node[nibbles[0]], nibbles[1:]
        assert isinstance(ref, bytes) and len(ref) == 32
        node = trie._resolve(ref)
    del source.held[ref]
    source._mpt_dcache.clear()
    seen = []
    for walk in (trie.get, trie._py_get):
        with pytest.raises(MPTNodeMissingException) as err:
            walk(key)
        seen.append((err.value.hash, str(err.value)))
        source._mpt_dcache.pop(ref, None)
    assert seen[0] == seen[1] == (ref, f"missing MPT node {ref.hex()}")


def test_the_decoded_cache_keeps_its_bound():
    rng = random.Random(67)
    keys = hashed_keys(rng, 300)
    trie, values, source = build(keys, rng, False)
    trie = trie.persist()
    cache = source._mpt_dcache
    cache.clear()
    cache.update((i.to_bytes(32, "big"), [b" ", b"x"])
                 for i in range(262_144))
    assert trie.get(keys[0]) == values[keys[0]]
    # the call-back met the cache at its bound and cleared it, then the
    # walk's later nodes filled it again
    assert 0 < len(cache) < 16
    assert trie.get(keys[0]) == values[keys[0]]


class Ruthless(Source):
    """A source whose every read does what another thread may do while
    a call-back has let go of the GIL: empties the decoded cache and
    the session's staged map."""

    def __init__(self):
        super().__init__()
        self.tries = []

    def get(self, key):
        self._mpt_dcache.clear()
        for trie in self.tries:
            trie._staged.clear()
        return super().get(key)


def test_the_walk_owns_its_node_across_a_call_back():
    rng = random.Random(71)
    source = Ruthless()
    trie = MerklePatriciaTrie(source)
    keys = hashed_keys(rng, 3_000)
    values = {}
    for key in keys:
        values[key] = rng.randbytes(50)
        trie = trie.put(key, values[key])
    trie = trie.persist()
    reopened = MerklePatriciaTrie(source, root_hash=trie.root_hash)
    source.tries.append(reopened)
    for key in keys[:500]:
        assert reopened.get(key) == values[key]
        assert len(source._mpt_dcache) == 1  # the last node read


def test_readers_and_a_clearing_thread():
    rng = random.Random(73)
    keys = hashed_keys(rng, 2_000)
    trie, values, source = build(keys, rng, False)
    trie = trie.persist()
    stop = threading.Event()
    wrong = []

    def clearer():
        while not stop.is_set():
            source._mpt_dcache.clear()

    def reader(seed):
        order = random.Random(seed).sample(keys, len(keys))
        t = MerklePatriciaTrie(source, root_hash=trie.root_hash)
        for key in order:
            if t.get(key) != values[key]:
                wrong.append(key)

    threads = [threading.Thread(target=reader, args=(s,)) for s in range(3)]
    bg = threading.Thread(target=clearer)
    bg.start()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    stop.set()
    bg.join()
    assert not wrong


@pytest.mark.parametrize("node", [
    [b"", b"v"],                       # an empty hex-prefix path
    [b"\x20", b"v", b"extra"],         # neither 2 nor 17 items
    [[b"\x20"], b"v"],                 # a path that is no bytes
    b"\x01",                           # a root that is no node at all
], ids=["empty-hp", "three-items", "list-path", "bytes-node"])
def test_a_malformed_node_fails_as_it_did(node):
    source = Source()
    enc = rlp_encode(node)
    ref = keccak256(enc)
    source.put(ref, enc)
    trie = MerklePatriciaTrie(source, root_hash=ref)
    outcomes = []
    for walk in (trie.get, trie._py_get):
        try:
            outcomes.append(("ok", walk(b"\x12")))
        except Exception as err:  # whatever it is, both walks agree
            outcomes.append((type(err), str(err)))
        source._mpt_dcache.clear()
    assert outcomes[0] == outcomes[1]
    # three items read as a leaf whose path differs; the others raise
    assert (outcomes[0] == ("ok", None)) == (len(node) == 3)


# -------------------------------------------------------- the counters

def test_counters_say_what_the_walk_did():
    rng = random.Random(79)
    keys = hashed_keys(rng, 1_000)
    trie, _values, source = build(keys, rng, False)
    trie = trie.persist()
    source._mpt_dcache.clear()
    c0 = counters()
    for key in keys:
        trie.get(key)
    c1 = counters()  # cold: every hashed node called back once
    assert c1["khipu_trie_reads_total[native]"] \
        - c0["khipu_trie_reads_total[native]"] == len(keys)
    assert c1["khipu_trie_reads_total[python]"] \
        == c0["khipu_trie_reads_total[python]"]
    cold = c1["khipu_trie_read_callbacks_total"] \
        - c0["khipu_trie_read_callbacks_total"]
    assert cold == len(source._mpt_dcache) == source.gets > 0
    for key in keys:
        trie.get_hashed(key)
    c2 = counters()  # warm: the caches answer every visit
    assert c2["khipu_trie_read_callbacks_total"] \
        == c1["khipu_trie_read_callbacks_total"]
    assert c2["khipu_trie_reads_total[native]"] \
        - c1["khipu_trie_reads_total[native]"] == len(keys)
    walked = c2["khipu_trie_read_seconds_total"] \
        - c1["khipu_trie_read_seconds_total"]
    assert 0 < walked < 1.0


def test_the_walks_own_time_leaves_the_call_backs_out():
    import time

    class Slow(Source):
        def get(self, key):
            time.sleep(0.02)
            return super().get(key)

    rng = random.Random(83)
    source = Slow()
    trie = MerklePatriciaTrie(source)
    keys = hashed_keys(rng, 40)
    for key in keys:
        trie = trie.put(key, rng.randbytes(40))
    trie = trie.persist()
    source._mpt_dcache.clear()
    c0 = counters()
    t0 = time.perf_counter()
    for key in keys[:5]:
        trie.get(key)
    wall = time.perf_counter() - t0
    c1 = counters()
    assert wall > 0.1  # five or more reads of 20 ms
    assert c1["khipu_trie_read_seconds_total"] \
        - c0["khipu_trie_read_seconds_total"] < wall / 10


def test_without_the_extension_the_python_walk_answers(monkeypatch):
    rng = random.Random(89)
    keys = hashed_keys(rng, 200)
    trie, values, _source = build(keys, rng, False)
    monkeypatch.setattr(rlp, "native_ext", None)
    c0 = counters()
    for key in keys:
        assert trie.get(key) == values[key]
    pre = (5).to_bytes(32, "big")
    assert trie.get_hashed(pre) == trie._py_get(keccak256(pre))
    c1 = counters()
    assert c1["khipu_trie_reads_total[python]"] \
        - c0["khipu_trie_reads_total[python]"] == len(keys) + 2
    # nothing native to report while the extension is withheld
    assert c1["khipu_trie_reads_total[native]"] == 0
    assert c1["khipu_trie_read_seconds_total"] == 0
    assert mpt._python_reads[0] == c1["khipu_trie_reads_total[python]"]


def test_the_registry_serves_the_trie_counters_of_a_node():
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import GenesisSpec
    from khipu_tpu.observability.registry import REGISTRY
    from khipu_tpu.service_board import ServiceBoard

    alloc = {bytes([i + 1]) * 20: 1 + i for i in range(64)}
    board = ServiceBoard(fixture_config(chain_id=1),
                         genesis=GenesisSpec(alloc=alloc))
    try:
        chain = board.blockchain
        root = chain.get_header_by_number(0).state_root
        before = REGISTRY.snapshot()["khipu_trie_reads_total"]
        for addr in alloc:
            assert chain.get_account(addr, root).balance == addr[0]
        snap = REGISTRY.snapshot()
        reads = snap["khipu_trie_reads_total"]
        assert reads['walk="native"'] - before['walk="native"'] == 64
        assert reads['walk="python"'] == before['walk="python"']
        assert snap["khipu_trie_read_seconds_total"] > 0
        assert snap["khipu_trie_read_callbacks_total"] >= 0
        assert 'khipu_trie_reads_total{walk="native"}' in \
            REGISTRY.prometheus_text()
    finally:
        board.shutdown()
