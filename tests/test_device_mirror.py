"""Device-resident word-major node mirror (storage/device_mirror.py):
admit -> verify round trip, corruption detection, ring eviction, and
read-back. Runs on the CPU backend via the jnp sponge (same digests)."""

import random

import pytest

from khipu_tpu.base.crypto.keccak import keccak256
from khipu_tpu.storage.device_mirror import DeviceNodeMirror


@pytest.fixture(scope="module")
def mirror():
    m = DeviceNodeMirror(capacity_rows_per_class=1024)
    rng = random.Random(5)
    items = {}
    for _ in range(40):
        enc = rng.randbytes(rng.choice([70, 130, 300, 532]))
        items[keccak256(enc)] = enc
    m.admit(items)
    m.flush()
    return m, items


def test_verify_clean(mirror):
    m, items = mirror
    assert m.resident_count == len(items)
    assert m.verify() == 0


def test_read_back(mirror):
    m, items = mirror
    for h, enc in list(items.items())[:5]:
        assert m.contains(h)
        assert m.get(h) == enc
    assert m.get(b"\x00" * 32) is None


def test_corrupt_admit_detected():
    m = DeviceNodeMirror(capacity_rows_per_class=1024)
    enc = b"\xab" * 64
    m.admit({keccak256(enc): enc, b"\x99" * 32: b"\xcd" * 64})
    m.flush()
    assert m.verify() == 1  # exactly the forged claim fails


def test_ring_eviction():
    m = DeviceNodeMirror(capacity_rows_per_class=1024)
    items = {}
    for i in range(1500):
        enc = i.to_bytes(8, "big") * 9
        items[keccak256(enc)] = enc
    m.admit(items)
    m.flush()
    assert m.resident_count <= 1024
    assert m.verify() == 0  # evicted rows dropped, survivors intact


def test_exact_length_class():
    """Uniform-length populations store unpadded (in-kernel pad):
    verify and read-back must behave identically to the generic class."""
    import numpy as np

    rng = random.Random(11)
    m2 = DeviceNodeMirror(capacity_rows_per_class=1024)
    raw_full = np.frombuffer(
        rng.randbytes(64 * 1024), dtype=np.uint8
    ).reshape(1024, 64)
    hs = [keccak256(raw_full[i].tobytes()) for i in range(1024)]
    m2.admit_packed(hs, raw_full, [64] * 1024, exact=True)
    assert m2.verify() == 0
    assert m2.get(hs[0]) == raw_full[0].tobytes()
    assert m2.resident_count == 1024


def test_duplicate_admit_bookkeeping():
    """Re-admitting a resident hash must not inflate resident_count,
    and ring eviction of the OLD copy must not unmap the newer row."""
    m = DeviceNodeMirror(capacity_rows_per_class=1024)
    enc = b"\x77" * 64
    h = keccak256(enc)
    m.admit({h: enc})
    m.flush()
    assert m.resident_count == 1
    # duplicate admit via a fresh staging round (new tile, same hash)
    m.admit({h: enc})
    m.flush()
    assert m.resident_count == 1
    assert m.get(h) == enc
    assert m.verify() == 0


def _device_tile(encs):
    """One padded TILE of on-device encodings + claims: the first
    len(encs) rows are real, the rest repeat row 0 (claim-consistent
    padding, the unit-test analog of the fused dummy row)."""
    import jax.numpy as jnp
    import numpy as np

    from khipu_tpu.storage.device_mirror import RATE, TILE

    width = RATE
    padded = np.zeros((TILE, width), np.uint8)
    claims = np.zeros((TILE, 32), np.uint8)
    for r in range(TILE):
        enc = encs[r] if r < len(encs) else encs[0]
        padded[r, : len(enc)] = np.frombuffer(enc, np.uint8)
        padded[r, len(enc)] ^= 0x01
        padded[r, width - 1] ^= 0x80
        claims[r] = np.frombuffer(keccak256(enc), np.uint8)
    return jnp.asarray(padded), jnp.asarray(claims)


def test_alias_rows_hidden_until_rekey():
    """Device-admitted window rows live in the placeholder (alias)
    namespace: invisible to content-address reads until the persist
    stage's rekey publishes them under their real hashes — a reader
    following a published root must never see un-published rows."""
    from khipu_tpu.storage.device_mirror import TILE

    m = DeviceNodeMirror(capacity_rows_per_class=1024)
    encs = [bytes([i + 1]) * (40 + 7 * i) for i in range(3)]
    enc_dev, claim_dev = _device_tile(encs)
    aliases = [b"\xaa" + i.to_bytes(31, "big") for i in range(3)]
    keys = aliases + [None] * (TILE - 3)
    lengths = [len(e) for e in encs] + [0] * (TILE - 3)
    m.admit_device(1, keys, enc_dev, claim_dev, lengths)
    for enc in encs:
        assert m.get(keccak256(enc)) is None, "unpublished row served"
    assert m.verify() == 0  # claim-consistent even while aliased
    mapping = {a: keccak256(e) for a, e in zip(aliases, encs)}
    mapping[b"\xbb" * 32] = b"\xcc" * 32  # unrelated entries are inert
    assert m.rekey(mapping) == 3
    for enc in encs:
        assert m.get(keccak256(enc)) == enc
    assert m.verify() == 0


def test_admit_device_skips_tiles_that_hold_no_key():
    """The fused program hands over as many admit slots as the class has
    rows; the tiles past the live rows are padding and take no ring
    tile."""
    import jax.numpy as jnp

    from khipu_tpu.storage.device_mirror import TILE

    m = DeviceNodeMirror(capacity_rows_per_class=4 * TILE)
    encs = [bytes([i + 1]) * (40 + 7 * i) for i in range(3)]
    enc_dev, claim_dev = _device_tile(encs)
    aliases = [b"\xaa" + i.to_bytes(31, "big") for i in range(3)]
    m.admit_device(
        1, aliases + [None] * (3 * TILE - 3),
        jnp.concatenate([enc_dev] * 3), jnp.concatenate([claim_dev] * 3),
        [len(e) for e in encs] + [0] * (3 * TILE - 3))
    cm = m._class(1)
    assert cm.fill == TILE  # one tile taken, two skipped
    assert m.rekey({a: keccak256(e) for a, e in zip(aliases, encs)}) == 3
    assert all(m.get(keccak256(e)) == e for e in encs)
    assert m.verify() == 0


def test_drop_aliases_forgets_unpublished_rows():
    """A torn window's aliases are dropped, never promoted: a later
    rekey with the same placeholder bytes must move nothing."""
    from khipu_tpu.storage.device_mirror import TILE

    m = DeviceNodeMirror(capacity_rows_per_class=1024)
    encs = [b"\x5a" * 44]
    enc_dev, claim_dev = _device_tile(encs)
    aliases = [b"\xaa" * 32]
    m.admit_device(
        1, aliases + [None] * (TILE - 1), enc_dev, claim_dev,
        [44] + [0] * (TILE - 1),
    )
    m.drop_aliases(aliases)
    assert m.rekey({aliases[0]: keccak256(encs[0])}) == 0
    assert m.get(keccak256(encs[0])) is None


def test_node_storage_read_through_and_detach():
    """NodeStorage falls through to the mirror for not-yet-spilled
    nodes; recovery's detach makes the same read miss (the mirror is
    volatile — crash verification must see host-durable state only)."""
    from khipu_tpu.storage.storages import Storages

    storages = Storages()
    m = DeviceNodeMirror(capacity_rows_per_class=1024)
    enc = b"\x42" * 80
    h = keccak256(enc)
    m.admit({h: enc})
    m.flush()
    storages.attach_mirror(m)
    assert storages.account_node_storage.get(h) == enc
    assert storages.storage_node_storage.get(h) == enc
    assert storages.get_node_any(h) == enc
    storages.detach_mirror()
    assert storages.account_node_storage.get(h) is None
    assert storages.get_node_any(h) is None


def test_long_string_overflow_rejected():
    """Adversarial RLP length fields near PY_SSIZE_T_MAX must raise
    RLPError (not wrap around) in BOTH codecs."""
    import pytest as _pytest

    from khipu_tpu.base import rlp as R

    for bad in (
        b"\xbf" + b"\x7f" + b"\xff" * 7,           # huge string length
        b"\xff" + b"\x7f" + b"\xff" * 7,           # huge list length
        b"\xbf" + b"\x00\x10" + b"\xff" * 6,       # non-canonical lead 0
    ):
        with _pytest.raises(R.RLPError):
            R.rlp_decode(bad)
        with _pytest.raises(R.RLPError):
            R._py_rlp_decode(bad)


# ---------------------------------------------- rows per size class


def _nodes_of(length: int, n: int, tag: int):
    encs = [bytes([tag]) + i.to_bytes(4, "big") + b"\x5a" * (length - 5)
            for i in range(n)]
    return {keccak256(e): e for e in encs}


def test_rows_per_size_class_from_a_mapping():
    m = DeviceNodeMirror({1: 3072, 4: 1024})
    small, big = _nodes_of(70, 2500, 1), _nodes_of(532, 900, 2)
    m.admit(small)
    m.admit(big)
    m.flush()
    by_class = {nb: cm for (nb, _exact), cm in m._classes.items()}
    assert by_class[1].capacity == 3072 and by_class[1].tiles == 3
    assert by_class[4].capacity == 1024 and by_class[4].tiles == 1
    # each class holds all of its own nodes: nothing wrapped round
    assert by_class[1].count == 2500 and by_class[4].count == 900
    assert m.resident_count == 3400 and m.verify() == 0


def test_a_class_the_mapping_does_not_name_takes_the_default_ring():
    m = DeviceNodeMirror({1: 2048})
    m.admit(_nodes_of(300, 10, 3))  # class 3
    m.flush()
    (cm,) = m._classes.values()
    assert cm.nblocks == 3 and cm.capacity == 16 * 1024
    assert m.capacity_by_class == {1: 2048}


@pytest.mark.parametrize("rows", [{1: 1000}, {"2": 1536}])
def test_a_class_of_the_mapping_still_wants_whole_tiles(rows):
    m = DeviceNodeMirror(rows)
    nb = int(next(iter(rows)))
    with pytest.raises(ValueError, match="multiple of 1024"):
        m.admit(_nodes_of(136 * nb - 60, 1, 4))
        m.flush()


def test_the_one_number_form_is_unchanged():
    m = DeviceNodeMirror(2048)
    assert m.capacity == 2048 and m.capacity_by_class == {}
    m.admit(_nodes_of(70, 5, 5))
    m.admit(_nodes_of(532, 5, 6))
    m.flush()
    assert {cm.capacity for cm in m._classes.values()} == {2048}
    # a class built after the number was changed from outside takes the
    # new one (benchmark/drivers/statesync.py sizes its classes so)
    m.capacity = 1024
    m.admit(_nodes_of(300, 5, 7))
    m.flush()
    assert m._class(3).capacity == 1024 and m._class(1).capacity == 2048
    assert DeviceNodeMirror().capacity == 16 * 1024
