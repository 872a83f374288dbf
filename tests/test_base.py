"""Unit tests for L0 primitives: RLP, hex-prefix, Keccak.

Vector sources: Ethereum Yellow Paper appendix B examples and the
Keccak reference digests (also exercised by the reference's
crypto/package.scala kec256 call sites).
"""

import pytest

from khipu_tpu.base import EMPTY_KECCAK, EMPTY_TRIE_HASH
from khipu_tpu.base.crypto.keccak import keccak256, keccak512
from khipu_tpu.base.nibbles import (
    bytes_to_nibbles,
    hp_decode,
    hp_encode,
)
from khipu_tpu.base.rlp import (
    RLPError,
    decode_int,
    rlp_decode,
    rlp_encode,
    rlp_encode_int,
)


class TestKeccak:
    def test_empty(self):
        assert keccak256(b"") == EMPTY_KECCAK

    def test_abc(self):
        assert (
            keccak256(b"abc").hex()
            == "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45"
        )

    def test_empty_trie_root(self):
        # root of the empty MPT = keccak256(rlp(b""))
        assert keccak256(rlp_encode(b"")) == EMPTY_TRIE_HASH

    def test_multiblock_absorb_vs_hashlib_sha3(self):
        # Independent cross-validation of the permutation + multi-block
        # absorb loop: our sponge with NIST domain byte 0x06 must equal
        # hashlib's SHA3-256 (OpenSSL). Combined with the single-block
        # Keccak known-answer vectors (which pin the 0x01 domain), this
        # covers the whole multi-block path.
        import hashlib

        from khipu_tpu.base.crypto.keccak import sha3_256

        for n in (0, 1, 135, 136, 137, 272, 500, 1000, 4096):
            data = bytes((i * 7 + n) % 256 for i in range(n))
            assert sha3_256(data) == hashlib.sha3_256(data).digest(), n

    def test_keccak512_len(self):
        assert len(keccak512(b"khipu")) == 64

    def test_rate_boundary(self):
        # exactly one rate block of input → two permutations (pad block)
        for n in (135, 136, 137, 271, 272, 273):
            assert len(keccak256(b"\x5a" * n)) == 32


class TestRLP:
    @pytest.mark.parametrize(
        "value,encoded",
        [
            (b"dog", bytes([0x83]) + b"dog"),
            (b"", bytes([0x80])),
            (b"\x0f", bytes([0x0F])),
            (b"\x04\x00", bytes([0x82, 0x04, 0x00])),
            ([], bytes([0xC0])),
            ([b"cat", b"dog"], bytes([0xC8, 0x83]) + b"cat" + bytes([0x83]) + b"dog"),
        ],
    )
    def test_yellow_paper_vectors(self, value, encoded):
        assert rlp_encode(value) == encoded
        assert rlp_decode(encoded) == value

    def test_long_string(self):
        s = b"Lorem ipsum dolor sit amet, consectetur adipisicing elit"
        enc = rlp_encode(s)
        assert enc[:2] == bytes([0xB8, 0x38])
        assert rlp_decode(enc) == s

    def test_nested_list(self):
        v = [[], [[]], [[], [[]]]]
        assert rlp_decode(rlp_encode(v)) == v

    def test_long_list(self):
        v = [b"x" * 40, b"y" * 40]
        enc = rlp_encode(v)
        assert enc[0] == 0xF8
        assert rlp_decode(enc) == v

    def test_scalars(self):
        assert rlp_encode_int(0) == bytes([0x80])
        assert rlp_encode_int(15) == bytes([0x0F])
        assert rlp_encode_int(1024) == bytes([0x82, 0x04, 0x00])
        assert decode_int(b"\x04\x00") == 1024
        assert decode_int(b"") == 0

    def test_reject_noncanonical(self):
        with pytest.raises(RLPError):
            rlp_decode(bytes([0x81, 0x05]))  # single byte <0x80 must be itself
        with pytest.raises(RLPError):
            rlp_decode(bytes([0x83]) + b"ab")  # truncated
        with pytest.raises(RLPError):
            rlp_decode(rlp_encode(b"dog") + b"!")  # trailing bytes
        with pytest.raises(RLPError):
            decode_int(b"\x00\x01")  # leading zero scalar

    def test_depth_cap(self):
        # adversarial deep nesting must be a clean RLPError, not RecursionError
        payload = bytes([0xC0])
        for _ in range(200):
            n = len(payload)
            if n < 56:
                payload = bytes([0xC0 + n]) + payload
            else:
                lb = n.to_bytes((n.bit_length() + 7) // 8, "big")
                payload = bytes([0xF7 + len(lb)]) + lb + payload
        with pytest.raises(RLPError):
            rlp_decode(payload)
        v = b"x"
        for _ in range(100):
            v = [v]
        with pytest.raises(RLPError):
            rlp_encode(v)

    def test_roundtrip_large(self):
        payload = [bytes([i % 256]) * (i % 70) for i in range(200)]
        assert rlp_decode(rlp_encode(payload)) == payload


class TestHexPrefix:
    def test_bytes_to_nibbles(self):
        assert bytes_to_nibbles(b"\x12\xab") == bytes([1, 2, 0xA, 0xB])

    @pytest.mark.parametrize(
        "nibbles,is_leaf,expect",
        [
            # Yellow Paper / ethereum wiki hex-prefix examples
            (bytes([1, 2, 3, 4, 5]), False, bytes([0x11, 0x23, 0x45])),
            (bytes([0, 1, 2, 3, 4, 5]), False, bytes([0x00, 0x01, 0x23, 0x45])),
            (bytes([0, 0xF, 1, 0xC, 0xB, 8]), True, bytes([0x20, 0x0F, 0x1C, 0xB8])),
            (bytes([0xF, 1, 0xC, 0xB, 8]), True, bytes([0x3F, 0x1C, 0xB8])),
        ],
    )
    def test_hp_vectors(self, nibbles, is_leaf, expect):
        assert hp_encode(nibbles, is_leaf) == expect
        assert hp_decode(expect) == (nibbles, is_leaf)

    def test_roundtrip(self):
        for n in range(0, 10):
            nib = bytes(i % 16 for i in range(n))
            for leaf in (False, True):
                assert hp_decode(hp_encode(nib, leaf)) == (nib, leaf)


class TestNativeRLPCodec:
    """The C-extension RLP codec (native/csrc_ext/rlp_ext.c) must be
    bit-identical to the pure-Python reference, including canonical-
    form rejection and the nesting cap."""

    def test_differential_fuzz(self):
        import random

        from khipu_tpu.base import rlp as R

        rng = random.Random(99)

        def rand_item(depth=0):
            if depth > 3 or rng.random() < 0.6:
                return rng.randbytes(rng.randint(0, 90))
            return [rand_item(depth + 1) for _ in range(rng.randint(0, 6))]

        def norm(x):
            if isinstance(x, list):
                return [norm(i) for i in x]
            return bytes(x)

        for _ in range(500):
            it = rand_item()
            enc = R.rlp_encode(it)
            assert enc == R._py_rlp_encode(it)
            assert R.rlp_decode(enc) == norm(it)
            assert R._py_rlp_decode(enc) == R.rlp_decode(enc)

    def test_error_parity(self):
        import pytest as _pytest

        from khipu_tpu.base import rlp as R

        for bad in (b"", b"\x81\x05", b"\xb8\x01a", b"\xc1", b"\x80x"):
            with _pytest.raises(R.RLPError):
                R.rlp_decode(bad)
            with _pytest.raises(R.RLPError):
                R._py_rlp_decode(bad)

    def test_depth_cap(self):
        import pytest as _pytest

        from khipu_tpu.base import rlp as R

        deep = [b"h"]
        for _ in range(R.MAX_DEPTH + 5):
            deep = [deep]
        with _pytest.raises(R.RLPError):
            R.rlp_encode(deep)
        with _pytest.raises(R.RLPError):
            R._py_rlp_encode(deep)


class TestConfigSurface:
    def test_leaf_config_fields_are_counted(self):
        """Every field is a configuration somebody has to cover: a PR
        that adds or removes one changes this number on purpose.
        KhipuConfig's own fields are the leaf groups, not options."""
        import dataclasses

        from khipu_tpu import config

        leaves = [
            cls for cls in vars(config).values()
            if dataclasses.is_dataclass(cls) and isinstance(cls, type)
            and cls is not config.KhipuConfig
        ]
        assert len(leaves) == 9
        assert sum(len(dataclasses.fields(c)) for c in leaves) == 119
        sync = {f.name for f in dataclasses.fields(config.SyncConfig)}
        assert len(sync) == 28
        # added in PR 44, with fast sync through the node's normal path:
        # the fast-sync mirror's rows per size class (the pool's cap is
        # a constant of sync/fast_sync_service.py: one value in use)
        assert "fast_sync_mirror_rows" in sync
        assert "max_concurrent_requests" not in sync
        # retired in PR 29 (one never ran, one lost on the chip): no
        # execute-stage device switch, no sender hash switch
        assert not [n for n in sync if n.startswith("exec_")
                    or n.endswith("_hash")]
