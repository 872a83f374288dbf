"""Batched device Keccak vs the scalar oracle (SURVEY.md §4 test plan
item 2: kernel tests — batched digests vs known-good reference)."""

import random

import numpy as np
import pytest

from khipu_tpu.base.crypto.keccak import keccak256
from khipu_tpu.ops.keccak_jnp import keccak256_batch_jnp, pad_to_blocks
from khipu_tpu.ops.keccak import keccak256_batch


class TestJnpBatch:
    def test_small_sizes_vs_oracle(self):
        random.seed(7)
        # one- and two-block classes (keeps CPU compile time sane)
        msgs = [random.randbytes(n) for n in (0, 1, 31, 55, 56, 135, 136, 200, 271)]
        got = keccak256_batch_jnp(msgs)
        for g, m in zip(got, msgs):
            assert g == keccak256(m), f"len={len(m)}"

    def test_batch_order_preserved_across_buckets(self):
        random.seed(8)
        msgs = [random.randbytes(n) for n in (140, 3, 139, 7, 0)]
        got = keccak256_batch_jnp(msgs)
        assert [g for g in got] == [keccak256(m) for m in msgs]

    def test_empty_batch(self):
        assert keccak256_batch_jnp([]) == []

    def test_wrong_class_rejected(self):
        with pytest.raises(ValueError):
            pad_to_blocks([b"x" * 200], 1)

    def test_dispatcher_jnp_on_cpu(self):
        msgs = [b"khipu", b""]
        assert keccak256_batch(msgs, impl="auto") == [keccak256(m) for m in msgs]


class TestPallasInterpret:
    """Interpret-mode emulation of the kernel: minutes per tile on CPU,
    so marked slow (run with `pytest -m slow`). The round permutation
    itself (_round/_RC32) is fast-tested through the jnp path above,
    which the Pallas kernel shares verbatim."""

    @pytest.mark.slow
    def test_one_block_class_vs_oracle(self):
        from khipu_tpu.ops.keccak_pallas import keccak256_batch_pallas

        random.seed(9)
        msgs = [random.randbytes(n) for n in (0, 1, 64, 135)]
        got = keccak256_batch_pallas(msgs, interpret=True)
        for g, m in zip(got, msgs):
            assert g == keccak256(m), f"len={len(m)}"

    @pytest.mark.slow
    def test_fixed_path_vs_oracle(self):
        from khipu_tpu.ops.keccak_pallas import keccak256_fixed

        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, size=(6, 100), dtype=np.uint8)
        out = keccak256_fixed(data, interpret=True)
        assert out.shape == (6, 32)
        for i in range(6):
            assert out[i].tobytes() == keccak256(data[i].tobytes())


class TestPallasRateClassBound:
    """The kernel is built for rate classes 1..MAX_PALLAS_BLOCKS; the
    unrolled sponge costs seconds of compile per block (a 24 KB
    contract-creation pre-image is 181 blocks, and its input block
    outgrows VMEM), so anything longer hashes on the host's native
    Keccak and never reaches ``pl.pallas_call``."""

    @pytest.fixture
    def no_kernel(self, monkeypatch):
        """Any attempt to build a Pallas program fails the test."""
        from khipu_tpu.ops import keccak_pallas

        def boom(*a, **k):
            raise AssertionError("a message reached pl.pallas_call")

        monkeypatch.setattr(keccak_pallas, "_build_cached", boom)
        monkeypatch.setattr(keccak_pallas.pl, "pallas_call", boom)

    def test_25kb_message_hashes_on_the_host_bit_exact(self, no_kernel):
        random.seed(21)
        msgs = [random.randbytes(25_000), random.randbytes(680),
                random.randbytes(24_576)]
        got = keccak256_batch(msgs, impl="pallas")
        assert got == [keccak256(m) for m in msgs]

    def test_bound_is_the_first_length_of_class_six(self):
        from khipu_tpu.ops.keccak_jnp import RATE
        from khipu_tpu.ops.keccak_pallas import MAX_PALLAS_BLOCKS, _build

        assert MAX_PALLAS_BLOCKS == 5  # trie nodes 1-4, 576 B class 5
        assert 576 // RATE + 1 == MAX_PALLAS_BLOCKS
        with pytest.raises(ValueError, match="exceeds the Pallas bound"):
            _build(MAX_PALLAS_BLOCKS + 1, True)
        with pytest.raises(ValueError, match="exceeds the Pallas bound"):
            _build(181, False)

    def test_fixed_path_over_the_bound_hashes_on_the_host(self, no_kernel):
        from khipu_tpu.ops.keccak_pallas import keccak256_fixed

        rng = np.random.default_rng(5)
        data = rng.integers(0, 256, size=(7, 700), dtype=np.uint8)
        out = keccak256_fixed(data)
        assert out.shape == (7, 32)
        for i in range(7):
            assert out[i].tobytes() == keccak256(data[i].tobytes())

    def test_fused_program_refuses_a_class_past_the_bound(self, no_kernel):
        """A (never organic) > 5-block node makes the fused Pallas path
        decline the window — the caller's level loop routes it through
        ops.keccak, i.e. to the host — instead of asking Mosaic for a
        6-block sponge."""
        from khipu_tpu.trie.deferred import (
            _PLACEHOLDER_PREFIX,
            _make_placeholder,
        )
        from khipu_tpu.trie.fused import FusedUnsupported, fused_submit

        to_resolve = {_make_placeholder(0): b"\x7f" * 700}
        with pytest.raises(FusedUnsupported, match="Pallas bound"):
            fused_submit(to_resolve, {}, _PLACEHOLDER_PREFIX,
                         use_jnp=False, depth=1)

    def test_mixed_batch_scatters_back_in_input_order(
            self, no_kernel, monkeypatch):
        """Long and short messages interleaved: the long ones go to
        the host, ONLY the short ones to the kernel frame (stubbed
        here — interpret mode is minutes per tile), and the digests
        come back in input order."""
        from khipu_tpu.ops import keccak_jnp
        from khipu_tpu.ops.keccak_pallas import keccak256_batch_pallas

        sent_to_kernel = []

        def fake_frame(messages, target_count, run_bucket):
            sent_to_kernel.extend(messages)
            return [keccak256(m) for m in messages]

        monkeypatch.setattr(keccak_jnp, "bucketed_batch", fake_frame)
        random.seed(22)
        msgs = [random.randbytes(n)
                for n in (10, 25_000, 135, 700, 0, 679, 680)]
        assert keccak256_batch_pallas(msgs) == [keccak256(m) for m in msgs]
        assert [len(m) for m in sent_to_kernel] == [10, 135, 0, 679]

    @pytest.mark.slow
    def test_mixed_batch_keeps_input_order(self):
        from khipu_tpu.ops.keccak_pallas import keccak256_batch_pallas

        random.seed(22)
        msgs = [random.randbytes(n) for n in (10, 25_000, 135, 700, 0)]
        got = keccak256_batch_pallas(msgs, interpret=True)
        assert got == [keccak256(m) for m in msgs]


class TestPallasLayout:
    """Numpy-only checks of the Pallas host-side layout logic (retile and
    its inverse indexing) — the kernel-independent part that interpret
    mode would otherwise be the only off-TPU coverage for."""

    def test_retile_roundtrip_indexing(self):
        from khipu_tpu.ops.keccak_pallas import TILE, retile

        rng = np.random.default_rng(11)
        nblocks, batch = 2, 2 * TILE
        blocks = rng.integers(0, 2**32, size=(nblocks, 34, batch), dtype=np.uint64
                              ).astype(np.uint32)
        tiled = retile(blocks)
        assert tiled.shape == (batch // TILE, nblocks * 34, 8, 128)
        # message j's word w must land at [j // TILE, w, (j % TILE) // 128,
        # j % 128] — the exact inverse used by keccak256_batch_pallas.
        for j in (0, 1, 127, 128, 1023, 1024, 2047):
            t, r = divmod(j, TILE)
            s, l = divmod(r, 128)
            np.testing.assert_array_equal(
                tiled[t, :, s, l],
                blocks.reshape(nblocks * 34, batch)[:, j],
            )
