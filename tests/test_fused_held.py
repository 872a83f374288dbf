"""Held buckets of the fused fixpoint program (trie/fused.py
``HeldBuckets``): a count of the signature takes the largest bucket any
recent window of the same owner needed, so counts that sit on bucket
edges stop flipping the signature from window to window."""

import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from khipu_tpu.trie import fused  # noqa: E402

PREFIX = b"\xfe\xedPH"


class _Stop(Exception):
    pass


class _Signatures:
    """Stands in for the compile cache: keeps each signature asked for
    and stops the dispatch there (no program is built)."""

    def __init__(self):
        self.seen = []

    def lookup(self, sig, rounds, use_jnp, ext_rows=0):
        self.seen.append((sig, rounds, ext_rows))
        raise _Stop


def _window(n_by_class):
    """Leaves only: ``n`` encodings in each rate class, no child refs."""
    to_resolve = {}
    for nb, n in n_by_class.items():
        for i in range(n):
            ph = PREFIX + nb.to_bytes(1, "big") + i.to_bytes(26, "big")
            to_resolve[ph] = bytes([nb]) * ((nb - 1) * fused.RATE + 40)
    return to_resolve


def _signature(monkeypatch, n_by_class, held, ext=None):
    seen = _Signatures()
    monkeypatch.setattr(fused, "_build_fused", seen)
    to_resolve = _window(n_by_class)
    with pytest.raises(_Stop):
        fused.fused_submit(to_resolve, {ph: [] for ph in to_resolve},
                           PREFIX, use_jnp=True, depth=1, ext=ext,
                           held=held)
    (sig, rounds, ext_rows), = seen.seen
    return {nb: rows for nb, rows, _, _ in sig}, ext_rows


@pytest.mark.parametrize("asked,want", [
    ([64, 16, 32], [64, 64, 64]),  # held at the largest so far
    ([16, 32, 64, 32], [16, 32, 64, 64]),  # raised when outgrown
    ([128, 16, 256, 64], [128, 128, 256, 256]),
])
def test_held_takes_the_largest_bucket_so_far(asked, want):
    held = fused.HeldBuckets()
    assert [held.take(("d",), b) for b in asked] == want


def test_dimensions_are_held_apart():
    held = fused.HeldBuckets()
    assert held.take((1, "rows"), 64) == 64
    assert held.take((2, "rows"), 16) == 16
    assert held.take((1, "subs"), 1024) == 1024
    assert held.take((1, "rows"), 16) == 64
    assert held.snapshot() == {
        ("ext",): fused.EXT_HELD_ROWS,
        (1, "rows"): 64, (2, "rows"): 16, (1, "subs"): 1024}


def test_a_new_record_starts_the_ext_tile_at_its_floor():
    held = fused.HeldBuckets()
    assert held.take(("ext",), fused.EXT_FLOOR) == fused.EXT_HELD_ROWS
    assert held.take(("ext",), 16384) == 16384
    assert fused.HeldBuckets().snapshot() == {
        ("ext",): fused.EXT_HELD_ROWS}  # a record each


def test_a_bucket_no_window_needed_for_long_falls_to_what_they_did(
        monkeypatch):
    """One outlier window does not pad every later window for good: a
    held bucket outlives the last window that needed it by HOLD_WINDOWS
    windows, then falls to the largest asked since."""
    monkeypatch.setattr(fused, "HOLD_WINDOWS", 8)
    held = fused.HeldBuckets()
    assert held.take(("d",), 64) == 64
    assert held.take(("d",), 4096) == 4096  # the outlier
    asked = [64, 128, 64, 64, 64, 64, 64]
    assert [held.take(("d",), b) for b in asked] == [4096] * 7
    assert held.take(("d",), 64) == 128  # the eighth window without it
    assert held.take(("d",), 64) == 128  # and 128 is held in its turn
    # a window that needs the held bucket again starts the count anew
    held.take(("e",), 4096)
    for _ in range(5):
        assert held.take(("e",), 64) == 4096
    assert held.take(("e",), 4096) == 4096
    assert [held.take(("e",), 64) for _ in range(7)] == [4096] * 7
    # the ext tile never falls under its floor
    assert [held.take(("ext",), fused.EXT_FLOOR)
            for _ in range(20)] == [fused.EXT_HELD_ROWS] * 20


def test_a_held_bucket_is_served_as_a_gauge():
    from khipu_tpu.observability.registry import REGISTRY

    held = fused.HeldBuckets()
    held.take((4, "rows"), 8192)
    held.take((4, "rows"), 4096)
    held.take((4, "subs"), 65536)
    text = REGISTRY.prometheus_text()
    assert 'khipu_fused_held_bucket{dim="4.rows"} 8192' in text
    assert 'khipu_fused_held_bucket{dim="4.subs"} 65536' in text
    assert ('khipu_fused_held_bucket{dim="ext"} '
            f'{fused.EXT_HELD_ROWS}') in text


def test_a_small_window_after_a_large_one_shares_its_signature(monkeypatch):
    held = fused.HeldBuckets()
    big, ext_big = _signature(monkeypatch, {1: 40, 4: 100}, held)
    small, ext_small = _signature(monkeypatch, {1: 5, 4: 3}, held)
    assert big == small == {1: 64, 2: 16, 3: 16, 4: 128}
    assert ext_big == ext_small == 8192
    larger, _ = _signature(monkeypatch, {1: 70, 4: 3}, held)
    assert larger == {1: 128, 2: 16, 3: 16, 4: 128}  # class 1 outgrew it


def test_admit_slots_follow_the_row_bucket_not_the_live_count(monkeypatch):
    """How many of a class's rows are live is no count of the signature:
    the admit slots are the class's row bucket in whole mirror tiles,
    with or without live rows in the class."""
    from khipu_tpu.storage.device_mirror import TILE

    seen = _Signatures()
    monkeypatch.setattr(fused, "_build_fused", seen)
    to_resolve = _window({1: 40, 4: 1500})
    phs = list(to_resolve)
    for live in (set(phs[:3]), set(phs[:30]), set(phs[40:1400])):
        with pytest.raises(_Stop):
            fused.fused_submit(to_resolve, {ph: [] for ph in to_resolve},
                               PREFIX, use_jnp=True, depth=1,
                               admit_live=live)
    admits = [{nb: nadmit for nb, _, _, nadmit in sig}
              for sig, _, _ in seen.seen]
    assert admits[0] == admits[1] == admits[2] == {
        1: TILE, 2: TILE, 3: TILE, 4: 2 * TILE}  # 2,048 rows of class 4
    assert len({sig for sig, _, _ in seen.seen}) == 1
    with pytest.raises(_Stop):  # no admit asked for: no admit slots
        fused.fused_submit(to_resolve, {ph: [] for ph in to_resolve},
                           PREFIX, use_jnp=True, depth=1)
    assert {nadmit for _, _, _, nadmit in seen.seen[-1][0]} == {0}


def test_a_call_without_a_record_starts_a_new_one(monkeypatch):
    big, ext_rows = _signature(monkeypatch, {1: 40, 4: 100}, None)
    small, _ = _signature(monkeypatch, {1: 5, 4: 3}, None)
    assert big == {1: 64, 2: 16, 3: 16, 4: 128}
    assert small == {1: 16, 2: 16, 3: 16, 4: 16}
    assert ext_rows == fused.EXT_HELD_ROWS


def test_the_ext_tile_is_gathered_at_the_held_size():
    import jax.numpy as jnp
    import numpy as np

    table = jnp.asarray(np.arange(256 * 32, dtype=np.uint8).reshape(256, 32))
    rows = np.asarray([3, 200, 7], dtype=np.int32)
    held = fused.HeldBuckets()
    tile, offsets = fused.gather_ext_tile([(table, rows)], held)
    assert tile.shape == (fused.EXT_HELD_ROWS, 32) and offsets == [0]
    np.testing.assert_array_equal(np.asarray(tile)[:3],
                                  np.asarray(table)[rows])
    held.take(("ext",), 2 * fused.EXT_HELD_ROWS)  # a larger window's
    tile, _ = fused.gather_ext_tile([(table, rows)], held)
    assert tile.shape == (2 * fused.EXT_HELD_ROWS, 32)


@pytest.mark.parametrize("device_commit", [False, True])
def test_the_replay_driver_keeps_one_record_for_its_node(device_commit):
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import Blockchain
    from khipu_tpu.ledger.window import WindowCommitter
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.replay import ReplayDriver
    from khipu_tpu.trie.mpt import EMPTY_TRIE_HASH

    cfg = fixture_config(chain_id=1)
    bc = Blockchain(Storages(), cfg)
    driver = ReplayDriver(bc, cfg, device_commit=device_commit)
    if not device_commit:  # the host path dispatches no fused program
        assert driver._fused_held is None
        return
    assert isinstance(driver._fused_held, fused.HeldBuckets)
    committer = WindowCommitter(bc.storages, EMPTY_TRIE_HASH, fused=True,
                                fused_held=driver._fused_held)
    assert committer.fused_held is driver._fused_held
    own = WindowCommitter(bc.storages, EMPTY_TRIE_HASH, fused=True)
    assert isinstance(own.fused_held, fused.HeldBuckets)  # its own
    assert own.fused_held is not driver._fused_held
