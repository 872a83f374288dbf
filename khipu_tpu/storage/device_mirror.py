"""Device-resident word-major node mirror — the hot-read store's TPU
half.

Role parity: the reference's production node store keeps hot trie nodes
in a memory-mapped Kesque table so reads never touch the cold store
(khipu-kesque/.../KesqueNodeDataSource.scala:18, 4KB-fetch design).
On TPU the analogous asset is not host RAM but HBM *in the kernel's
native layout*: this mirror keeps admitted nodes as multi-rate-padded
u32 word-major tiles ``[tiles, nwords, 8, 128]`` with their claimed
content addresses resident alongside, so the two hot batch operations
run with ZERO per-call layout work (docs/roofline.md identifies the
batch-major -> word-major HBM transpose as the last gap between the
full Keccak path and the kernel bound):

  * :meth:`verify` — re-hash every resident node and compare against
    its claimed hash (the fast-sync snapshot verification, BASELINE
    config #5) in ONE dispatch per size class;
  * sustained content-address hashing over the resident tiles
    (BASELINE config #2; ``chip_smoke.py``'s kernel leg).

The layout cost is paid once at ADMIT (write) time on the host, which
is the store-ingest side where the reference also pays its layout
(Kesque packs records into its log format at write). Source of truth
stays the backing byte store; the mirror is an accelerator cache with
ring eviction, safe to drop at any time.

Capacity is fixed per size class at construction (one number for all
classes, or rows per class: ``DeviceNodeMirror.__init__``): one preallocated
device buffer per class, filled in place with donated jit updates
(stable shapes -> a handful of XLA compiles for the process lifetime).
Unfilled rows hold a synthetic padding row whose claimed digest is
self-consistent by construction, so verify needs no masking.
"""

from __future__ import annotations

import threading
from functools import lru_cache
from typing import Dict, List, Mapping, Optional, Tuple, Union

import numpy as np

from khipu_tpu.observability.profiler import D2H, H2D, LEDGER
from khipu_tpu.observability.registry import REGISTRY
from khipu_tpu.observability.trace import span as _span
from khipu_tpu.ops.keccak_jnp import RATE, class_tag

TILE = 8 * 128  # messages per kernel tile (keccak_pallas.TILE)
DEFAULT_ROWS = 16 * TILE  # a size class's ring where no one says

MIRROR_GAUGES = REGISTRY.gauge_group("khipu_mirror", {
    # ring evictions that overwrote a window row BEFORE the persist
    # stage spilled it to the host store (the row stays readable
    # through the session's staged encodings, but the bulk-tile spill
    # must fall back to host substitution for it — a sizing signal:
    # nonzero means mirror_capacity_rows is too small for the
    # configured pipeline depth)
    "unspilled_evictions": 0,
    # whole resident tiles fetched by the bulk spill read-back
    "spilled_tiles": 0,
}, help="device-mirror spill watermark state (storage/device_mirror.py)")

def _count_hashed(nblocks: int, rows: int) -> None:
    """Rows the mirror sent through the Keccak kernel, by rate-block
    class: a class's filler tile, each partial tile's self-claim hash
    at install, and every row of the class (filler included) per
    verify. The registry hands back the same counter per class."""
    REGISTRY.counter(
        "khipu_mirror_hashed_rows_total",
        help="rows hashed on the device by the mirror "
             "(storage/device_mirror.py)",
        labels={"nblocks": str(nblocks)},
    ).inc(rows)


def _pack_word_major(padded_rows: np.ndarray) -> np.ndarray:
    """u8[N, nblocks*RATE] (N % TILE == 0) -> u32[tiles, nwords, 8, 128]
    — the kernel's native plane layout. Host-side, admit-time only."""
    n, width = padded_rows.shape
    nwords = width // 4
    words = (
        np.ascontiguousarray(padded_rows)
        .reshape(n, nwords, 4)
        .view("<u4")
        .reshape(n, nwords)
    )
    tiles = n // TILE
    return np.ascontiguousarray(
        words.reshape(tiles, 8, 128, nwords).transpose(0, 3, 1, 2)
    )


@lru_cache(maxsize=None)
def _class_kernels(nblocks: int, exact_len: Optional[int],
                   interpret: bool):
    """Process-wide jitted kernels for one (nblocks, exact_len) size
    class — hash runner, donated tile installers, verifier. Cached at
    module level (NOT per mirror instance) so a rebuilt mirror (tests,
    epoch restarts, one mirror per driver) reuses the XLA executables
    instead of paying a fresh multi-second compile per class."""
    import jax
    import jax.numpy as jnp

    from khipu_tpu import device

    width = exact_len if exact_len else nblocks * RATE
    nwords = width // 4
    # the device's name for each program of this class: a trace tells
    # programs apart by nothing else. `verify` is in the verify
    # program's name and in no other (trace readers find it by that)
    tag = class_tag(nblocks, nwords if exact_len else None)

    if device.platform() == "tpu":
        from khipu_tpu.ops.keccak_pallas import _build

        run = _build(
            nblocks, interpret,
            nwords_in=nwords if exact_len else None,
        )
    else:
        # CPU/test backend: XLA-compiled jnp sponge over the SAME
        # word-major plane layout (pallas interpret mode is orders
        # of magnitude too slow — same convention as trie/fused)
        from khipu_tpu.ops.keccak_jnp import hash_padded_u8

        full = nblocks * RATE

        def _run_jnp(planes):  # u32[t, nwords, 8, 128]
            t = planes.shape[0]
            words = planes.transpose(0, 2, 3, 1).reshape(
                t * TILE, nwords
            )
            u8 = jax.lax.bitcast_convert_type(
                words, jnp.uint8
            ).reshape(t * TILE, width)
            if exact_len is not None:  # fuse the multi-rate pad
                pad = jnp.zeros(
                    (t * TILE, full - width), dtype=jnp.uint8
                )
                u8 = jnp.concatenate([u8, pad], axis=1)
                u8 = u8.at[:, width].set(u8[:, width] ^ 0x01)
                u8 = u8.at[:, full - 1].set(u8[:, full - 1] ^ 0x80)
            digs = hash_padded_u8(u8, nblocks)  # u8[N, 32]
            dw = jax.lax.bitcast_convert_type(
                digs.reshape(t * TILE, 8, 4), jnp.uint32
            )
            return dw.reshape(t, 8, 128, 8).transpose(0, 3, 1, 2)

        run = device.named_jit(f"mirror_hash_tiles_{tag}", _run_jnp)

    # donated: the admit path updates the resident buffers in place
    # instead of copying the whole mirror per tile
    def set_tile(resident, claimed, tile_idx, planes, digs):
        resident = jax.lax.dynamic_update_slice(
            resident, planes[None], (tile_idx, 0, 0, 0)
        )
        claimed = jax.lax.dynamic_update_slice(
            claimed, digs[None], (tile_idx, 0, 0, 0)
        )
        return resident, claimed

    # DEVICE-RESIDENT admit: encodings + claimed digests already live
    # on device (row-major u8, e.g. gathered from a FusedJob's output);
    # the word-major retile runs here instead of on the host, so the
    # window-commit admit path uploads ZERO node bytes
    def admit_device(resident, claimed, tile_idx, enc_u8, claim_u8):
        words = jax.lax.bitcast_convert_type(
            enc_u8.reshape(TILE, nwords, 4), jnp.uint32
        )  # [TILE, nwords] little-endian — matches _pack_word_major
        planes = words.reshape(8, 128, nwords).transpose(2, 0, 1)
        cw = jax.lax.bitcast_convert_type(
            claim_u8.reshape(TILE, 8, 4), jnp.uint32
        )  # [TILE, 8]
        claim = cw.reshape(8, 128, 8).transpose(2, 0, 1)
        resident = jax.lax.dynamic_update_slice(
            resident, planes[None], (tile_idx, 0, 0, 0)
        )
        claimed = jax.lax.dynamic_update_slice(
            claimed, claim[None], (tile_idx, 0, 0, 0)
        )
        return resident, claimed

    def verify(resident, claimed):
        digs = run(resident)
        bad = jnp.any(digs != claimed, axis=1)  # (tiles, 8, 128)
        return jnp.sum(bad.astype(jnp.int32))

    return (
        run,
        device.named_jit(f"mirror_set_tile_{tag}", set_tile,
                         donate_argnums=(0, 1)),
        device.named_jit(f"mirror_admit_device_{tag}", admit_device,
                         donate_argnums=(0, 1)),
        device.named_jit(f"mirror_verify_{tag}", verify),
    )


def _filler_row_u8_for(width: int, exact_len: Optional[int]) -> np.ndarray:
    filler = np.zeros(width, dtype=np.uint8)
    if exact_len is None:
        filler[0] ^= 0x01
        filler[-1] ^= 0x80
    return filler


@lru_cache(maxsize=None)
def _filler_for(nblocks: int, exact_len: Optional[int],
                interpret: bool) -> Tuple[bytes, bytes]:
    """(filler plane words u32[nwords], filler digest u32[8]) as raw
    bytes — the synthetic padding row and its self-consistent digest,
    computed once per class per process (one small device round-trip)."""
    import jax

    width = exact_len if exact_len else nblocks * RATE
    run = _class_kernels(nblocks, exact_len, interpret)[0]
    tile = np.broadcast_to(
        _filler_row_u8_for(width, exact_len), (TILE, width)
    ).astype(np.uint8)
    planes = _pack_word_major(tile)
    # amortized one-time cost: billed to its own phase so the lazy
    # first-admit build never pollutes a steady-state stage's totals
    with LEDGER.context(phase="init"):
        LEDGER.record("mirror.init", H2D, planes.nbytes)
        with LEDGER.transfer("mirror.init", D2H, TILE * 32):
            d = np.asarray(jax.device_get(run(planes)))  # (1, 8, 8, 128)
    _count_hashed(nblocks, TILE)
    return (
        planes[0, :, 0, 0].copy().tobytes(),
        d[0, :, 0, 0].copy().tobytes(),
    )


class _ClassMirror:
    """One size class (fixed rate-block count).

    Thread model: the window-commit collect stage admits, the persist
    stage rekeys, and RPC/readers fetch rows — concurrently. ``_lock``
    serializes buffer installs (which DONATE the resident arrays —
    a reader holding the old reference would see a deleted buffer)
    against row fetches; the bookkeeping dicts ride along under the
    same lock for a consistent row <-> key view."""

    def _filler_row_u8(self) -> np.ndarray:
        return _filler_row_u8_for(self.width, self.exact_len)

    def __init__(self, nblocks: int, capacity_rows: int, interpret: bool,
                 exact_len: Optional[int] = None):
        """``exact_len``: every row of this class is exactly that many
        bytes (a multiple of 4) — rows are stored UNPADDED and the
        kernel fuses the multi-rate padding in registers, ~18% less
        HBM read per hash than the generic padded layout. The generic
        class (exact_len None) stores padded rows and serves any
        length within its rate-block count."""
        import jax
        import jax.numpy as jnp

        if capacity_rows % TILE:
            raise ValueError("capacity_rows must be a multiple of 1024")
        if exact_len is not None and exact_len % 4:
            raise ValueError("exact_len must be a multiple of 4")
        self.nblocks = nblocks
        self.exact_len = exact_len
        self.width = exact_len if exact_len else nblocks * RATE
        self.nwords = self.width // 4
        self.capacity = capacity_rows
        self.tiles = capacity_rows // TILE
        self.fill = 0  # ring write pointer (rows)
        self.count = 0  # resident rows (<= capacity)
        self.rows: Dict[bytes, int] = {}  # hash -> row
        # placeholder-keyed rows of not-yet-published windows: the
        # device-resident commit admits under the window's placeholder
        # ALIASES (real hashes are unknown until the persist stage
        # fetches the mapping) and rekey() moves them into ``rows``.
        # Kept OUT of the content-address namespace on purpose: a
        # stale alias (crashed window, reused placeholder counter)
        # must never serve a get() by hash.
        self.alias_rows: Dict[bytes, int] = {}
        self.row_hash: List[Optional[bytes]] = [None] * capacity_rows
        self.lengths: Dict[bytes, int] = {}  # exact unpadded length
        # the SPILL WATERMARK: keys admitted from a window commit that
        # the persist stage has not yet written to the host store.
        # Ring eviction consults this set — overwriting an unspilled
        # row is counted (khipu_mirror_unspilled_evictions) because it
        # forces the spill back onto the host-substitution path
        self.unspilled: set = set()
        self._lock = threading.RLock()
        (self._run, self._set_tile, self._admit_device,
         self._verify) = _class_kernels(nblocks, exact_len, interpret)
        fw, fd = _filler_for(nblocks, exact_len, interpret)
        self._filler_words = np.frombuffer(fw, dtype="<u4").copy()
        filler_digest = np.frombuffer(fd, dtype="<u4").copy()

        # one-time per-class buffer materialization. Only the two small
        # filler arrays are uploaded — the broadcast to full mirror
        # size happens on device — so that is what the ledger records
        # (site AND phase kept separate from the per-tile admit path:
        # classes build lazily on first admit, which runs inside the
        # collect stage, and this setup cost must not bill there)
        with LEDGER.context(phase="init"), LEDGER.transfer(
            "mirror.init", H2D,
            self._filler_words.nbytes + filler_digest.nbytes,
        ):
            self.resident = jax.device_put(
                jnp.broadcast_to(
                    jnp.asarray(self._filler_words)[None, :, None, None],
                    (self.tiles, self.nwords, 8, 128),
                ).astype(jnp.uint32)
            )
            self.claimed = jax.device_put(
                jnp.broadcast_to(
                    jnp.asarray(filler_digest)[None, :, None, None],
                    (self.tiles, 8, 8, 128),
                ).astype(jnp.uint32)
            )

    def admit_tile(self, hashes: List[bytes], padded: np.ndarray,
                   lengths: List[int]) -> None:
        """Install one full tile (1024 rows; short batches are filled
        with the synthetic row by the caller)."""
        import jax
        import jax.numpy as jnp

        planes = _pack_word_major(padded)
        # claimed digests come from the CLAIMED hashes, not our kernel
        # (verify must catch a corrupt admit); filler rows claim their
        # own digest. A FULL tile of real rows needs no kernel call —
        # partial tiles (at most one per class per flush) hash once so
        # their filler rows self-claim
        if len(hashes) >= TILE:
            claim_rows = np.frombuffer(
                b"".join(hashes), dtype="<u4"
            ).reshape(TILE, 8).copy()
        else:
            # partial-tile tax: one extra device round-trip (planes up,
            # self-claim digests back) that full tiles never pay — the
            # ledger is what makes this visible per window
            LEDGER.record("mirror.claim", H2D, planes.nbytes)
            with LEDGER.transfer("mirror.claim", D2H, TILE * 32):
                digs = np.asarray(
                    jax.device_get(self._run(planes))
                )  # (1, 8, 8, 128)
            _count_hashed(self.nblocks, TILE)
            claim_rows = (
                digs[0].transpose(1, 2, 0).reshape(TILE, 8).copy()
            )  # row-major [row, word]
            if hashes:
                claim_rows[: len(hashes)] = np.frombuffer(
                    b"".join(hashes), dtype="<u4"
                ).reshape(len(hashes), 8)
        claim = claim_rows.reshape(8, 128, 8).transpose(2, 0, 1)[None]
        claim = np.ascontiguousarray(claim)

        with self._lock:
            tile_idx = self.fill // TILE
            # the resident-tile refresh: one word-major plane + its
            # claim tile cross host->device per admitted tile
            with LEDGER.transfer(
                "mirror.admit", H2D, planes[0].nbytes + claim[0].nbytes
            ):
                self.resident, self.claimed = self._set_tile(
                    self.resident, self.claimed, tile_idx,
                    jnp.asarray(planes[0]), jnp.asarray(claim[0]),
                )
            self._bookkeep_tile(hashes, lengths, self.rows)

    def _evict_row(self, row: int) -> None:
        # evict only if the mapping still points HERE: a duplicate
        # re-admit may have moved the hash to a newer row, whose
        # entry must survive this slot's overwrite
        old = self.row_hash[row]
        if old is None:
            return
        if self.rows.get(old) == row:
            del self.rows[old]
            self.lengths.pop(old, None)
            self.count -= 1
        elif self.alias_rows.get(old) == row:
            del self.alias_rows[old]
            self.lengths.pop(old, None)
            self.count -= 1
        else:
            return
        # spill-watermark check: overwriting a row the persist stage
        # has not spilled yet is legal (the session's staged encodings
        # still serve it) but costs the bulk spill its fast path
        if old in self.unspilled:
            self.unspilled.discard(old)
            MIRROR_GAUGES["unspilled_evictions"] += 1

    def _bookkeep_tile(self, keys, lengths,
                       target: Dict[bytes, int]) -> None:
        """Row <-> key accounting for one freshly installed tile
        starting at ``self.fill`` (lock held by caller)."""
        for r in range(TILE):
            row = self.fill + r
            self._evict_row(row)
            h = keys[r] if r < len(keys) else None
            self.row_hash[row] = h
            if h is not None:
                if h not in target:
                    self.count += 1  # re-admit of a resident key
                target[h] = row  # latest copy wins
                self.lengths[h] = int(lengths[r])
        self.fill = (self.fill + TILE) % self.capacity

    def admit_tile_device(self, keys: List[Optional[bytes]],
                          enc_dev, claim_dev, lengths,
                          alias: bool = True) -> None:
        """Install one tile whose encodings (u8[TILE, width]) and
        claimed digests (u8[TILE, 32]) ALREADY live on device — the
        window-commit path. No node bytes leave the device; the
        word-major retile happens in the donated jit. ``alias`` keys
        go to the placeholder namespace (see ``alias_rows``)."""
        with self._lock, _span("mirror.admit_tile", rows=len(keys)):
            tile_idx = self.fill // TILE
            self.resident, self.claimed = self._admit_device(
                self.resident, self.claimed, tile_idx,
                enc_dev, claim_dev,
            )
            self._bookkeep_tile(
                keys, lengths, self.alias_rows if alias else self.rows
            )
            if alias:
                # below the spill watermark until persist reads them
                self.unspilled.update(
                    k for k in keys if k is not None
                )

    def rekey(self, mapping: Mapping[bytes, bytes]) -> int:
        """Move alias-keyed rows to their real content addresses once
        the persist stage has fetched the window's placeholder->digest
        mapping. Returns the number of rows promoted."""
        moved = 0
        with self._lock:
            for alias, real in mapping.items():
                row = self.alias_rows.pop(alias, None)
                if row is None:
                    continue
                if self.row_hash[row] != alias:
                    continue  # slot was ring-evicted since admit
                if real in self.rows:
                    self.count -= 1  # duplicate: newer copy wins below
                self.rows[real] = row
                self.row_hash[row] = real
                ln = self.lengths.pop(alias, None)
                if ln is not None:
                    self.lengths[real] = ln
                if alias in self.unspilled:
                    self.unspilled.discard(alias)
                    self.unspilled.add(real)
                moved += 1
        return moved

    def drop_aliases(self, aliases) -> None:
        """Forget alias rows without promoting them (torn window)."""
        with self._lock:
            for alias in aliases:
                row = self.alias_rows.pop(alias, None)
                if row is not None and self.row_hash[row] == alias:
                    self.row_hash[row] = None
                    self.count -= 1
                self.lengths.pop(alias, None)
                self.unspilled.discard(alias)

    def fetch_row(self, key: bytes) -> Optional[bytes]:
        """Read one row back by content address (unpadded). Lock held
        across the device fetch so a concurrent donated install can't
        delete the buffer under us."""
        import jax

        with self._lock:
            row = self.rows.get(key)
            if row is None:
                return None
            ln = self.lengths.get(key)
            if ln is None:
                return None
            t, r = divmod(row, TILE)
            i, j = divmod(r, 128)
            with LEDGER.transfer("mirror.get", D2H, self.nwords * 4):
                words = np.asarray(
                    # khipu-lint: ok KL004 fetch must finish under the install lock
                    jax.device_get(self.resident[t, :, i, j])
                ).astype("<u4")
            return words.tobytes()[:ln]

    def spill_rows(self, keys) -> Dict[bytes, bytes]:
        """Bulk read-back for the persist spill: ONE whole-tile array
        slice per resident tile covering the requested keys, instead
        of a device round-trip per node (``fetch_row``). Rows come
        back FINAL (the admitted encodings already carry real child
        digests), unpadded via the stored lengths. Keys not resident
        (ring-evicted before the spill) are simply absent — the
        caller substitutes those on the host. Fetched keys drop below
        the spill watermark."""
        import jax

        out: Dict[bytes, bytes] = {}
        with self._lock:
            by_tile: Dict[int, List[Tuple[bytes, int, int]]] = {}
            for key in keys:
                row = self.rows.get(key)
                if row is None:
                    row = self.alias_rows.get(key)
                if row is None:
                    continue
                ln = self.lengths.get(key)
                if not ln:
                    continue
                by_tile.setdefault(row // TILE, []).append(
                    (key, row % TILE, ln)
                )
            for t in sorted(by_tile):
                with LEDGER.transfer(
                    "mirror.spill", D2H, self.nwords * 4 * TILE
                ):
                    planes = np.asarray(
                        # khipu-lint: ok KL004 fetch must finish under the install lock
                        jax.device_get(self.resident[t])
                    )  # u32[nwords, 8, 128]
                MIRROR_GAUGES["spilled_tiles"] += 1
                # word-major -> row-major: row r of the tile lives at
                # [:, r // 128, r % 128] (same mapping as fetch_row)
                rows_u8 = np.ascontiguousarray(
                    planes.transpose(1, 2, 0).reshape(TILE, self.nwords)
                    .astype("<u4")
                ).view(np.uint8).reshape(TILE, self.width)
                for key, r, ln in by_tile[t]:
                    out[key] = rows_u8[r, :ln].tobytes()
                    self.unspilled.discard(key)
        return out

    def verify(self) -> int:
        import jax

        # lock held across the dispatch: a concurrent donated install
        # would delete the very buffers we are hashing
        with self._lock, _span("mirror.verify_class", nblocks=self.nblocks,
                               rows=self.count, tiles=self.tiles):
            with LEDGER.transfer("mirror.verify", D2H, 4):
                bad = int(
                    # khipu-lint: ok KL004 hash must read under the install lock
                    jax.device_get(
                        self._verify(self.resident, self.claimed)
                    )
                )
        _count_hashed(self.nblocks, self.capacity)
        return bad


class DeviceNodeMirror:
    """Multi-class device mirror; admit in batches, verify in one
    dispatch per class. See module docstring."""

    def __init__(
        self,
        capacity_rows_per_class: Union[int, Mapping[int, int]] = DEFAULT_ROWS,
        interpret: bool = False,
    ):
        """One number: every size class gets that many rows (the replay
        path's ring). A mapping ``{rate blocks: rows}``: each class it
        names gets its own rows, because a snapshot's classes differ a
        hundred to one (fast sync sizes it from
        ``SyncConfig.fast_sync_mirror_rows``); a class it does not name
        takes the default ring."""
        self.capacity = DEFAULT_ROWS
        self.capacity_by_class: Dict[int, int] = {}
        if isinstance(capacity_rows_per_class, Mapping):
            self.capacity_by_class = {
                int(nb): int(rows)
                for nb, rows in capacity_rows_per_class.items()
            }
        else:
            self.capacity = capacity_rows_per_class
        self.interpret = interpret
        # keyed by (nblocks, exact_len-or-None): generic padded classes
        # serve arbitrary node lengths; exact classes store uniform-
        # length populations unpadded (in-kernel pad, less HBM/hash)
        self._classes: Dict[Tuple[int, Optional[int]], _ClassMirror] = {}
        # host staging until a whole tile per class is ready
        self._pending: Dict[int, List[Tuple[bytes, bytes]]] = {}

    def _class(self, nblocks: int,
               exact_len: Optional[int] = None) -> _ClassMirror:
        key = (nblocks, exact_len)
        cm = self._classes.get(key)
        if cm is None:
            cm = _ClassMirror(
                nblocks,
                self.capacity_by_class.get(nblocks, self.capacity),
                self.interpret, exact_len,
            )
            self._classes[key] = cm
        return cm

    def admit(self, items: Mapping[bytes, bytes]) -> None:
        """Stage nodes (hash -> encoding); full 1024-row tiles upload
        immediately, the remainder stays staged until flush()."""
        with _span("mirror.admit", rows=len(items)):
            for h, enc in items.items():
                nb = len(enc) // RATE + 1
                self._pending.setdefault(nb, []).append((h, enc))
            for nb, pend in self._pending.items():
                while len(pend) >= TILE:
                    self._install(nb, pend[:TILE])
                    del pend[:TILE]

    def flush(self) -> None:
        """Upload partial tiles (padded out with synthetic rows)."""
        with _span("mirror.flush"):
            for nb, pend in self._pending.items():
                if pend:
                    self._install(nb, pend)
                    pend.clear()

    def admit_packed(self, hashes: List[bytes], rows: np.ndarray,
                     lengths: Optional[List[int]] = None,
                     exact: bool = False) -> None:
        """Bulk admit of one size class, N a multiple of 1024 — the
        vectorized ingest the snapshot-verify bench and bulk loaders
        use (per-row staging would dominate at millions of nodes).

        ``exact`` True: ``rows`` are RAW uniform-length encodings
        (length a multiple of 4) stored unpadded in an exact-length
        class — the kernel pads in registers. Otherwise ``rows`` are
        already multi-rate padded for their rate-block class."""
        n, width = rows.shape
        if n % TILE:
            raise ValueError("admit_packed wants whole 1024-row tiles")
        if exact:
            cm = self._class(width // RATE + 1, exact_len=width)
        else:
            if width % RATE:
                raise ValueError("padded rows must span whole blocks")
            cm = self._class(width // RATE)
        for start in range(0, n, TILE):
            chunk = hashes[start : start + TILE]
            cm.admit_tile(
                chunk,
                rows[start : start + TILE],
                (lengths[start : start + TILE] if lengths
                 else [width] * TILE),
            )

    def _install(self, nb: int, batch: List[Tuple[bytes, bytes]]) -> None:
        cm = self._class(nb)
        padded = np.broadcast_to(
            cm._filler_row_u8(), (TILE, cm.width)
        ).copy()
        hashes: List[bytes] = []
        lengths: List[int] = []
        for r, (h, enc) in enumerate(batch):
            padded[r, :] = 0
            padded[r, : len(enc)] = np.frombuffer(enc, dtype=np.uint8)
            padded[r, len(enc)] ^= 0x01
            padded[r, cm.width - 1] ^= 0x80
            hashes.append(h)
            lengths.append(len(enc))
        cm.admit_tile(hashes, padded, lengths)

    # ----------------------------------------------- device-side admit

    def admit_device(self, nblocks: int, keys: List[Optional[bytes]],
                     enc_dev, claim_dev, lengths: List[int],
                     alias: bool = True) -> None:
        """Admit rows whose padded encodings (u8[N, nblocks*RATE]) and
        claimed digests (u8[N, 32]) already live ON DEVICE, N a
        multiple of 1024; a tile without a key is skipped. This is the
        window-commit ingest: gathers from a FusedJob's outputs feed
        straight in, zero node bytes uploaded. ``alias`` keys land in the placeholder
        namespace until :meth:`rekey` publishes them."""
        n = enc_dev.shape[0]
        if n % TILE:
            raise ValueError("admit_device wants whole 1024-row tiles")
        cm = self._class(nblocks)
        for start in range(0, n, TILE):
            if all(k is None for k in keys[start : start + TILE]):
                continue  # padding only (the fused program's spare
                # admit slots): nothing to make resident
            cm.admit_tile_device(
                keys[start : start + TILE],
                enc_dev[start : start + TILE],
                claim_dev[start : start + TILE],
                lengths[start : start + TILE],
                alias=alias,
            )

    def rekey(self, mapping: Mapping[bytes, bytes]) -> int:
        """Promote alias-admitted rows to their real content addresses
        (persist stage, once the placeholder->digest mapping is on
        host). Returns rows promoted across all classes."""
        moved = 0
        for cm in list(self._classes.values()):
            if cm.alias_rows:
                moved += cm.rekey(mapping)
        return moved

    def drop_aliases(self, aliases) -> None:
        """Forget un-published alias rows (torn/abandoned window)."""
        for cm in list(self._classes.values()):
            if cm.alias_rows:
                cm.drop_aliases(aliases)

    def spill_rows(self, keys) -> Dict[bytes, bytes]:
        """Bulk-tile read-back of resident rows for the persist spill:
        one array-slice fetch per covered mirror tile per class (site
        ``mirror.spill``). Missing keys (evicted, never admitted) are
        absent from the result — the caller's host path covers them."""
        out: Dict[bytes, bytes] = {}
        remaining = list(keys)
        with _span("mirror.spill", rows=len(remaining)):
            for cm in list(self._classes.values()):
                if not remaining:
                    break
                got = cm.spill_rows(remaining)
                if got:
                    out.update(got)
                    remaining = [k for k in remaining if k not in out]
        return out

    @property
    def unspilled_count(self) -> int:
        return sum(
            len(cm.unspilled) for cm in list(self._classes.values())
        )

    # ------------------------------------------------------------ reads

    def contains(self, h: bytes) -> bool:
        for cm in list(self._classes.values()):
            if h in cm.rows:
                return True
        return any(h == ph for pend in self._pending.values()
                   for ph, _ in pend)

    def get(self, h: bytes) -> Optional[bytes]:
        """Read a node back from the device mirror (unpads via the
        stored exact length). Serves not-yet-spilled window nodes to
        the host read path (NodeStorage falls through here), so it
        must be safe against concurrent admits — each class fetch
        runs under that class's lock."""
        for cm in list(self._classes.values()):
            enc = cm.fetch_row(h)
            if enc is not None:
                return enc
        for pend in self._pending.values():
            for ph, enc in pend:
                if ph == h:
                    return enc
        return None

    # ------------------------------------------------------------ stats

    @property
    def resident_count(self) -> int:
        return sum(cm.count for cm in list(self._classes.values()))

    def verify(self) -> int:
        """Re-hash EVERY resident node on device and count content-
        address mismatches — one dispatch per size class, zero layout
        work (the tiles already live in kernel layout)."""
        with _span("mirror.verify", classes=len(self._classes)):
            return sum(cm.verify() for cm in list(self._classes.values()))


