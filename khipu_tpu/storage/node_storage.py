"""Node storages over NodeDataSource.

Parity: khipu-eth/.../storage/NodeStorage.scala:7 (unconfirmed ring,
never deletes from the source :16-19), ReadOnlyNodeStorage (buffering
wrapper for eth_call simulation), ArchiveNodeStorage (no prune).
"""

from __future__ import annotations

import time
from typing import Dict, Iterable, List, Mapping, Optional

from khipu_tpu.observability.thread_books import ThreadBooks
from khipu_tpu.storage.cache import FIFOCache
from khipu_tpu.storage.unconfirmed import SimpleMapWithUnconfirmed

# columns of a thread's book of misses (NodeStorage._misses)
_SOURCE, _MIRROR, _ABSENT, _SECONDS = range(4)


class NodeStorage:
    """hash -> node-rlp store with reorg ring + FIFO read cache.

    Deletes are swallowed: a content-addressed archive store never
    removes nodes (NodeStorage.scala:16-19)."""

    def __init__(self, source, depth: int = 20, cache_size: int = 1 << 20):
        self.source = source
        self._unconfirmed = SimpleMapWithUnconfirmed(source, depth)
        self._unconfirmed.set_buffering(False)  # regular-sync switch turns on
        self._cache: FIFOCache = FIFOCache(cache_size)
        # device-resident read-through (storage/device_mirror.py): when
        # the window commit targets the device mirror, freshly committed
        # nodes live ONLY there until the async spill stage writes them
        # here. Attached by the replay driver; None = host-only reads.
        # Never cached on hit: the mirror ring-evicts, and the spill
        # lands the durable copy in the host store shortly after.
        self.mirror = None
        # where a read that missed the cache was answered from, and the
        # wall seconds the reading thread spent in its look in the
        # source (ring + engine, found or not; a wait for the GIL after
        # the engine's pread is inside it). Miss path only: hits are
        # FIFOCache's own count. Readers are the driver, persist and
        # RPC threads: each adds to a book of its own, no lock
        # (observability/thread_books.py), columns as _SOURCE.._SECONDS.
        self._misses = ThreadBooks(0, 0, 0, 0.0)

    def get(self, key: bytes) -> Optional[bytes]:
        v = self._cache.get(key)
        if v is not None:
            return v
        t0 = time.perf_counter()
        v = self._unconfirmed.get(key)
        dt = time.perf_counter() - t0
        book = self._misses.mine()
        book[_SECONDS] += dt
        if v is not None:
            book[_SOURCE] += 1
            self._cache.put(key, v)
            return v
        m = self.mirror
        if m is not None:
            v = m.get(key)
        book[_MIRROR if v is not None else _ABSENT] += 1
        return v

    @property
    def source_reads(self) -> int:
        return self._misses.of()[_SOURCE]

    @property
    def source_seconds(self) -> float:
        return self._misses.of()[_SECONDS]

    def thread_misses(self, ident: int) -> tuple:
        """What thread ``ident`` has paid on this store's miss path so
        far: ``(misses, seconds in the source look-up, of them waiting
        for the ring's and the engine's locks, of them in the engine's
        read)``. The engine's two are 0 where the source keeps no
        ``read_book`` (every engine but Kesque)."""
        src, mir, absent, seconds = self._misses.of(ident)
        wait = self._unconfirmed.lock_wait.of(ident)[0]
        engine = 0.0
        read_book = getattr(self.source, "read_book", None)
        if read_book is not None:
            _gets, engine_wait, engine = read_book(ident)
            wait += engine_wait
        return src + mir + absent, seconds, wait, engine

    def put(self, key: bytes, value: bytes) -> None:
        self.update([], {key: value})

    def update(
        self, to_remove: Iterable[bytes], to_upsert: Mapping[bytes, bytes]
    ) -> None:
        for k, v in to_upsert.items():
            self._cache.put(bytes(k), bytes(v))
        # to_remove intentionally dropped (never delete from source)
        self._unconfirmed.update([], to_upsert)

    def switch_to_unconfirmed(self) -> None:
        self._unconfirmed.set_buffering(True)

    def clear_unconfirmed(self) -> None:
        # The FIFO cache is populated by update()/get() with unconfirmed
        # values; dropping the ring without evicting those keys would
        # keep serving nodes that were never durably written (and mask
        # MPTNodeMissingException after a reorg + restart). Evict only
        # the dropped keys — confirmed hot nodes stay cached. The trie
        # layer's decoded-node cache (mpt.py attaches _mpt_dcache to its
        # source, i.e. this object) reads through get() and can hold the
        # same unconfirmed nodes — evict there too.
        dcache = getattr(self, "_mpt_dcache", None)
        for key in self._unconfirmed.clear_unconfirmed():
            self._cache.remove(key)
            if dcache is not None:
                dcache.pop(key, None)

    def flush(self) -> None:
        self._unconfirmed.flush()

    @property
    def cache_hit_rate(self) -> float:
        return self._cache.hit_rate

    @property
    def cache_read_count(self) -> int:
        return self._cache.read_count

    def registry_samples(self, store: str) -> list:
        """``khipu_nodestore_*`` samples of this store for a registry
        collector (``Storages.nodestore_samples``)."""
        src, mir, absent, seconds = self._misses.of()
        out = [
            ("khipu_nodestore_reads_total", "counter",
             {"store": store, "from": origin}, n)
            for origin, n in (
                ("cache", self._cache.hits),
                ("source", src),
                ("mirror", mir),
                ("absent", absent),
            )
        ]
        out.append(("khipu_nodestore_source_seconds_total", "counter",
                    {"store": store}, round(seconds, 6)))
        out.append(("khipu_nodestore_lock_wait_seconds_total", "counter",
                    {"store": store},
                    round(self._unconfirmed.lock_wait.of()[0], 6)))
        return out


class ReadOnlyNodeStorage:
    """Buffers writes in memory; underlying storage is never touched.

    Used by simulateTransaction / eth_call (ReadOnlyNodeStorage.scala).
    """

    def __init__(self, inner):
        self.inner = inner
        self._buffer: Dict[bytes, bytes] = {}

    def get(self, key: bytes) -> Optional[bytes]:
        v = self._buffer.get(key)
        return v if v is not None else self.inner.get(key)

    def put(self, key: bytes, value: bytes) -> None:
        self._buffer[bytes(key)] = bytes(value)

    def update(self, to_remove, to_upsert) -> None:
        for k, v in to_upsert.items():
            self._buffer[bytes(k)] = bytes(v)
