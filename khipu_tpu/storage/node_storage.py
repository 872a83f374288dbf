"""Node storages over NodeDataSource.

Parity: khipu-eth/.../storage/NodeStorage.scala:7 (unconfirmed ring,
never deletes from the source :16-19), ReadOnlyNodeStorage (buffering
wrapper for eth_call simulation), ArchiveNodeStorage (no prune).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, List, Mapping, Optional

from khipu_tpu.storage.cache import FIFOCache
from khipu_tpu.storage.unconfirmed import SimpleMapWithUnconfirmed


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
        # RPC threads, so the adds are under a lock of their own.
        self._miss_lock = threading.Lock()
        self.source_reads = 0
        self.mirror_reads = 0
        self.absent_reads = 0
        self.source_seconds = 0.0

    def get(self, key: bytes) -> Optional[bytes]:
        v = self._cache.get(key)
        if v is not None:
            return v
        t0 = time.perf_counter()
        v = self._unconfirmed.get(key)
        dt = time.perf_counter() - t0
        if v is not None:
            with self._miss_lock:
                self.source_seconds += dt
                self.source_reads += 1
            self._cache.put(key, v)
            return v
        m = self.mirror
        if m is not None:
            v = m.get(key)
        with self._miss_lock:
            self.source_seconds += dt
            if v is not None:
                self.mirror_reads += 1
            else:
                self.absent_reads += 1
        return v

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
        out = [
            ("khipu_nodestore_reads_total", "counter",
             {"store": store, "from": origin}, n)
            for origin, n in (
                ("cache", self._cache.hits),
                ("source", self.source_reads),
                ("mirror", self.mirror_reads),
                ("absent", self.absent_reads),
            )
        ]
        out.append(("khipu_nodestore_source_seconds_total", "counter",
                    {"store": store}, round(self.source_seconds, 6)))
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
