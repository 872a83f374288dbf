"""Fast-sync orchestration: pivot choice + multi-peer download scheduler.

Parity: blockchain/sync/FastSyncService.scala —
  pivot selection: ask every handshaked peer for its best header, take
  the MEDIAN best number minus ``pivot_block_offset`` (requires
  ``min_peers_to_choose_pivot`` peers)                    :184-273
  download scheduler: bounded-concurrency node requests spread across
  the peer pool; a stalling/failing peer is blacklisted and its
  work is redistributed                                   :537-667
  block-data backfill to the pivot (headers/bodies/receipts stored
  WITHOUT execution — the state arrives as the downloaded trie)

The queue/verify/persist half lives in sync/fast_sync.py (StateSyncer);
this module supplies its ``fetch`` callback from real peers and drives
the whole flow end to end.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Mapping, Optional

from khipu_tpu.native.keccak import keccak256_batch
from khipu_tpu.config import KhipuConfig
from khipu_tpu.domain.block import BlockBody
from khipu_tpu.domain.block_header import BlockHeader
from khipu_tpu.domain.blockchain import Blockchain
from khipu_tpu.domain.receipt import Receipt, encode_receipts
from khipu_tpu.network.messages import (
    BLOCK_BODIES,
    BLOCK_HEADERS,
    ETH_OFFSET,
    GET_BLOCK_BODIES,
    GET_BLOCK_HEADERS,
    GET_NODE_DATA,
    GET_RECEIPTS,
    NODE_DATA,
    RECEIPTS,
    GetBlockHeaders,
    decode_bodies,
    decode_headers,
)
from khipu_tpu.network.peer import Peer, PeerError, PeerManager
from khipu_tpu.observability.registry import REGISTRY
from khipu_tpu.observability.trace import current_tracer, span
from khipu_tpu.sync.fast_sync import FastSyncStateStorage, StateSyncer, SyncState
from khipu_tpu.validators.roots import (
    ommers_hash,
    receipts_root,
    transactions_root,
)


class FastSyncError(Exception):
    pass


# node-data requests the pool may have in flight, however many peers
# are live (the upstream's max-concurrent-requests)
MAX_CONCURRENT_REQUESTS = 50


def _pool_counter(name: str, help: str, **labels):
    return REGISTRY.counter(
        "khipu_fastsync_" + name,
        help=help + " (sync/fast_sync_service.py)", labels=labels or None)


# The pool's books: the process's, so they run on through a restarted
# sync's new pool. Added to once a request, under the pool's lock.
PEER_REQUESTS = {
    outcome: _pool_counter(
        "peer_requests_total", "node-data requests to peers, by outcome",
        outcome=outcome)
    for outcome in ("ok", "timeout", "garbage")
}
PEER_REQUEST_SECONDS = _pool_counter(
    "peer_request_seconds_total",
    "seconds from a node-data request's send to its answer or timeout")
PEER_BYTES = _pool_counter(
    "peer_bytes_total", "bytes of the blobs peers answered with")
PEERS_BLACKLISTED = _pool_counter(
    "peers_blacklisted_total",
    "peers blacklisted for stalling or for an answer that is no NodeData")
PEER_IN_FLIGHT_SECONDS = _pool_counter(
    "peer_requests_in_flight_seconds_total",
    "time-integral of node-data requests in flight: over the fetch "
    "phase's seconds, how many peers were working at once")


def _node_data(body) -> List[bytes]:
    """The blobs of a NodeData answer. ``Peer.request`` hands over the
    peer's RLP as it came: anything but a list of strings raises."""
    if not isinstance(body, list):
        raise ValueError("NodeData: not a list")
    if not all(isinstance(b, (bytes, bytearray, memoryview)) for b in body):
        raise ValueError("NodeData: a nested list among the blobs")
    return [bytes(b) for b in body]


class PeerFetchPool:
    """Spread node-data requests across live peers with bounded
    concurrency; timeout -> blacklist + redistribute
    (processDownload:537-667 role)."""

    def __init__(
        self,
        manager: PeerManager,
        nodes_per_request: int = 50,
        timeout: float = 5.0,
        max_rounds: int = 5,
        log: Optional[Callable[[str], None]] = None,
        cluster=None,
    ):
        self.manager = manager
        self.per_request = nodes_per_request
        self.timeout = timeout
        self.max_rounds = max_rounds
        self.log = log or (lambda s: None)
        self.blacklisted = 0
        # requests on the wire now, and since when that many
        self._in_flight = 0
        self._in_flight_since = 0.0
        self._lock = threading.Lock()
        # kept from call to call, started by the first ``fetch_nodes``
        # and ended by ``close``
        self._workers: Optional[ThreadPoolExecutor] = None
        self._rr = 0  # rotating start so small fetches still spread
        # sharded node-cache cluster: consulted before the peer pool —
        # a shard read is one verified RPC vs. a devp2p round-trip, and
        # the client's replica failover/breakers absorb dead shards
        self.cluster = cluster
        self.cluster_served = 0

    def _live_peers(self) -> List[Peer]:
        return [
            p for p in self.manager.peers
            if p.alive
            and not self.manager.blacklist.is_blacklisted(p.remote_pub)
        ]

    def width(self) -> int:
        """Requests the pool may have in flight: one a live peer, under
        the cap. A syncer batch of ``per_request`` times this gives
        ``fetch_nodes`` a chunk for every peer."""
        return max(1, min(len(self._live_peers()), MAX_CONCURRENT_REQUESTS))

    def close(self) -> None:
        """End the pool's worker threads (the next ``fetch_nodes``
        starts new ones)."""
        workers, self._workers = self._workers, None
        if workers is not None:
            workers.shutdown(wait=True)

    def _flight(self, change: int) -> None:
        """A request went out or came back (``_lock`` held): book the
        time the old count stood."""
        now = time.perf_counter()
        PEER_IN_FLIGHT_SECONDS.inc(
            self._in_flight * (now - self._in_flight_since))
        self._in_flight += change
        self._in_flight_since = now

    def fetch_nodes(self, hashes: List[bytes]) -> Mapping[bytes, bytes]:
        """StateSyncer fetch callback: every returned value is keyed by
        its CONTENT hash (NodeData replies carry no correlation)."""
        results: Dict[bytes, bytes] = {}
        pending = list(hashes)
        if self.cluster is not None and pending:
            try:
                got = self.cluster.fetch(pending)
            except Exception:
                got = {}
            results.update(got)  # values verified by the client
            self.cluster_served += len(got)
            pending = [h for h in pending if h not in results]
        for _ in range(self.max_rounds):
            if not pending:
                break
            peers = self._live_peers()
            if not peers:
                raise FastSyncError("no live peers for node download")
            start = self._rr % len(peers)
            self._rr += 1
            # one request a peer in flight, and no more than the cap
            peers = (peers[start:] + peers[:start])[:MAX_CONCURRENT_REQUESTS]
            chunks = [
                pending[i : i + self.per_request]
                for i in range(0, len(pending), self.per_request)
            ]
            lock = self._lock
            got_any = [False]
            # the workers' spans: this thread's tracer, under its span
            tracer = current_tracer()
            token = tracer.current_token()

            def worker(peer: Peer, mine: List[List[bytes]]) -> None:
                for chunk in mine:
                    with tracer.span(
                        "fastsync.pool.request", parent=token,
                        peer=peer.remote_pub[:4].hex(), hashes=len(chunk),
                    ) as sp:
                        with lock:
                            self._flight(+1)
                        t0 = time.perf_counter()
                        try:
                            body = peer.request(
                                ETH_OFFSET + GET_NODE_DATA,
                                list(chunk),
                                ETH_OFFSET + NODE_DATA,
                                timeout=self.timeout,
                            )
                            outcome = "ok"
                        except PeerError:
                            outcome = "timeout"
                        seconds = time.perf_counter() - t0
                        blobs: List[bytes] = []
                        if outcome == "ok":
                            # the peer's RLP, unchecked until here
                            try:
                                blobs = _node_data(body)
                            except ValueError:
                                outcome = "garbage"
                        nbytes = sum(map(len, blobs))
                        sp.set_tag("blobs", len(blobs))
                        sp.set_tag("bytes", nbytes)
                        sp.set_tag("outcome", outcome)
                        with lock:
                            self._flight(-1)
                            PEER_REQUESTS[outcome].inc()
                            PEER_REQUEST_SECONDS.inc(seconds)
                            PEER_BYTES.inc(nbytes)
                    if outcome != "ok":
                        # stalling, dead or lying peer: blacklist,
                        # abandon its remaining chunks (requeued by the
                        # outer round)
                        self.manager.blacklist.add(
                            peer.remote_pub, duration=600.0
                        )
                        peer.disconnect()
                        self.blacklisted += 1
                        PEERS_BLACKLISTED.inc()
                        self.log(
                            f"blacklisted peer {peer.remote_pub[:4].hex()}"
                            f": {outcome}"
                        )
                        return
                    # one native call hashes the answer's blobs, on
                    # this worker and outside the lock
                    keyed = dict(zip(keccak256_batch(blobs), blobs))
                    with lock:
                        results.update(keyed)
                        got_any[0] = got_any[0] or bool(keyed)

            # round-robin chunk assignment across the live pool
            assign: Dict[int, List[List[bytes]]] = {
                i: [] for i in range(len(peers))
            }
            for i, chunk in enumerate(chunks):
                assign[i % len(peers)].append(chunk)
            # the pool's own workers, kept from call to call: starting
            # a thread a peer a round cost more than the requests did
            # (each start waits for the GIL to go to the new thread and
            # come back, while the workers already out compete for it)
            if self._workers is None:
                self._workers = ThreadPoolExecutor(
                    max_workers=MAX_CONCURRENT_REQUESTS,
                    thread_name_prefix="fastsync-pool")
            for done in [
                self._workers.submit(worker, peers[i], assign[i])
                for i in range(len(peers))
                if assign[i]
            ]:
                done.result()
            pending = [h for h in pending if h not in results]
            if pending and not got_any[0] and not self._live_peers():
                break
        return results


class FastSyncService:
    """choose pivot -> download state via the peer pool -> backfill
    block data -> hand off at the pivot."""

    def __init__(
        self,
        blockchain: Blockchain,
        config: KhipuConfig,
        manager: PeerManager,
        hasher=None,
        log: Optional[Callable[[str], None]] = None,
        cluster=None,
        mirror=None,
    ):
        """``hasher``: the node's batched device hasher for the
        per-batch content-address check (None: the host's). ``mirror``:
        the node's ``DeviceNodeMirror``; every verified trie node is
        admitted to it and the sync closes with the device verify over
        all of it (None: no mirror, the per-batch check alone)."""
        self.blockchain = blockchain
        self.config = config
        self.manager = manager
        self.hasher = hasher
        self.mirror = mirror
        self.log = log or (lambda s: None)
        sync = config.sync
        self.min_peers = sync.min_peers_to_choose_pivot
        self.pivot_offset = sync.pivot_block_offset
        self.pool = PeerFetchPool(
            manager,
            nodes_per_request=sync.nodes_per_request,
            timeout=sync.peer_request_timeout,
            log=self.log,
            cluster=cluster,
        )
        # built here, not in run(): from now on the registry's
        # khipu_fastsync_* families are this service's, from zero
        self.syncer = StateSyncer(
            blockchain.storages,
            FastSyncStateStorage(blockchain.storages.app_state.source),
            self.pool.fetch_nodes,
            batch_size=sync.nodes_per_request,  # run() asks for wider
            hasher=hasher,
            mirror=mirror,
        )

    # -------------------------------------------------------------- pivot

    def _best_header_of(self, peer: Peer) -> Optional[BlockHeader]:
        try:
            body = peer.request(
                ETH_OFFSET + GET_BLOCK_HEADERS,
                GetBlockHeaders(peer.status.best_hash, 1).body(),
                ETH_OFFSET + BLOCK_HEADERS,
                timeout=self.pool.timeout,
            )
            headers = decode_headers(body)
            return headers[0] if headers else None
        except PeerError:
            return None

    def choose_pivot(self) -> BlockHeader:
        """Median best number over >= min_peers peers, minus the offset
        (FastSyncService.scala:184-273)."""
        with span("fastsync.pivot", min_peers=self.min_peers) as sp:
            header = self._choose_pivot()
            sp.set_tag("number", header.number)
            return header

    def _choose_pivot(self) -> BlockHeader:
        peers = [p for p in self.pool._live_peers() if p.status is not None]
        if len(peers) < self.min_peers:
            raise FastSyncError(
                f"need {self.min_peers} peers to choose a pivot, "
                f"have {len(peers)}"
            )
        bests: List[int] = []
        for p in peers:
            h = self._best_header_of(p)
            if h is not None:
                bests.append(h.number)
        if len(bests) < self.min_peers:
            raise FastSyncError(
                f"only {len(bests)}/{self.min_peers} peers answered the "
                "pivot probe"
            )
        bests.sort()
        median = bests[len(bests) // 2]
        pivot_number = max(1, median - self.pivot_offset)
        header = self._fetch_header_by_number(pivot_number)
        if header is None:
            raise FastSyncError(f"no peer served pivot header {pivot_number}")
        self.log(
            f"pivot = #{pivot_number} (median best {median} - "
            f"{self.pivot_offset}), root {header.state_root.hex()[:16]}"
        )
        return header

    def _fetch_header_by_number(self, n: int) -> Optional[BlockHeader]:
        for peer in self.pool._live_peers():
            try:
                body = peer.request(
                    ETH_OFFSET + GET_BLOCK_HEADERS,
                    GetBlockHeaders(n, 1).body(),
                    ETH_OFFSET + BLOCK_HEADERS,
                    timeout=self.pool.timeout,
                )
                headers = decode_headers(body)
                if headers and headers[0].number == n:
                    return headers[0]
            except PeerError:
                continue
        return None

    # ----------------------------------------------------------- backfill

    def _backfill_blocks(self, pivot: BlockHeader) -> None:
        """Headers/bodies/receipts genesis..pivot, stored WITHOUT
        execution (the state trie arrived separately); every link is
        validated: parent hashes, tx/ommers roots, receipts roots."""
        with span("fastsync.backfill", pivot=pivot.number):
            self._backfill(pivot)

    def _backfill(self, pivot: BlockHeader) -> None:
        s = self.blockchain.storages
        expected_parent = self.blockchain.get_hash_by_number(0)
        td = self.blockchain.get_total_difficulty(0) or 0
        n = 1
        batch = 20
        while n <= pivot.number:
            count = min(batch, pivot.number - n + 1)
            headers = self._headers_range(n, count)
            hashes = [h.hash for h in headers]
            bodies = self._bodies_of(hashes)
            receipts = self._receipts_of(hashes)
            for h, body, rcpts in zip(headers, bodies, receipts):
                if h.parent_hash != expected_parent:
                    raise FastSyncError(
                        f"backfill: broken parent link at #{h.number}"
                    )
                if transactions_root(body.transactions) != h.transactions_root:
                    raise FastSyncError(f"backfill: bad txRoot at #{h.number}")
                if ommers_hash(body.ommers) != h.ommers_hash:
                    raise FastSyncError(
                        f"backfill: bad ommersHash at #{h.number}"
                    )
                if receipts_root(rcpts) != h.receipts_root:
                    raise FastSyncError(
                        f"backfill: bad receiptsRoot at #{h.number}"
                    )
                td += h.difficulty
                s.block_header_storage.put(h.number, h.encode())
                s.block_body_storage.put(h.number, body.encode())
                s.receipts_storage.put(h.number, encode_receipts(rcpts))
                s.total_difficulty_storage.put_td(h.number, td)
                s.block_numbers.put(h.hash, h.number)
                for i, tx in enumerate(body.transactions):
                    s.transaction_storage.put(tx.hash, h.number, i)
                expected_parent = h.hash
            n += count
        s.app_state.best_block_number = pivot.number

    def _headers_range(self, start: int, count: int) -> List[BlockHeader]:
        for peer in self.pool._live_peers():
            try:
                body = peer.request(
                    ETH_OFFSET + GET_BLOCK_HEADERS,
                    GetBlockHeaders(start, count).body(),
                    ETH_OFFSET + BLOCK_HEADERS,
                    timeout=self.pool.timeout,
                )
                headers = decode_headers(body)
                if len(headers) == count:
                    return headers
            except PeerError:
                continue
        raise FastSyncError(f"no peer served headers [{start}..+{count})")

    def _bodies_of(self, hashes: List[bytes]) -> List[BlockBody]:
        # EXACT counts only: replies carry no correlation and servers
        # skip unknown hashes, so a short reply would silently shift
        # every later header/body pair — try the next peer instead
        out: List[BlockBody] = []
        want = list(hashes)
        while want:
            chunk = want[:20]
            served = False
            for peer in self.pool._live_peers():
                try:
                    body = peer.request(
                        ETH_OFFSET + GET_BLOCK_BODIES,
                        chunk,
                        ETH_OFFSET + BLOCK_BODIES,
                        timeout=self.pool.timeout,
                    )
                except PeerError:
                    continue
                got = decode_bodies(body)
                if len(got) == len(chunk):
                    out.extend(got)
                    want = want[len(chunk) :]
                    served = True
                    break
            if not served:
                raise FastSyncError("no peer served the full body chunk")
        return out

    def _receipts_of(self, hashes: List[bytes]) -> List[List[Receipt]]:
        from khipu_tpu.domain.receipt import decode_receipts
        from khipu_tpu.base.rlp import rlp_encode

        out: List[List[Receipt]] = []
        want = list(hashes)
        while want:
            chunk = want[:5]
            served = False
            for peer in self.pool._live_peers():
                try:
                    body = peer.request(
                        ETH_OFFSET + GET_RECEIPTS,
                        chunk,
                        ETH_OFFSET + RECEIPTS,
                        timeout=self.pool.timeout,
                    )
                except PeerError:
                    continue
                if len(body) == len(chunk):
                    out.extend(
                        decode_receipts(rlp_encode(item)) for item in body
                    )
                    want = want[len(chunk) :]
                    served = True
                    break
            if not served:
                raise FastSyncError("no peer served the full receipt chunk")
        return out

    # ------------------------------------------------------------- driver

    def run(self) -> SyncState:
        """Full fast sync: pivot -> state download -> block backfill.
        After this, regular sync takes over from the pivot. One syncer
        batch is as many requests wide as the pool may have in flight,
        so that every live peer is asked at once."""
        try:
            pivot = self.choose_pivot()
            width = self.pool.width()
            state = self.syncer.start(
                pivot.state_root,
                batch_size=self.config.sync.nodes_per_request * width)
            self.log(
                f"state download complete: {state.downloaded_nodes} nodes, "
                f"{width} requests wide "
                f"({self.pool.blacklisted} peers blacklisted)"
            )
            self._backfill_blocks(pivot)
            self.log(f"backfilled block data to pivot #{pivot.number}")
            return state
        finally:
            # however the run ends (done, stopped, failed): the pool's
            # worker threads end with it
            self.pool.close()

    def stop(self) -> None:
        """A node's shutdown while ``run`` is under way on another
        thread: the syncer stores the batch in hand, writes its
        checkpoint and ``run`` raises ``SyncStopped`` and closes the
        pool on its way out; the next service's ``run`` resumes from
        there. A service that never ran has nothing to stop."""
        self.syncer.stop()
