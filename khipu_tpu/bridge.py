"""gRPC bridge: block batches in, verified state roots back.

Parity: SURVEY §2.9 north-star channel — "Akka regular-sync actors
stream block batches to the TPU host over a thin gRPC bridge". The
service is schema-light by design (raw-bytes methods, RLP payloads) so
the JVM side needs no shared protobuf artifacts — any gRPC client can
call ``khipu.Bridge/ExecuteBlocks`` with an RLP list of block RLPs and
read back rlp([[number, state_root], ...]).

Methods (all request/response = opaque bytes):
  ExecuteBlocks: rlp([block_rlp, ...]) -> rlp([[number_be, root], ...])
                 — executes + persists through the window committer
                 (device-batched trie commits), all roots gated.
  BestBlock:     b"" -> rlp([number_be, hash])
  GetStateRoot:  rlp(number_be) -> root (32 bytes) | b"" if unknown
  GetNodeData:   rlp([hash, ...]) -> rlp([value-or-empty, ...]) — the
                 served node cache (P6 DistributedNodeStorage role):
                 remote hosts heal missing trie nodes through it
  PutNodeData:   rlp([[hash, value], ...]) -> rlp(admitted_be) — the
                 write-replication half: a ShardedNodeClient places
                 each node on every replica of its key so the cluster
                 keeps serving it when one shard dies. Values are
                 content-address verified before admission.
  Ping:          x -> x, EXCEPT the clock-probe sentinel
                 (``CLOCK_PROBE``) which answers rlp(shard_wall_us_be)
                 — the NTP-style offset/RTT estimate the merged chrome
                 trace is built on (observability/export.py)
  GetTraceSpans: b"" -> rlp([trace_id, [span...]]) — the shard's span
                 ring, each span
                 [sid, parent|"" , name, t0_wall_us, t1_wall_us, tid,
                  thread_name, error|"", tags_json] with ABSOLUTE
                 shard-wall microsecond stamps
  GetMetrics:    b"" -> rlp([[name, kind, help, labels_json,
                 value_json], ...]) — one consistent pull of the
                 shard's MetricsRegistry families (instruments + pull
                 collectors), the scrape half of the cluster telemetry
                 plane (observability/telemetry.py): ClusterTelemetry
                 merges these into the shard-labeled exposition

Trace propagation (Dapper-style): every BridgeClient call carries
``khipu-trace-id`` / ``khipu-parent-token`` / ``khipu-sampled`` gRPC
metadata; the server opens a ``bridge.serve.<Method>`` span in its OWN
tracer ring tagged with the remote linkage, so the driver can pull the
ring and nest shard work under the exact RPC span that caused it.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from concurrent import futures
from typing import List, Optional

import grpc

from khipu_tpu.base.rlp import rlp_decode, rlp_encode
from khipu_tpu.chaos import fault_point, fault_value
from khipu_tpu.config import KhipuConfig
from khipu_tpu.domain.block import Block
from khipu_tpu.domain.blockchain import Blockchain
from khipu_tpu.evm.dataword import from_bytes, to_minimal_bytes
from khipu_tpu.observability.trace import (
    Tracer,
    apply_config as apply_trace_config,
    current_tracer,
    use_tracer,
)

SERVICE = "khipu.Bridge"

REPLAY_STATS_KEPT = 256  # ExecuteBlocks batches whose ReplayStats stay

# gRPC metadata keys the client attaches on EVERY call (values are the
# caller's tracer identity; the keys ship unconditionally so the wire
# format stays greppable — khipu-sampled is "1" (record+link), "0"
# (head sampler dropped this trace id; server skips its serve span
# too), or "" (tracing off on the caller, no decision))
MD_TRACE_ID = "khipu-trace-id"
MD_PARENT_TOKEN = "khipu-parent-token"
MD_SAMPLED = "khipu-sampled"

# Ping clock-probe sentinel: any other payload echoes verbatim (pure
# Ping semantics preserved); this one answers the shard's wall clock in
# microseconds so one timed Ping yields (offset, rtt)
CLOCK_PROBE = b"\x00khipu-clock-probe\x00"


def _identity(b: bytes) -> bytes:
    return b


def _encode_trace_spans(tracer_: Tracer) -> bytes:
    """The GetTraceSpans response: the ring as RLP with absolute
    shard-wall microsecond stamps (the driver re-anchors them with the
    Ping offset estimate). Tags ship as JSON — values are display-only
    on the far side; bytes become hex."""
    rows = []
    for s in tracer_.snapshot():
        tags = {
            k: (v.hex() if isinstance(v, bytes) else v)
            for k, v in s.tags.items()
        }
        rows.append([
            to_minimal_bytes(s.sid),
            to_minimal_bytes(s.parent) if s.parent else b"",
            s.name.encode(),
            to_minimal_bytes(int(tracer_.to_wall(s.t0) * 1e6)),
            to_minimal_bytes(int(tracer_.to_wall(s.t1) * 1e6)),
            to_minimal_bytes(s.tid),
            (s.thread_name or "").encode(),
            b"\x01" if s.error else b"",
            json.dumps(tags).encode(),
        ])
    return rlp_encode([tracer_.trace_id.encode(), rows])


def decode_trace_spans(payload: bytes) -> dict:
    """Inverse of ``_encode_trace_spans``: {traceId, spans:[{...}]}
    with ``t0_wall``/``t1_wall`` back in float seconds."""
    trace_id, rows = rlp_decode(payload)
    spans = []
    for row in rows:
        (sid, parent, name, t0, t1, tid, tname, err, tags) = row
        spans.append({
            "sid": from_bytes(sid),
            "parent": from_bytes(parent) if parent else None,
            "name": name.decode(),
            "t0_wall": from_bytes(t0) / 1e6,
            "t1_wall": from_bytes(t1) / 1e6,
            "tid": from_bytes(tid),
            "thread_name": tname.decode(),
            "error": bool(err),
            "tags": json.loads(tags.decode() or "{}"),
        })
    return {"traceId": trace_id.decode(), "spans": spans}


class BridgeServer:
    def __init__(self, blockchain: Blockchain, config: KhipuConfig,
                 device_commit: bool = False, max_workers: int = 4,
                 tracer: Optional[Tracer] = None, registry=None):
        self.blockchain = blockchain
        self.config = config
        self.device_commit = device_commit
        self.max_workers = max_workers
        self._exec_lock = threading.Lock()  # blocks apply serially
        # ONE driver for the server's lifetime (built on the first
        # ExecuteBlocks): its device mirror and adaptive controller
        # are device state that must outlive a batch — a driver per
        # call re-allocated the mirror in HBM and restarted the
        # controller's history every few windows
        self._driver = None
        # the ReplayStats of the last ExecuteBlocks batches, newest
        # last: the per-phase split of what this server just did, for
        # whoever embeds it (replay_stats()) and, through GetMetrics,
        # for whoever asks why one batch took long (_replay_samples)
        self._replay_stats: deque = deque(maxlen=REPLAY_STATS_KEPT)
        self._server: Optional[grpc.Server] = None
        # the SHARD's own span ring (per-instance: two in-process
        # servers — the 2-shard tests — must not interleave rings),
        # served raw over GetTraceSpans. Enabled by config or by the
        # operator poking ``server.tracer.enable()``.
        self.tracer = tracer if tracer is not None else Tracer()
        apply_trace_config(config.observability, self.tracer)
        # the registry GetMetrics serves: the process REGISTRY by
        # default; in-process multi-shard tests hand each server its
        # own MetricsRegistry so the scraped families stay per-shard
        if registry is None:
            from khipu_tpu.observability.registry import REGISTRY
            registry = REGISTRY
        self.registry = registry
        # the newest server on a registry owns the slot, as its driver
        # owns the pipeline gauges
        registry.register_collector("bridge_replay", self._replay_samples)

    # ------------------------------------------------------------ methods

    def _execute_blocks(self, request: bytes, context) -> bytes:
        from khipu_tpu.sync.replay import ReplayDriver

        try:
            items = rlp_decode(request)
            blocks = [Block.decode(rlp_encode(item)) for item in items]
        except Exception as e:
            context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, f"bad batch: {e}"
            )
        with self._exec_lock:
            if self._driver is None:
                self._driver = ReplayDriver(
                    self.blockchain, self.config,
                    device_commit=self.device_commit,
                    tracer=self.tracer,
                )
            try:
                # khipu-lint: ok KL004 the lock IS the serial-apply rule: batches execute one at a time
                self._replay_stats.append(self._driver.replay(blocks))
            except Exception as e:
                context.abort(
                    grpc.StatusCode.FAILED_PRECONDITION,
                    f"{type(e).__name__}: {e}",
                )
        out = [
            [to_minimal_bytes(b.number), b.header.state_root]
            for b in blocks
        ]
        return rlp_encode(out)

    def replay_stats(self) -> list:
        """``ReplayStats`` of the most recent ExecuteBlocks batches
        (at most ``REPLAY_STATS_KEPT``), oldest first."""
        return list(self._replay_stats)

    def _replay_samples(self) -> list:
        """``khipu_bridge_replay_*``: the kept batches' count, the last
        batch's seconds, and the SLOWEST kept batch with its phase
        split — an outlier batch names its phase after the fact."""
        kept = self.replay_stats()
        out = [("khipu_bridge_replay_batches_kept", "gauge", {},
                len(kept))]
        if kept:
            slow = max(kept, key=lambda s: s.seconds)
            out += [
                ("khipu_bridge_replay_batch_seconds", "gauge",
                 {"which": "last"}, kept[-1].seconds),
                ("khipu_bridge_replay_batch_seconds", "gauge",
                 {"which": "slowest"}, slow.seconds),
            ]
            out += [("khipu_bridge_replay_slowest_phase_seconds", "gauge",
                     {"phase": p}, v) for p, v in slow.phases.items()]
        return out

    def _best_block(self, request: bytes, context) -> bytes:
        n = self.blockchain.best_block_number
        header = self.blockchain.get_header_by_number(n)
        return rlp_encode(
            [to_minimal_bytes(n), header.hash if header else b""]
        )

    def _get_state_root(self, request: bytes, context) -> bytes:
        n = from_bytes(rlp_decode(request))
        header = self.blockchain.get_header_by_number(n)
        return header.state_root if header else b""

    def _get_node_data(self, request: bytes, context) -> bytes:
        """Serve trie nodes / code blobs by hash — the cluster-wide
        node-cache endpoint (P6: DistributedNodeStorage.scala:13 role,
        NodeEntity.scala:28's served reads). Request rlp([hash, ...]),
        response rlp([value-or-empty, ...]) positionally; a remote
        khipu host points storage/remote.py's fetch at this method and
        self-heals MPTNodeMissingException across processes."""
        try:
            hashes = rlp_decode(request)
        except Exception as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, f"bad: {e}")
        storages = self.blockchain.storages
        out = []
        for h in hashes[:384]:  # reference caps node batches (conf:100)
            v = storages.get_node_any(h)
            out.append(v if v is not None else b"")
        return rlp_encode(out)

    def _put_node_data(self, request: bytes, context) -> bytes:
        """Admit replicated nodes (cluster write path). Every value is
        verified against its key before it touches the store — a buggy
        or hostile replicator cannot poison the served cache. Returns
        the count actually admitted."""
        from khipu_tpu.base.crypto.keccak import keccak256

        try:
            pairs = rlp_decode(request)
        except Exception as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, f"bad: {e}")
        storages = self.blockchain.storages
        admitted = 0
        for h, v in pairs[:384]:
            if len(h) == 32 and v and keccak256(v) == h:
                # same dual admission the heal path uses: the server
                # cannot know which trie the node belongs to, and
                # get_node_any serves from either store
                storages.account_node_storage.put(h, v)
                storages.storage_node_storage.put(h, v)
                admitted += 1
        return rlp_encode(to_minimal_bytes(admitted))

    def _stream_node_data(self, request: bytes, context) -> bytes:
        """Cursor-paged, range-filtered node export — the live-
        rebalance pull path (cluster/rebalance.py). Request
        ``rlp([cursor, count, [[lo, hi], ...]])`` where each
        ``[lo, hi)`` is a half-open 64-bit ring-point range the caller
        is moving; response ``rlp([done, next_cursor, [[hash, value],
        ...]])`` with at most ``count`` pairs whose key hashes into one
        of the ranges and sorts after ``cursor``. Iteration is
        restartable from any cursor (idempotent — exactly what a
        crash-resumed rebalance replays) and serves durably-landed
        nodes via the same ``get_node_any`` resolution the GetNodeData
        cache uses."""
        from khipu_tpu.cluster.ring import _point

        try:
            cursor, count_b, raw_ranges = rlp_decode(request)
            count = min(from_bytes(count_b) or 384, 1024)
            ranges = [
                (from_bytes(lo), from_bytes(hi))
                for lo, hi in raw_ranges
            ]
        except Exception as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, f"bad: {e}")
        storages = self.blockchain.storages
        try:
            keys = storages.node_keys()
        except Exception as e:
            context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                f"node store cannot stream: {e}",
            )
        out = []
        done = b"\x01"
        for k in keys:
            if cursor and k <= cursor:
                continue
            if ranges:
                pt = _point(k)
                if not any(lo <= pt < hi for lo, hi in ranges):
                    continue
            if len(out) >= count:
                done = b""  # more matching keys remain
                break
            v = storages.get_node_any(k)
            if v is not None:
                out.append([k, v])
        nxt = out[-1][0] if out else bytes(cursor)
        return rlp_encode([done, nxt, out])

    def _engine_info(self, request: bytes, context) -> bytes:
        """Capability negotiation for segment-ship (cluster/rebalance
        and segment-streamed fast sync): ``rlp([engine, [[topic, seq,
        size], ...]])``. Non-Kesque engines answer with their name and
        an empty manifest — the caller falls back to the paged
        ``StreamNodeData`` path."""
        storages = self.blockchain.storages
        engine = getattr(storages, "kesque_engine", None)
        if engine is None:
            name = getattr(storages, "engine", "unknown")
            return rlp_encode([name.encode(), []])
        manifest = [
            [topic.encode(), to_minimal_bytes(seq), to_minimal_bytes(size)]
            for topic, seq, size in engine.list_segments()
        ]
        return rlp_encode([b"kesque", manifest])

    def _stream_segments(self, request: bytes, context) -> bytes:
        """Raw whole-frame segment chunks — the bulk-movement unit.
        Request ``rlp([topic, seq, offset, max_bytes])``; response
        ``rlp([done, next_offset, raw])``. Restartable from any offset
        (frame boundaries are self-describing), serves only the
        committed prefix, and ships bytes the RECEIVER verifies by
        content address — a corrupt chunk cannot land under a valid
        key."""
        storages = self.blockchain.storages
        engine = getattr(storages, "kesque_engine", None)
        if engine is None:
            context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                "segment streaming requires the kesque engine",
            )
        try:
            topic_b, seq_b, off_b, max_b = rlp_decode(request)
            topic = topic_b.decode()
            seq = from_bytes(seq_b)
            offset = from_bytes(off_b)
            max_bytes = min(from_bytes(max_b) or (1 << 20), 8 << 20)
        except Exception as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, f"bad: {e}")
        try:
            raw, nxt, done = engine.read_chunk(topic, seq, offset,
                                               max_bytes)
        except KeyError as e:
            # compacted away mid-stream: NOT_FOUND tells the puller to
            # refetch the manifest and restart (idempotent by content
            # address)
            context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        return rlp_encode([
            b"\x01" if done else b"", to_minimal_bytes(nxt), raw,
        ])

    def _ping(self, request: bytes, context) -> bytes:
        if request == CLOCK_PROBE:
            # shard wall clock, anchored through the tracer epoch so a
            # test can inject a known offset by shifting epoch_wall —
            # spans and probe answers then shift together, exactly like
            # a skewed host clock would
            now = self.tracer.to_wall(time.perf_counter())
            return rlp_encode(to_minimal_bytes(int(now * 1e6)))
        return request

    def _get_trace_spans(self, request: bytes, context) -> bytes:
        return _encode_trace_spans(self.tracer)

    def _get_metrics(self, request: bytes, context) -> bytes:
        from khipu_tpu.observability.telemetry import encode_metrics

        return encode_metrics(self.registry)

    # ------------------------------------------------------------- server

    def start(self, host: str = "127.0.0.1", port: int = 0) -> int:
        def _guarded(name, fn):
            # chaos seam per served method: a `latency` rule here models
            # a slow shard (what the client's rpc_deadline exists for),
            # a `raise` rule a shard-side failure
            def handler(request, context):
                fault_point(f"bridge.serve.{name}")
                tr = self.tracer
                if not tr.enabled:
                    return fn(request, context)
                # server-side span, linked to the remote parent from
                # the propagated metadata (tags, not a local parent id
                # — the token lives in the CALLER's id space)
                tags = {"method": name}
                md = dict(context.invocation_metadata() or ())
                sampled = md.get(MD_SAMPLED)
                if sampled == "0":
                    # the caller made the head-based per-trace-id drop
                    # decision (trace.trace_sampled) — honor it so one
                    # trace is whole or absent FLEET-wide: no server
                    # span, no orphan fragments in the shard's ring
                    return fn(request, context)
                if sampled == "1":
                    tags["remote_trace"] = md.get(MD_TRACE_ID, "")
                    tok = md.get(MD_PARENT_TOKEN, "")
                    if tok.isdigit():
                        tags["remote_parent"] = int(tok)
                with use_tracer(tr), tr.span(
                    f"bridge.serve.{name}", **tags
                ):
                    return fn(request, context)

            return grpc.unary_unary_rpc_method_handler(
                handler, _identity, _identity
            )

        handlers = {
            "ExecuteBlocks": _guarded(
                "ExecuteBlocks", self._execute_blocks
            ),
            "BestBlock": _guarded("BestBlock", self._best_block),
            "GetStateRoot": _guarded(
                "GetStateRoot", self._get_state_root
            ),
            "GetNodeData": _guarded("GetNodeData", self._get_node_data),
            "PutNodeData": _guarded("PutNodeData", self._put_node_data),
            "StreamNodeData": _guarded(
                "StreamNodeData", self._stream_node_data
            ),
            "EngineInfo": _guarded("EngineInfo", self._engine_info),
            "StreamSegments": _guarded(
                "StreamSegments", self._stream_segments
            ),
            "Ping": _guarded("Ping", self._ping),
            "GetTraceSpans": _guarded(
                "GetTraceSpans", self._get_trace_spans
            ),
            "GetMetrics": _guarded("GetMetrics", self._get_metrics),
        }
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=self.max_workers)
        )
        self._server.add_generic_rpc_handlers(
            (grpc.method_handlers_generic_handler(SERVICE, handlers),)
        )
        bound = self._server.add_insecure_port(f"{host}:{port}")
        self._server.start()
        return bound

    def stop(self, grace: float = 0.5) -> None:
        if self._server is not None:
            self._server.stop(grace)
            self._server = None


class BridgeClient:
    """The JVM-side caller's shape, for tests and local tooling."""

    def __init__(self, target: str, deadline: Optional[float] = None):
        # ``deadline``: per-RPC gRPC deadline in seconds
        # (ClusterConfig.rpc_deadline) — a hung shard surfaces as
        # DEADLINE_EXCEEDED into the caller's retry/breaker machinery
        # instead of blocking a reader forever. None = no deadline.
        self.channel = grpc.insecure_channel(target)
        self.deadline = deadline

    def _call(self, method: str, payload: bytes) -> bytes:
        fault_point(f"bridge.call.{method}")
        fn = self.channel.unary_unary(
            f"/{SERVICE}/{method}",
            request_serializer=_identity,
            response_deserializer=_identity,
        )
        # Dapper propagation: the caller's tracer identity + innermost
        # span token ride as gRPC metadata on EVERY call (sampled="0"
        # when tracing is off — the keys are unconditional). The
        # ``bridge.call`` span is the client half of the RPC edge; its
        # token is what the server records as remote_parent, so the
        # merged trace nests the server span inside exactly this one.
        t = current_tracer()
        with t.span("bridge.call", method=method) as sp:
            md = (
                (MD_TRACE_ID, t.trace_id),
                (MD_PARENT_TOKEN, str(sp.token or "")),
                # three-valued: "1" = record+link, "0" = the head
                # sampler DROPPED this trace id (tracer on, trace
                # out — the server must skip too so the trace is
                # whole or absent fleet-wide), "" = tracing is off
                # here, no decision made (the server keeps its own
                # local, unlinked serve span)
                (
                    MD_SAMPLED,
                    "1" if t.enabled
                    else ("0" if getattr(t, "_on", False) else ""),
                ),
            )
            return fn(payload, timeout=self.deadline, metadata=md)

    def execute_blocks(self, blocks: List[Block]):
        payload = rlp_encode(
            [rlp_decode(b.encode()) for b in blocks]
        )
        out = rlp_decode(self._call("ExecuteBlocks", payload))
        return [(from_bytes(n), root) for n, root in out]

    def best_block(self):
        n, h = rlp_decode(self._call("BestBlock", b""))
        return from_bytes(n), h

    def get_state_root(self, number: int) -> Optional[bytes]:
        out = self._call(
            "GetStateRoot", rlp_encode(to_minimal_bytes(number))
        )
        return out if out else None

    def get_node_data(self, hashes: List[bytes]):
        """Fetch nodes by hash from the served node cache; returns
        {hash: value} for the ones the server had. Plugs directly into
        RemoteReadThroughNodeStorage's fetch callback. Chunks at the
        server's 384-hash cap so oversized requests don't silently
        report the tail as missing."""
        hashes = list(hashes)
        result = {}
        for start in range(0, len(hashes), 384):
            chunk = hashes[start : start + 384]
            out = rlp_decode(self._call("GetNodeData", rlp_encode(chunk)))
            # data seam: a `corrupt` rule bit-flips a fetched node —
            # the caller's content-address check MUST reject it
            result.update(
                (h, fault_value("bridge.node.value", v))
                for h, v in zip(chunk, out) if v
            )
        return result

    def put_node_data(self, nodes) -> int:
        """Replicate {hash: value} onto this shard; returns the number
        of nodes the server verified and admitted. Chunks at the
        server's 384-pair cap."""
        pairs = [[h, v] for h, v in nodes.items()]
        admitted = 0
        for start in range(0, len(pairs), 384):
            out = self._call(
                "PutNodeData", rlp_encode(pairs[start : start + 384])
            )
            admitted += from_bytes(rlp_decode(out))
        return admitted

    def stream_node_data(self, ranges, cursor: bytes = b"",
                         count: int = 384):
        """One page of the shard's nodes whose ring points fall in
        ``ranges`` (half-open ``[lo, hi)`` 64-bit pairs), resuming
        after ``cursor``: ``(done, next_cursor, [(hash, value), ...])``.
        The caller MUST verify each value by content address before
        forwarding it anywhere (cluster/rebalance.py does)."""
        payload = rlp_encode([
            bytes(cursor),
            to_minimal_bytes(count),
            [[to_minimal_bytes(lo), to_minimal_bytes(hi)]
             for lo, hi in ranges],
        ])
        done, nxt, pairs = rlp_decode(
            self._call("StreamNodeData", payload)
        )
        # data seam: a `corrupt` rule bit-flips a streamed value — the
        # rebalancer's receipt-time keccak check MUST catch it
        return (
            bool(done),
            nxt,
            [(h, fault_value("bridge.node.value", v))
             for h, v in pairs],
        )

    def engine_info(self):
        """``(engine_name, [(topic, seq, size), ...])`` — the shard's
        storage engine and (for Kesque) its segment manifest. The
        rebalancer's capability negotiation: ``name == "kesque"``
        means the peer can segment-ship."""
        name, manifest = rlp_decode(self._call("EngineInfo", b""))
        return (
            name.decode(),
            [
                (topic.decode(), from_bytes(seq), from_bytes(size))
                for topic, seq, size in manifest
            ],
        )

    def stream_segments(self, topic: str, seq: int, offset: int = 0,
                        max_bytes: int = 1 << 20):
        """One raw whole-frame chunk of a shard's segment:
        ``(raw, next_offset, done)``. The caller MUST parse the frames
        and verify every record by content address before admitting it
        (the kesque ingest path does — a bit-flip injected through the
        ``bridge.segment.raw`` corrupt seam must die at the receiver's
        keccak, never in the store)."""
        done, nxt, raw = rlp_decode(self._call(
            "StreamSegments",
            rlp_encode([
                topic.encode(), to_minimal_bytes(seq),
                to_minimal_bytes(offset), to_minimal_bytes(max_bytes),
            ]),
        ))
        return (
            fault_value("bridge.segment.raw", raw),
            from_bytes(nxt),
            bool(done),
        )

    def ping(self, payload: bytes = b"ping") -> bytes:
        return self._call("Ping", payload)

    def clock_probe(self, samples: int = 5):
        """NTP-style clock estimate from timed Ping probes: returns
        ``(offset_s, rtt_s)`` for the MINIMUM-RTT probe, where
        ``offset = shard_clock - local_clock`` and the true offset lies
        within ±rtt/2 of the estimate (the shard stamped its clock
        somewhere inside the round trip; the midpoint assumption is off
        by at most half of it)."""
        best = None
        for _ in range(max(1, samples)):
            t0 = time.time()
            out = self._call("Ping", CLOCK_PROBE)
            t1 = time.time()
            shard_s = from_bytes(rlp_decode(out)) / 1e6
            rtt = max(0.0, t1 - t0)
            offset = shard_s - (t0 + t1) / 2.0
            if best is None or rtt < best[1]:
                best = (offset, rtt)
        return best

    def get_trace_spans(self) -> dict:
        """Pull the shard's span ring: {traceId, spans:[{...}]} with
        absolute shard-wall second stamps (see decode_trace_spans)."""
        return decode_trace_spans(self._call("GetTraceSpans", b""))

    def get_metrics(self):
        """Pull one consistent snapshot of the shard's metric families:
        ``{name: (kind, help, [(labels_dict, value)])}`` — the same
        shape ``MetricsRegistry.families()`` returns locally."""
        from khipu_tpu.observability.telemetry import decode_metrics

        return decode_metrics(self._call("GetMetrics", b""))

    def close(self) -> None:
        self.channel.close()
