"""Block-window commit: N blocks of dirty trie nodes, one device pass.

The north-star architecture (SURVEY §3.2 HOT LOOPs 3-4, BASELINE
configs #1/#4): per-block state diffs accumulate into ONE deferred
placeholder session; later blocks execute against the *unresolved*
state (placeholder refs resolve through the session's staged store);
``finalize`` hashes the whole window's node DAG level-synchronously —
one batched device call per level across every block and every trie —
then checks each block's resolved root against its header and persists.

This is what amortizes device-dispatch latency over the window: a
window of W blocks costs O(levels) device calls instead of
O(W x levels), and each call carries W x the batch width.

Pre-Byzantium receipts embed per-tx intermediate roots (host-computed
during execution), so windows > 1 require Byzantium+ receipt semantics
(ReplayDriver enforces this).
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from khipu_tpu.base.crypto.keccak import keccak256
from khipu_tpu.domain.account import address_key
from khipu_tpu.domain.block_header import BlockHeader
from khipu_tpu.ledger.world import BlockWorldState
from khipu_tpu.observability.profiler import H2D, HOST, LEDGER
from khipu_tpu.observability.trace import event, span
from khipu_tpu.trie.bulk import Hasher, host_hasher
from khipu_tpu.trie.deferred import (
    DeferredMPT,
    _is_placeholder,
    _make_placeholder,
    _substitute_bytes,
    _substitute_many,
    _PLACEHOLDER_PREFIX,
    find_sites,
    found_digests,
    splice_sites,
)
from khipu_tpu.trie.mpt import EMPTY_TRIE_HASH


# graceful-degradation gauges served by the khipu_metrics RPC
# (jsonrpc/eth_service.py), registered as khipu_window_* in the unified
# registry. Module-level (not on the committer): committers are rebuilt
# every epoch and the metric must survive them.
from khipu_tpu.observability.registry import REGISTRY

WINDOW_GAUGES = REGISTRY.gauge_group("khipu_window", {
    # windows whose fused device dispatch failed at runtime and fell
    # back to the host hasher (docs/recovery.md graceful degradation)
    "fused_fallbacks": 0,
}, help="window-commit graceful-degradation state (ledger/window.py)")

# what the seal stage's one site scan met, by what each site's 32 bytes
# turned out to be: this window's own node (a substitution of the fused
# program), a node an earlier window resolved (spliced on the host), a
# node of a window still in flight (a row of the ext tile: what
# pipeline_depth costs that tile), or nothing this committer knows
SEAL_SITES = {
    kind: REGISTRY.counter(
        "khipu_seal_sites_total",
        help="placeholder sites the seal stage's scan met "
             "(ledger/window.py)",
        labels={"kind": kind},
    )
    for kind in ("local", "resolved", "ext", "opaque")
}


class _StagedReadThrough:
    """Node source that serves the window's staged (unresolved) nodes
    first, then the underlying storage — how a block reads state
    committed by earlier blocks of the same open window.

    ``resolved`` maps pruned placeholders to their real hashes: once a
    window is collected its staged encodings are dropped (the nodes are
    persisted), but retained trie structure still holds placeholder
    refs into it — those reads indirect through the mapping to the
    store instead of keeping every encoding alive (memory bound)."""

    __slots__ = ("inner", "staged", "resolved")

    def __init__(self, inner, staged: Dict[bytes, bytes], resolved=None):
        self.inner = inner
        self.staged = staged
        self.resolved = resolved if resolved is not None else {}

    def get(self, key: bytes) -> Optional[bytes]:
        v = self.staged.get(key)
        if v is not None:
            return v
        real = self.resolved.get(key)
        if real is not None:
            return self.inner.get(real)
        return self.inner.get(key)


class WindowMismatch(Exception):
    def __init__(self, number: int, got: bytes, want: bytes):
        super().__init__(
            f"block {number}: window root {got.hex()} != header "
            f"{want.hex()}"
        )
        self.number = number


class WindowPlaceholderError(Exception):
    """A live placeholder could not be resolved at collect — it was
    skipped at seal (the ``enc is None`` counter-range branch: the
    placeholder belongs to a different session sharing the counter) or
    its digest never materialized. Raised with the placeholder index so
    the failure names WHICH node instead of a bare KeyError."""

    def __init__(self, ph: bytes, reason: str):
        idx = int.from_bytes(ph[len(_PLACEHOLDER_PREFIX):], "big")
        super().__init__(
            f"window collect: live placeholder #{idx} {reason} "
            "(skipped at seal? a foreign session sharing the counter "
            "range cannot be collected here)"
        )
        self.index = idx


@dataclasses.dataclass
class _Pack:
    """What the seal stage's one site scan gives its two halves."""

    to_resolve: Dict[bytes, bytes]  # ph -> encoding, resolved refs spliced
    ext_refs: Dict[bytes, Tuple["WindowJob", int]]  # in-flight children
    max_depth: int
    met: Dict[str, int]  # sites by kind (SEAL_SITES)
    # the sites the fused program substitutes: own children first, then
    # refs into in-flight windows. node/child index to_resolve's order
    node: np.ndarray
    off: np.ndarray
    child: np.ndarray  # of the own-child sites only
    ext_keys: List[bytes]  # of the in-flight sites: the child's bytes

    def subs(self, ext_pos: Dict[bytes, int]):
        """``(node, off, child)`` for ``fused_submit``: an in-flight
        child is ``len(to_resolve) + its row of the ext tile``."""
        ext = np.fromiter(
            map(ext_pos.__getitem__, self.ext_keys), np.int64,
            len(self.ext_keys),
        )
        return (
            self.node, self.off,
            np.concatenate([self.child, len(self.to_resolve) + ext]),
        )

    def deps(self) -> Dict[bytes, List[bytes]]:
        """ph -> own children, for the host hasher's level loop."""
        phs = list(self.to_resolve)
        deps: Dict[bytes, List[bytes]] = {ph: [] for ph in phs}
        own = self.node[: self.child.size]
        for parent, child in zip(own.tolist(), self.child.tolist()):
            deps[phs[parent]].append(phs[child])
        return deps


class WindowCommitter:
    def __init__(self, storages, parent_root: bytes,
                 hasher: Hasher = host_hasher,
                 account_start_nonce: int = 0,
                 get_block_hash=None,
                 fused: bool = False,
                 on_block_committed=None,
                 mirror=None,
                 adaptive=None,
                 fused_held=None):
        self.storages = storages
        self.hasher = hasher
        self.fused = fused  # one-dispatch finalize (trie/fused.py)
        # cost-model-adaptive commit controller (sync/adaptive.py):
        # consulted per window at PACK time — when it holds host mode
        # the window skips the fused dispatch and hashes on the host
        # (the mirror stays attached; content-addressed reads of rows
        # admitted by earlier device windows stay valid). None = the
        # configured path is unconditional
        self.adaptive = adaptive
        # the fused program's buckets as the owner's earlier windows
        # left them (trie/fused.py HeldBuckets): the replay driver hands
        # every committer of its node the same record; a committer
        # built without one holds them over its own windows
        if fused and fused_held is None:
            from khipu_tpu.trie.fused import HeldBuckets

            fused_held = HeldBuckets()
        self.fused_held = fused_held
        # device-resident commit target (storage/device_mirror.py):
        # when set, admit_mirror() lands each sealed window's live
        # nodes in HBM straight from the fused outputs and persist()
        # becomes an ASYNC spill — reads of not-yet-spilled nodes are
        # served by the mirror through NodeStorage's read-through.
        # None = classic host-commit (persist is the publication point)
        self.mirror = mirror
        self.account_start_nonce = account_start_nonce
        self.get_block_hash = get_block_hash or (lambda n: None)
        # serving hook (serving/readview.py): called per commit_block
        # with (header, {addr: Account | None}) — the exact account
        # diff folded into the session, BEFORE any of it is durable.
        # Must be cheap and must not raise (it runs on the driver
        # thread inside the window critical path)
        self.on_block_committed = on_block_committed

        # ONE placeholder namespace for every trie in the window
        self._logs: Dict[bytes, list] = {}
        self._staged: Dict[bytes, bytes] = {}
        self._counter = [0]
        # only storage placeholders need tagging: finalize routes nodes
        # account-side by default and storage-side on membership here
        self._storage_phs: Set[bytes] = set()
        # multi-window session state: placeholders of already-collected
        # windows resolve through this map (seal substitutes them into
        # later windows' encodings before packing)
        self._resolved_global: Dict[bytes, bytes] = {}
        self._window_start = 0  # counter value at the last seal
        # deep pipeline: sealed-but-uncollected windows. A later seal
        # resolves refs into them DEVICE-TO-DEVICE (resolved-input
        # tiles): ph -> (job, global digest row) while in flight, and
        # the FIFO of in-flight jobs (collect must run in seal order —
        # window N+1's packed encodings still embed window-N
        # placeholder bytes that only resolve through _resolved_global
        # once N is collected)
        self._inflight_rows: Dict[bytes, Tuple["WindowJob", int]] = {}
        self._inflight_jobs: deque = deque()
        # windows fully persisted (rows deregistered) whose device
        # buffers await release. Drained by seal() ON THE DRIVER
        # THREAD so a release can never race a concurrent
        # _gather_ext that already holds the job's digest array
        self._retired: deque = deque()

        # always-on persist-stage accounting (node bytes + keys landed
        # in the host store, and the seconds they took): feeds the
        # ``persist_bytes_per_sec`` extra on EVERY replay metric line
        # (sync/replay.py ReplayStats -> bench emits) — unlike the
        # ledger's window.store series, this does not need the ledger
        # enabled
        self.persist_bytes = 0
        self.persist_seconds = 0.0

        self._storage_source = _StagedReadThrough(
            storages.storage_node_storage, self._staged,
            self._resolved_global,
        )
        self._evmcode_source = _StagedReadThrough(
            storages.evmcode_storage, {}
        )
        # code hashes staged since the last seal (collect persists ONLY
        # the sealed window's codes — later windows' stay staged until
        # their own roots pass)
        self._window_codes: List[bytes] = []
        self.account_trie = DeferredMPT(
            _StagedReadThrough(
                storages.account_node_storage, self._staged,
                self._resolved_global,
            ),
            root_hash=parent_root,
            _logs=self._logs,
            _staged=self._staged,
            counter=self._counter,
        )
        # (header, root_ref) per committed block, checked at finalize
        self._pending_blocks: List[Tuple[BlockHeader, bytes]] = []

    # ------------------------------------------------------------ worlds

    def make_world(self, state_root: bytes) -> BlockWorldState:
        """World factory for execute_block. The root argument is the
        previous block's (possibly placeholder) root — which is exactly
        self.account_trie's current state; a mismatch means the caller
        skipped a block."""
        del state_root  # the open session IS the parent state
        return BlockWorldState(
            self.account_trie,
            self._storage_source,
            self._evmcode_source,
            get_block_hash=self.get_block_hash,
            account_start_nonce=self.account_start_nonce,
        )

    # ------------------------------------------------------------ commit

    def commit_block(self, world: BlockWorldState, header: BlockHeader,
                     txs: Optional[list] = None) -> Dict[str, float]:
        """Fold one executed block's world into the window session
        (the deferred analog of world.flush). ``txs`` (the block's tx
        hashes) rides through to ``on_block_committed`` so the serving
        overlay can stamp per-tx visibility journeys — ``None`` when
        the journey plane is off (the zero-cost default).

        Returns the commit by part, for the driver's ``commit`` span:
        seconds in the storage tries' write-back (``storage_s``), in
        the account trie's one batch (``account_s``) and in the
        root's fold (``root_s``), how many ``accounts`` and storage
        ``slots`` were written, and how many placeholders that
        ``created`` (storage tries included)."""
        first = self._counter[0]
        t0 = time.perf_counter()
        final = world._materialized_accounts(hasher=None, window=self)
        t1 = time.perf_counter()
        removes, upserts = [], []
        for addr, acc in final.items():
            if acc is None:
                removes.append(address_key(addr))
            else:
                upserts.append((address_key(addr), acc.encode()))
        trie = self.account_trie = self.account_trie.update_many(
            removes, upserts
        )
        for code in world.codes.values():
            if code:
                h = keccak256(code)
                if h not in self._evmcode_source.staged:
                    self._window_codes.append(h)
                self._evmcode_source.staged[h] = code
        t2 = time.perf_counter()
        self._pending_blocks.append(
            (header, trie.force_hashed_root())
        )
        t3 = time.perf_counter()
        if self.on_block_committed is not None:
            self.on_block_committed(header, final, txs)
        return {
            "storage_s": t1 - t0,
            "account_s": t2 - t1,
            "root_s": t3 - t2,
            "created": self._counter[0] - first,
            "accounts": len(final),
            "slots": sum(
                len(ts.logs) for a, ts in world.storages.items()
                if final.get(a) is not None
            ),
        }

    def storage_session(self, root_ref) -> DeferredMPT:
        """A storage-trie session sharing the window namespace; root_ref
        may be a placeholder from an earlier block of the window."""
        if isinstance(root_ref, bytes) and (
            root_ref == EMPTY_TRIE_HASH or not root_ref
        ):
            root_ref = b""
        return DeferredMPT(
            self._storage_source,
            _root_ref=root_ref if root_ref else None,
            root_hash=None if root_ref else EMPTY_TRIE_HASH,
            _logs=self._logs,
            _staged=self._staged,
            counter=self._counter,
            ref_sink=self._storage_phs,
        )

    # ------------------------------------------------------ seal/collect

    def drain_retired(self) -> None:
        """Free the device buffers of windows that fully left the
        pipeline. Persist only APPENDS to ``_retired`` (it runs on a
        collector stage thread, where releasing could race a driver
        seal's d2d gather out of the same digests array); the actual
        release happens here — on the driver thread, at the next seal
        or after the final pipeline drain."""
        while self._retired:
            self._retired.popleft().fused_job.release()

    def seal(self) -> "WindowJob":
        """Close the current window ON THE DRIVER THREAD: the cheap DAG
        close-out only — counter-range capture, pending-block swap, live
        map, fresh log namespace, staged-code swap. The expensive tail
        (the pack scan, dispatch build and upload) moved to
        :meth:`pack_and_dispatch`, which the staged pipeline runs on its
        seal stage (sync/replay.py) while the driver executes the next
        window's transactions. The session continues: later blocks keep
        reading the sealed window's staged nodes and committing into the
        same namespace.

        The journal crash contract holds at the new boundary: the
        driver fsyncs the window's intent AFTER seal() and BEFORE
        handing the job to the pipeline; pack mutates memory only, so
        the first durable mutation is still persist()."""
        start, end = self._window_start, self._counter[0]
        self._window_start = end
        pending, self._pending_blocks = self._pending_blocks, []
        # fresh log namespace for the next window; the retained account
        # trie must adopt it (its children share _logs by reference)
        live = {
            ph: rec[0]
            for ph, rec in self._logs.items()
            if _is_placeholder(ph) and rec[0] > 0
        }
        self._logs = {}
        self.account_trie._logs = self._logs
        job = WindowJob(self, pending, None, live)
        job._pack_range = (start, end)
        job.codes, self._window_codes = self._window_codes, []
        return job

    def pack_and_dispatch(self, job: "WindowJob") -> None:
        """Pack the sealed window's placeholder DAG and DISPATCH the
        fused fixpoint program (async — the device hashes while later
        windows pack), or resolve synchronously on the host-hasher
        path. Runs on the pipeline's SEAL STAGE thread — double
        buffering: window N+1 packs here while window N's upload is in
        flight on device.

        Previous windows need NOT be collected first: refs into an
        in-flight window ride into this dispatch as resolved-input
        tiles (their final digests gathered device-to-device from the
        in-flight job's output — docs/window_pipeline.md), so seals can
        run ``pipeline_depth`` ahead of collects.

        Idempotent per job: a chaos death mid-pack re-runs the whole
        step from ``take_pending`` — every mutation below either
        repeats to the same value or is guarded, and ``job._packed``
        flips only at the very end. Single-threaded per committer
        (one seal stage), which is what keeps the pack of window N+1
        ordered after N's in-flight row registration."""
        if job._packed:
            return
        # retire windows that left the pipeline: their rows are out of
        # _inflight_rows, so no later pack can gather from them — drop
        # the digest/encoding device buffers (HBM stays O(in-flight
        # windows), not O(replayed chain)). Runs HERE on the single
        # seal-stage thread — the same thread as _gather_ext, so a
        # release can never race a gather out of the same array
        self.drain_retired()
        start, end = job._pack_range

        resolved_global = self._resolved_global
        inflight_rows = self._inflight_rows
        _pack_t0 = time.perf_counter() if LEDGER.enabled else 0.0
        with span("seal.pack") as pack_sp:
            pack = self._pack_sites(start, end)
            to_resolve, ext_refs = pack.to_resolve, pack.ext_refs
            max_depth = pack.max_depth
            pack_sp.set_tag("nodes", len(to_resolve))
            pack_sp.set_tag("depth", max_depth)
            pack_sp.set_tag("ext_refs", len(ext_refs))
            pack_sp.set_tag("sites", sum(pack.met.values()))
            for kind, n in pack.met.items():
                pack_sp.set_tag(f"sites_{kind}", n)
                SEAL_SITES[kind].inc(n)
        if LEDGER.enabled:
            # host-side classification event: how many encoding bytes
            # the pack step staged for dispatch (the cost model's node
            # x bytes join for seal.pack)
            LEDGER.record(
                "seal.pack", HOST,
                sum(len(e) for e in to_resolve.values()),
                duration=time.perf_counter() - _pack_t0,
            )

        job.to_resolve = to_resolve
        # chaos seam: a die between the pack scan and the dispatch —
        # the resumed stage re-runs pack_and_dispatch from the top
        # (memory-only mutations so far; the re-pack is deterministic)
        from khipu_tpu.chaos import fault_point

        fault_point("collector.pack")
        adaptive = self.adaptive
        use_device = bool(
            self.fused and to_resolve
            and (adaptive is None or adaptive.device_mode)
        )
        _disp_t0 = time.perf_counter()
        if use_device:
            try:
                from khipu_tpu import device
                from khipu_tpu.trie.fused import (
                    FusedUnsupported,
                    fused_submit,
                )

                if job.fused_job is None:
                    ext_arg = (
                        self._gather_ext(ext_refs) if ext_refs else None
                    )
                    # tentpole: the mirror's alias-admit gather rides
                    # INSIDE the dispatch (extra resolved-input rows)
                    # instead of a separate d2d pass per window
                    admit_live = (
                        job.live if self.mirror is not None else None
                    )
                    job.fused_job = fused_submit(
                        to_resolve, {}, _PLACEHOLDER_PREFIX,
                        use_jnp=device.platform() != "tpu",
                        depth=max_depth,
                        ext=ext_arg,
                        sites=pack.subs(ext_arg[1] if ext_arg else {}),
                        admit_live=admit_live,
                        held=self.fused_held,
                    )
                fj = job.fused_job
                if fj.dpos:
                    inflight_rows.update(zip(
                        fj.dpos,
                        zip(itertools.repeat(job), fj.dpos.values()),
                    ))
                    # guard: a death between registration and _packed
                    # re-runs this block — never double-queue the job
                    if job not in self._inflight_jobs:
                        self._inflight_jobs.append(job)
                if adaptive is not None:
                    # a dispatch that built its program spent seconds
                    # compiling inside this interval: the window
                    # counts, its timing is not a sample
                    adaptive.observe_window(
                        "device", len(to_resolve),
                        time.perf_counter() - _disp_t0,
                        compiled=fj.compile_seconds > 0,
                    )
                    if fj.upload_nbytes:
                        adaptive.note_upload(
                            fj.upload_nbytes, fj.upload_seconds
                        )
                job._packed = True
                return
            except FusedUnsupported:
                pass
            except Exception as e:
                # a RUNTIME device failure (driver error, OOM, a chaos
                # `raise` at the fused.dispatch seam) — degrade this
                # window to the host hasher instead of killing the
                # replay; the root checks at collect still gate
                # persistence, so correctness is unaffected.
                # InjectedDeath is a BaseException and propagates.
                import sys

                WINDOW_GAUGES["fused_fallbacks"] += 1
                event(
                    "window.degrade",
                    error=type(e).__name__,
                    nodes=len(to_resolve),
                )
                print(
                    "WARNING: fused window dispatch failed "
                    f"({type(e).__name__}: {e}); hashing this window "
                    "on the host",
                    file=sys.stderr,
                )
        # host path: level-synchronous hasher loop, resolved eagerly.
        # Cross-window refs seed the mapping from the source job's
        # digests (a blocking collect of the device output — rare: only
        # the FusedUnsupported fallback mid-pipeline takes this branch
        # with ext_refs; the pure host-hasher path resolves eagerly so
        # its digests are already in _resolved_global at the next seal)
        from khipu_tpu.trie.fused import topo_levels

        # when the ADAPTIVE controller forced host mode, hash with the
        # scalar host hasher even if the committer was built with the
        # device bulk hasher — the whole point of the downgrade is to
        # stop paying O(levels) device dispatches per window
        hasher = self.hasher
        if adaptive is not None and not adaptive.device_mode:
            hasher = host_hasher
        mapping: Dict[bytes, bytes] = {}
        for child, (src, _row) in ext_refs.items():
            real = src.fused_job.collect().get(child)
            if real is None:
                real = resolved_global.get(child)
            if real is None:
                raise WindowPlaceholderError(
                    child, "is referenced across windows but has no digest"
                )
            mapping[child] = real
        with span("window.hash", nodes=len(to_resolve)):
            for level in topo_levels(pack.deps()):
                encodings = [
                    _substitute_bytes(to_resolve[ph], mapping)
                    for ph in level
                ]
                digests = hasher(encodings)
                mapping.update(zip(level, digests))
        job.mapping = mapping
        # digests are FINAL here — publish now so the next seal resolves
        # this window's refs without a barrier (persistence is still
        # gated by collect's root checks); idempotent on a re-run
        resolved_global.update(mapping)
        if adaptive is not None:
            adaptive.observe_window(
                "host", len(to_resolve),
                time.perf_counter() - _disp_t0,
            )
        job._packed = True

    def _pack_sites(self, start: int, end: int) -> "_Pack":
        """Locate every placeholder site of the counter range
        ``[start, end)`` ONCE (trie/deferred.py find_sites over the
        joined staged encodings) and sort the sites by what their child
        is. Memory-only and repeatable: a re-run after a death gives
        the same values.

        Placeholder counters are handed out at node creation and tries
        build bottom-up, so a window's own child has a LOWER counter
        than its parent: own children are an index range, found by
        arithmetic, and only the other sites (refs into earlier
        windows, opaque look-alikes) go to the dicts."""
        staged = self._staged
        phs = [_make_placeholder(i) for i in range(start, end)]
        encs = list(map(staged.get, phs))
        idx = np.arange(start, end, dtype=np.int64)
        if None in encs:  # e.g. another session's counter range
            have = [i for i, e in enumerate(encs) if e is not None]
            phs = [phs[i] for i in have]
            encs = [encs[i] for i in have]
            idx = idx[have]
        n = len(phs)
        sites = find_sites(encs)
        node, ctr = sites.node, sites.ctr
        # own child: staged in this range, and below its parent.
        # slot[c - start] is the node of counter c; the last slot is
        # what every counter outside the range reads
        slot = np.full(end - start + 1, -1, np.int64)
        slot[idx - start] = np.arange(n)
        k = slot[np.where((ctr >= start) & (ctr < end), ctr - start, -1)]
        local = (k >= 0) & (k < node)
        child = k[local]
        parent = node[local]

        # every other site goes to the dicts, once per DISTINCT child
        # (a node an earlier window wrote is held by each version of
        # its parent this window staged; a counter past 63 bits is
        # told apart by nothing but its bytes, so each such site is a
        # child of its own). The order is the collector's: it
        # publishes a window's hashes BEFORE it drops its in-flight
        # rows, so the in-flight probe is followed by a re-check
        resolved_global = self._resolved_global
        inflight_rows = self._inflight_rows
        joined = sites.joined
        other = np.flatnonzero(~local)
        other_pos = sites.pos[other]
        ids = ctr[other]
        wide = np.flatnonzero(ids < 0)
        ids[wide] = -1 - np.arange(wide.size)
        _, first, which = np.unique(
            ids, return_index=True, return_inverse=True)
        keys = [joined[p : p + 32] for p in other_pos[first].tolist()]
        reals = list(map(resolved_global.get, keys))
        in_flight = np.zeros(len(keys), bool)
        ext_refs: Dict[bytes, Tuple["WindowJob", int]] = {}
        for j in [j for j, real in enumerate(reals) if real is None]:
            key = keys[j]
            src = inflight_rows.get(key)
            if src is not None:
                ext_refs[key] = src
                in_flight[j] = True
                continue
            reals[j] = resolved_global.get(key)
            if reals[j] is None and key in staged:
                # neither this window's, nor resolved, nor in flight: a
                # foreign session sharing the staged namespace —
                # hashing would bake placeholder bytes into the node
                raise AssertionError(
                    "seal(): unresolvable placeholder ref (foreign "
                    "session sharing the staged namespace?)"
                )
        resolved, digests = found_digests(reals)
        hit = resolved[which]
        to_resolve = dict(zip(phs, splice_sites(
            sites, other_pos[hit],
            digests[(np.cumsum(resolved) - 1)[which[hit]]],
        )))
        ext_at = np.flatnonzero(in_flight[which])

        # depth: 1 + the deepest own child, in ONE ordered pass over
        # the own-child sites: the scan left them in ascending parent
        # order and a child is below its parent, so every child has its
        # depth by the time its parent is reached. Plain ints in a
        # Python loop, not numpy a level at a time: an array call lets
        # go of the GIL, and beside busy threads this stage would queue
        # for it once a level
        depth = [1] * n
        for p, c in zip(parent.tolist(), child.tolist()):
            if depth[c] >= depth[p]:
                depth[p] = depth[c] + 1
        max_depth = max(depth, default=0)
        n_hit, n_ext = int(hit.sum()), int(ext_at.size)
        return _Pack(
            to_resolve=to_resolve,
            ext_refs=ext_refs,
            max_depth=max_depth,
            met={
                "local": int(child.size),
                "resolved": n_hit,
                "ext": n_ext,
                "opaque": int(other.size) - n_hit - n_ext,
            },
            node=np.concatenate([parent, node[other[ext_at]]]),
            off=np.concatenate([sites.off[local], sites.off[other[ext_at]]]),
            child=child,
            ext_keys=[keys[j] for j in which[ext_at].tolist()],
        )

    def _gather_ext(self, ext_refs) -> Tuple[object, Dict[bytes, int]]:
        """Build the resolved-input tile for ``fused_submit``: gather
        the referenced rows out of each in-flight job's device digest
        array (device-to-device, no host round-trip) and concatenate.
        Returns ``(tile u8[n,32], ph -> tile row)``. The fixpoint
        program only reads the tile rows AFTER its own queue position,
        by which time the source dispatch has finished — XLA's program
        order on one device is the synchronization."""
        from khipu_tpu.trie.fused import gather_ext_tile

        groups: Dict[int, Tuple["WindowJob", List[bytes]]] = {}
        for child, (src, _row) in ext_refs.items():
            groups.setdefault(id(src), (src, []))[1].append(child)
        sources = []
        nbytes = 0
        for src, childs in groups.values():
            rows = np.asarray(
                [src.fused_job.dpos[c] for c in childs], dtype=np.int32
            )
            sources.append((src.fused_job.digests, rows))
            nbytes += rows.nbytes
        ext_pos: Dict[bytes, int] = {}
        with span("seal.alias_gather", refs=len(ext_refs)):
            # d2d gathers out of the source jobs' digest tiles: only
            # the int32 row indices are uploaded
            with LEDGER.transfer("seal.alias_gather", H2D, nbytes):
                tile, offsets = gather_ext_tile(sources, self.fused_held)
        for (_src, childs), base in zip(groups.values(), offsets):
            for i, c in enumerate(childs):
                ext_pos[c] = base + i
        return tile, ext_pos

    def collect_roots(self, job: "WindowJob"
                      ) -> List[Tuple[BlockHeader, bytes]]:
        """Stage 1 of the staged collect: CHECK every block root
        against its header, fetching ONLY the per-block root digests
        from the device (32 B x blocks via FusedJob.fetch_rows) — not
        the full digest tile, which stays on device for persist().
        Returns [(header, real_root)] and marks the job root-checked.

        May run while the PREVIOUS window is still in persist (its
        full mapping not yet published): a root ref pointing into it
        resolves through that job's own fetch_rows via
        ``_inflight_rows`` — rows are deregistered only at the end of
        persist, so FIFO stage order guarantees the source is there."""
        # non-staged callers (finalize, degraded collector, direct
        # tests) reach here straight from seal() — pack lazily
        if not job._packed:
            self.pack_and_dispatch(job)
        if job.fused_job is not None and job in self._inflight_jobs:
            for other in self._inflight_jobs:
                if other is job:
                    break
                if not other._roots_checked:
                    # window N+1's encodings still embed window-N
                    # placeholder bytes that only resolve once N runs
                    raise AssertionError(
                        "collect() out of FIFO order: an earlier "
                        "sealed window is still in flight"
                    )
        resolved_global = self._resolved_global
        refs = [root_ref for _h, root_ref in job.pending_blocks]
        results: List[Tuple[BlockHeader, bytes]] = []
        # the span covers the per-block digest FETCH as well as the
        # header comparison — fetch_rows is the d2h that makes this
        # step cost anything, so excluding it hid the whole sub-phase
        with span("seal.rootcheck", blocks=len(job.pending_blocks)):
            if job.mapping is not None:
                fetched = job.mapping
            elif job.fused_job is not None:
                fetched = job.fused_job.fetch_rows(refs)
            else:
                fetched = {}
            for header, root_ref in job.pending_blocks:
                real = fetched.get(root_ref) or resolved_global.get(
                    root_ref
                )
                if real is None:
                    # an earlier window mid-persist: fetch its digest
                    # row straight off the device
                    src = self._inflight_rows.get(root_ref)
                    if src is not None:
                        real = src[0].fused_job.fetch_rows(
                            [root_ref]
                        ).get(root_ref)
                if real is None:
                    real = root_ref
                if real != header.state_root:
                    raise WindowMismatch(
                        header.number, real, header.state_root
                    )
                results.append((header, real))
        job.results = results
        job._roots_checked = True
        return results

    def admit_mirror(self, job: "WindowJob") -> None:
        """Stage-1 second half: land the window's LIVE nodes in the
        device mirror straight from the fused outputs — encodings
        gathered d2d from the FINAL substituted buffers, claimed
        digests d2d from the digest tile; only the int32 row-index
        array is uploaded. Rows are keyed by the window's
        placeholder ALIASES (real digests are still on device) and
        persist() rekeys them once the mapping lands on host.
        No-op without a mirror or on the host-hasher path."""
        fj = job.fused_job
        mirror = self.mirror
        if mirror is None or fj is None:
            if fj is not None:
                fj.release_encs()
            return
        # fast path: the dispatch itself already gathered the live
        # rows (trie/fused.py admit_live) — the tiles land straight in
        # the mirror with zero extra device round-trips. The span
        # keeps the seal.alias_gather name so the cost model bills
        # the eliminated gather to the same site
        tiles = fj.admit_tiles
        if tiles is not None:
            aliases2: List[bytes] = []
            with span("seal.alias_gather", live=len(job.live),
                      fused_admit=True):
                for nb2, keys2, enc_g2, claim_g2, lengths2 in tiles:
                    mirror.admit_device(
                        nb2, keys2, enc_g2, claim_g2, lengths2
                    )
                    aliases2.extend(k for k in keys2 if k is not None)
            job.aliases = aliases2
            fj.admit_tiles = None  # free the gathered device arrays
            fj.release_encs()
            return
        if fj.encs is None:
            fj.release_encs()
            return
        import jax.numpy as jnp

        from khipu_tpu.ops.keccak_jnp import RATE
        from khipu_tpu.storage.device_mirror import TILE

        live = job.live
        aliases: List[bytes] = []
        with span("seal.alias_gather", live=len(live)):
            for c, (phs, base) in enumerate(fj.class_rows):
                enc_dev = fj.encs[c]
                nb = int(enc_dev.shape[1]) // RATE
                idx: List[int] = []
                keys: List[Optional[bytes]] = []
                lengths: List[int] = []
                for r, ph in enumerate(phs):
                    if ph in live:
                        idx.append(r)
                        keys.append(ph)
                        lengths.append(len(job.to_resolve[ph]))
                if not idx:
                    continue
                n = len(idx)
                npad = -(-n // TILE) * TILE
                # gather padding points at the class's guaranteed
                # padding row: its final encoding is a valid multi-
                # rate-padded row and digests[base+dummy] is its
                # self-consistent digest, so filler slots verify
                dummy = int(enc_dev.shape[0]) - 1
                idx_np = np.full(npad, dummy, dtype=np.int32)
                idx_np[:n] = idx
                keys.extend([None] * (npad - n))
                lengths.extend([0] * (npad - n))
                with LEDGER.transfer(
                    "mirror.admit_window", H2D, idx_np.nbytes
                ):
                    idx_dev = jnp.asarray(idx_np)
                enc_g = enc_dev[idx_dev]  # d2d
                claim_g = fj.digests[base + idx_dev]  # d2d
                mirror.admit_device(nb, keys, enc_g, claim_g, lengths)
                aliases.extend(k for k in keys if k is not None)
        job.aliases = aliases
        fj.release_encs()

    def persist(self, job: "WindowJob") -> None:
        """Stage 2: fetch the window's full mapping (the one remaining
        bulk d2h, now OFF the critical path), publish it, spill the
        substituted encodings to host storage, prune session state.

        May run on a background stage thread while the driver seals
        later windows and the collect stage root-checks the next
        window. The step ORDER below is the thread-safety invariant
        (every mutation is a GIL-atomic dict/deque op). WITH a mirror:
        rekey the device rows to their real hashes FIRST, then publish
        ``_resolved_global`` — a reader following a published hash
        finds the node in the mirror even before the host spill lands
        (NodeStorage read-through). WITHOUT a mirror: spill BEFORE
        publishing, publish BEFORE pruning ``_staged``, prune BEFORE
        dropping the in-flight rows — a racing ``seal`` or
        ``_StagedReadThrough`` reader always finds each node through
        at least one of the maps."""
        if job.fused_job is not None and self._inflight_jobs:
            if (self._inflight_jobs[0] is not job
                    and job in self._inflight_jobs):
                raise AssertionError(
                    "persist() out of FIFO order: an earlier sealed "
                    "window is still in flight"
                )
        mapping = job.mapping
        if mapping is None:
            mapping = job.fused_job.collect()
        resolved_global = self._resolved_global
        published = False
        if job.aliases:
            with span("window.rekey", rows=len(job.aliases)):
                self.mirror.rekey(
                    {a: mapping[a] for a in job.aliases if a in mapping}
                )
            resolved_global.update(mapping)
            published = True

        # spill LIVE nodes only (dead intermediates were hashed for the
        # root checks but nothing references them), routed by session
        # tag. Substitution is ONE vectorized pass over the joined
        # encodings (numpy prefix scan) instead of a Python scan per
        # node.
        # Cross-window refs resolve through resolved_global: FIFO
        # persist order guarantees the source window published first.
        live_phs: List[bytes] = []
        reals: List[bytes] = []
        encs: List[bytes] = []
        for ph in job.live:
            real = mapping.get(ph) or resolved_global.get(ph)
            if real is None:
                raise WindowPlaceholderError(ph, "has no resolved digest")
            enc = job.to_resolve.get(ph)
            if enc is None:
                raise WindowPlaceholderError(ph, "has no packed encoding")
            live_phs.append(ph)
            reals.append(real)
            encs.append(enc)

        def _lookup(ref, _m=mapping, _g=resolved_global):
            v = _m.get(ref)
            return v if v is not None else _g.get(ref)

        from khipu_tpu.chaos import fault_point

        # bulk-tile spill: the mirror's resident rows ARE the final
        # substituted encodings — read them back one whole-tile array
        # slice per mirror tile (mirror.spill) instead of substituting
        # every node on the host. Rows ring-evicted before the spill
        # fall back to host substitution below (and count in
        # khipu_mirror_unspilled_evictions)
        spilled: Dict[bytes, bytes] = {}
        if published and self.mirror is not None and reals:
            spilled = self.mirror.spill_rows(reals)

        with span("window.store", live=len(live_phs)):
            if spilled:
                miss = [
                    i for i, real in enumerate(reals)
                    if real not in spilled
                ]
                miss_sub = (
                    _substitute_many([encs[i] for i in miss], _lookup)
                    if miss else []
                )
                miss_map = dict(zip(miss, miss_sub))
                subbed = [
                    miss_map[i] if i in miss_map else spilled[real]
                    for i, real in enumerate(reals)
                ]
            else:
                subbed = _substitute_many(encs, _lookup)
            account_nodes: Dict[bytes, bytes] = {}
            storage_nodes: Dict[bytes, bytes] = {}
            storage_phs = self._storage_phs
            for ph, real, enc in zip(live_phs, reals, subbed):
                if ph in storage_phs:
                    storage_nodes[real] = enc
                else:
                    account_nodes[real] = enc
            t_store = time.perf_counter()
            self.storages.account_node_storage.update([], account_nodes)
            # chaos seam: a `die` here kills the spill between the two
            # node stores — the torn window must roll back bit-exact
            # through journal.recover() (host state has the account
            # half only; the mirror is volatile and detached there)
            fault_point("collector.spill")
            self.storages.storage_node_storage.update([], storage_nodes)
            store_bytes = sum(len(e) for e in subbed) + 32 * len(live_phs)
            store_secs = time.perf_counter() - t_store
            self.persist_bytes += store_bytes
            self.persist_seconds += store_secs
            if LEDGER.enabled:
                # host-side store traffic: classification only (HOST
                # direction never feeds the device-transfer counters)
                LEDGER.record(
                    "window.store", HOST, store_bytes,
                    duration=store_secs,
                )
        # only THIS window's codes persist (later windows' roots are
        # still unchecked; their codes stay staged until their collect)
        staged_codes = self._evmcode_source.staged
        for code_hash in job.codes:
            code = staged_codes.pop(code_hash, None)
            if code is not None:
                self.storages.evmcode_storage.put(code_hash, code)
        if not published:
            resolved_global.update(mapping)
        # prune the persisted window's staged encodings: the live nodes
        # are durable (or mirror-resident) and retained trie refs read
        # through the resolved mapping (_StagedReadThrough); dead ones
        # are unreferenced — keeps session memory ~O(open windows),
        # not O(replayed chain)
        staged = self._staged
        for ph in job.to_resolve:
            staged.pop(ph, None)
            storage_phs.discard(ph)
        # drop the in-flight registration LAST: a racing seal that
        # misses these rows re-checks _resolved_global, published above
        if job.fused_job is not None:
            inflight = self._inflight_rows
            for ph in job.fused_job.dpos:
                inflight.pop(ph, None)
            if self._inflight_jobs and self._inflight_jobs[0] is job:
                self._inflight_jobs.popleft()
            # device buffers released by the NEXT seal on the driver
            # thread (see __init__._retired) — never here, where a
            # concurrent _gather_ext may hold the digest array
            self._retired.append(job)

    def collect(self, job: "WindowJob") -> List[Tuple[BlockHeader, bytes]]:
        """Root-check + mirror-admit + persist in one call — the
        synchronous composition the non-staged paths (finalize, the
        degraded collector, direct tests) use. The staged pipeline in
        sync/replay.py calls the three stages separately so the bulk
        d2h of persist() overlaps the next window's root checks."""
        results = self.collect_roots(job)
        self.admit_mirror(job)
        self.persist(job)
        return results

    # ---------------------------------------------------------- finalize

    def finalize(self) -> List[Tuple[BlockHeader, bytes]]:
        """Resolve the whole open window's placeholder DAG, CHECK every
        block root against its header, persist all nodes + codes.
        Returns [(header, real_root)]. (seal + collect back to back —
        the pipelined replay driver calls them separately to overlap the
        device wait with the next window's host execution.)"""
        return self.collect(self.seal())


class WindowJob:
    """A sealed window in flight: its packed DAG (placeholder -> pre-
    substituted encoding), live set, pending block-root checks, and
    either an async FusedJob (device) or an eager mapping (host)."""

    __slots__ = ("committer", "pending_blocks", "to_resolve", "live",
                 "fused_job", "mapping", "codes", "results", "aliases",
                 "_roots_checked", "_packed", "_pack_range")

    def __init__(self, committer, pending_blocks, to_resolve, live):
        self.committer = committer
        self.pending_blocks = pending_blocks
        # None until pack_and_dispatch runs (seal() is close-out only)
        self.to_resolve = to_resolve
        self.live = live
        self.fused_job = None
        self.mapping: Optional[Dict[bytes, bytes]] = None
        self.codes: List[bytes] = []
        # set by collect_roots / admit_mirror (staged collect)
        self.results: Optional[List[Tuple[BlockHeader, bytes]]] = None
        self.aliases: List[bytes] = []
        self._roots_checked = False
        # pack_and_dispatch state: the counter range captured at seal
        # and the flipped-at-the-end idempotency latch
        self._packed = False
        self._pack_range: Tuple[int, int] = (0, 0)
