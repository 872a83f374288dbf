"""Regular-sync replay driver: feed blocks through execution, gate every
root, keep the per-block perf line.

Parity: blockchain/sync/RegularSyncService.scala:43 —
executeAndInsertBlocks:381 (serial fold), executeAndInsertBlock:405
(validate -> execute -> save), and the one-line per-block perf report
:429 (tx/s, mgas/s, parallel %, cache hit %). Networking is replaced by
a block source (another Blockchain, or decoded RLP blocks); the
north-star replay metric (blocks/s) is measured here.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterable, List, Optional

from khipu_tpu.chaos import InjectedDeath, fault_point
from khipu_tpu.chaos import apply_config as apply_fault_config
from khipu_tpu.config import KhipuConfig
from khipu_tpu.domain.block import Block
from khipu_tpu.domain.blockchain import Blockchain
from khipu_tpu.domain.difficulty import calc_difficulty
from khipu_tpu.ledger.ledger import execute_block
from khipu_tpu.sync.prefetch import recover_block_senders
from khipu_tpu.observability.journey import JOURNEY, current_node
from khipu_tpu.observability.profiler import HOST, LEDGER
from khipu_tpu.observability.registry import REGISTRY
from khipu_tpu.observability.trace import (
    Tracer,
    apply_config,
    event,
    span,
    use_tracer,
)
from khipu_tpu.observability.trace import tracer as _default_tracer
from khipu_tpu.validators.validators import (
    BlockHeaderValidator,
    BlockValidator,
    OmmersValidator,
)

# live window-pipeline gauges served by the khipu_metrics RPC
# (jsonrpc/eth_service.py), registered as khipu_pipeline_* in the
# unified registry. The GaugeGroup keeps dict-style writes — a gauge
# set is one attribute store, so the collector thread and the driver
# both update them in place exactly as the plain dict allowed.
PIPELINE_GAUGES = REGISTRY.gauge_group("khipu_pipeline", {
    "depth": 0,  # configured pipeline_depth of the last run
    "in_flight": 0,  # windows sealed but not yet fully saved
    "windows_sealed": 0,
    "windows_collected": 0,
    "occupancy": 0.0,  # driver/collector overlap fraction, last run
    "driver_stall_s": 0.0,  # driver seconds blocked on backpressure
    "collector_busy_s": 0.0,  # background stage busy seconds (all)
    "collector_deaths": 0,  # dead workers detected by liveness checks
    "sync_fallback_windows": 0,  # windows committed synchronously after
    # a collector death (graceful degradation — docs/recovery.md)
    # per-stage occupancy/depth of the staged collector pipeline
    # (seal -> collect -> persist -> save; docs/window_pipeline.md)
    "stage_seal_depth": 0,
    "stage_collect_depth": 0,
    "stage_persist_depth": 0,
    "stage_save_depth": 0,
    "stage_seal_busy_s": 0.0,
    "stage_collect_busy_s": 0.0,
    "stage_persist_busy_s": 0.0,
    "stage_save_busy_s": 0.0,
}, help="window-pipeline state (sync/replay.py)")


class CollectorDied(RuntimeError):
    """The background collector thread is no longer alive but never
    recorded a failure — a simulated (chaos ``die``) or real
    (interpreter-level) death mid-job. Detected by the timed liveness
    checks in submit/drain instead of hanging on the condition
    variable forever."""


@dataclass
class ReplayStats:
    blocks: int = 0
    txs: int = 0
    gas: int = 0
    seconds: float = 0.0
    parallel_txs: int = 0
    conflicts: int = 0
    # execute-stage split (ledger/schedule.py): txs through the
    # vectorized fast path vs the serial residue, and scheduled
    # attempts discarded by the post-hoc footprint check
    fast_path_txs: int = 0
    residue_txs: int = 0
    mispredictions: int = 0
    # per-phase wall-clock split (seconds): senders / validate / execute
    # / commit / seal / collect / save — the breakdown that names the
    # next bottleneck instead of guessing it. Under the deep pipeline
    # `seal` is the driver's cheap close-out + journal fsync and
    # `collect`/`save` are DRIVER-THREAD STALL (backpressure + drains);
    # the staged collector's busy time lands in `seal_bg` (pack +
    # dispatch build + upload) / `collect_bg` (root checks + mirror
    # admit) / `persist_bg` (async host spill) / `save_bg` (block
    # saves) — those overlap execute, so adding them to wall clock
    # would double-count
    phases: dict = field(default_factory=dict)
    # fraction of the collector's busy time that overlapped driver work
    # (1.0 = collect/save fully hidden behind execution)
    pipeline_occupancy: float = 0.0
    # persist-stage store traffic (WindowCommitter always-on counters):
    # node bytes + keys landed in the host store and the seconds the
    # store writes took — persist bytes/s is their quotient
    persist_bytes: int = 0
    persist_store_seconds: float = 0.0

    @property
    def blocks_per_s(self) -> float:
        return self.blocks / self.seconds if self.seconds else 0.0

    @property
    def persist_bytes_per_sec(self) -> float:
        """Persist-stage store throughput (bytes landed per second of
        store-write time — the number the Kesque engine moves)."""
        if self.persist_store_seconds <= 0.0:
            return 0.0
        return self.persist_bytes / self.persist_store_seconds

    @property
    def fast_path_coverage(self) -> float:
        """Fraction of executed txs the vectorized fast path carried —
        the scheduler's headline number (1.0 = every tx predicted and
        batched; the mixed-contract fixture pins it BELOW 0.5 to prove
        the residue carries real traffic)."""
        return self.fast_path_txs / self.txs if self.txs else 0.0

    def phase_line(self) -> dict:
        return {k: round(v, 3) for k, v in self.phases.items()}


def _timed_prefetch_pull(prefetcher, ph):
    """Pull blocks off the prefetch queue, billing the wait: it is the
    part of sender recovery the background thread failed to hide, so
    without it the driver phases would no longer tile the wall clock
    (pipeline.stall is a DRIVER_PHASES member; ph["senders"] keeps the
    phase attribution honest)."""
    it = iter(prefetcher)
    while True:
        t0 = time.perf_counter()
        with span("pipeline.stall", kind="prefetch"):
            try:
                block = next(it)
            except StopIteration:
                return
        ph["senders"] += time.perf_counter() - t0
        yield block


class _WindowCollector:
    """Staged background collector pipeline: each window job flows
    through up to four bounded FIFO stages on dedicated threads —
    **seal** (the pack scan + fused dispatch build + upload, off the
    driver; window N+1 packs while window N's upload is in flight —
    the double buffering), **collect** (root checks + d2d mirror
    admit), **persist** (async host spill of the window's nodes),
    **save** (block storage) — while the driver executes the next
    window's transactions. ``submit``
    enqueues one job (a single callable, or a tuple of per-stage
    callables) and blocks only while ``depth`` jobs already occupy the
    first stage (backpressure); stage hand-offs are bounded the same
    way; ``drain`` blocks until every stage is empty. Within a stage
    jobs run strictly FIFO — block saves chain total difficulty, and
    window N+1's encodings resolve through window N's published hashes
    (ledger/window.persist docstring) — and a job cannot overtake
    another across stages because hand-off order preserves queue order.

    Failure semantics: the FIRST exception (typically WindowMismatch)
    aborts the whole pipeline — queued jobs at EVERY stage are dropped
    WITHOUT persisting anything and the original exception object
    re-raises on the driver thread at its next submit/drain, so a
    mismatch still names the failing block number."""

    STAGES = ("seal", "collect", "persist", "save")

    def __init__(self, depth: int, join_timeout: float = 60.0,
                 liveness_poll: float = 0.1):
        self.depth = max(1, depth)
        self.join_timeout = join_timeout
        # backpressure/drain waits wake at this period to re-check the
        # workers are still alive — a dead thread can never notify, so
        # an untimed wait would hang the driver forever
        self.liveness_poll = liveness_poll
        self._cv = threading.Condition()
        k = len(self.STAGES)
        self._qs: List[deque] = [deque() for _ in range(k)]
        self._active: List[bool] = [False] * k
        self._current: List[Optional[tuple]] = [None] * k
        self._done: List[bool] = [False] * k  # normal thread exit
        self.stage_busy: List[float] = [0.0] * k
        self._failure: Optional[BaseException] = None
        self._closed = False
        self._inflight = 0  # jobs submitted but not fully completed
        self._threads = [
            threading.Thread(
                target=self._run, args=(i,),
                name=f"window-{name}", daemon=True,
            )
            for i, name in enumerate(self.STAGES)
        ]
        for t in self._threads:
            t.start()

    @property
    def _thread(self) -> threading.Thread:
        """The first-stage thread — the legacy single-worker handle
        (tests and external liveness probes join/poll it)."""
        return self._threads[0]

    @property
    def busy_seconds(self) -> float:
        return sum(self.stage_busy)

    # ------------------------------------------------------- driver side

    def _update_gauges(self) -> None:
        """Call under ``_cv``."""
        PIPELINE_GAUGES["in_flight"] = self._inflight
        for i, name in enumerate(self.STAGES):
            PIPELINE_GAUGES[f"stage_{name}_depth"] = (
                len(self._qs[i]) + (1 if self._active[i] else 0)
            )
            PIPELINE_GAUGES[f"stage_{name}_busy_s"] = round(
                self.stage_busy[i], 3
            )

    def _check_liveness(self) -> None:
        """Call under ``_cv``. A stage worker that exited without
        recording a failure, without being closed, died mid-job (chaos
        ``die`` or a real interpreter-level death) — raise instead of
        waiting on notifies that will never come."""
        if self._failure is not None or self._closed:
            return
        for i, t in enumerate(self._threads):
            if not self._done[i] and not t.is_alive():
                raise CollectorDied(
                    f"window-{self.STAGES[i]} stage thread died mid-"
                    f"job ({sum(len(q) for q in self._qs)} queued, "
                    f"active={self._active})"
                )

    def submit(self, fns) -> float:
        """Queue one job: a bare callable (runs entirely on the first
        stage) or a tuple of per-stage callables — stage i runs
        ``fns[i]`` then hands the job to stage i+1; the job completes
        at its last callable. Returns driver seconds stalled on
        first-stage backpressure. Re-raises the collector's failure,
        if any; raises CollectorDied when a worker is gone."""
        fns = (fns,) if callable(fns) else tuple(fns)
        t0 = time.perf_counter()
        with self._cv:
            self._check_liveness()
            while (self._failure is None and not self._closed
                   and len(self._qs[0]) + self._active[0] >= self.depth):
                self._cv.wait(timeout=self.liveness_poll)
                self._check_liveness()
            if self._failure is not None:
                raise self._failure
            if self._closed:
                raise RuntimeError("collector is closed")
            self._qs[0].append(fns)
            self._inflight += 1
            PIPELINE_GAUGES["windows_sealed"] += 1
            self._update_gauges()
            self._cv.notify_all()
        return time.perf_counter() - t0

    def drain(self) -> float:
        """Wait until every submitted job has fully completed (all
        stages); returns driver seconds stalled. Re-raises the
        collector's failure, if any; raises CollectorDied when a
        worker is gone."""
        t0 = time.perf_counter()
        with self._cv:
            self._check_liveness()
            while self._failure is None and self._inflight:
                self._cv.wait(timeout=self.liveness_poll)
                self._check_liveness()
            if self._failure is not None:
                raise self._failure
        return time.perf_counter() - t0

    def take_pending(self) -> List[Callable[[], None]]:
        """After CollectorDied: every unfinished job in FIFO order —
        deepest stage first (those windows are oldest), each stage's
        partially-executed current job ahead of its queue (jobs are
        idempotent: node puts are content-addressed, block saves
        overwrite by number, stats apply only at job end). A job with
        several stages left comes back as one closure running them in
        order; a job with ONE stage left comes back as that bare
        callable. Marks the collector closed; the caller runs these
        synchronously."""
        with self._cv:
            out: List[Callable[[], None]] = []
            for i in range(len(self.STAGES) - 1, -1, -1):
                entries: List[tuple] = []
                if self._active[i] and self._current[i] is not None:
                    entries.append(self._current[i])
                entries.extend(self._qs[i])
                self._qs[i].clear()
                out.extend(self._resume(fns, i) for fns in entries)
            self._closed = True
            self._inflight = 0
            self._update_gauges()
            self._cv.notify_all()
        return out

    @staticmethod
    def _resume(fns: tuple, i: int) -> Callable[[], None]:
        rest = fns[i:]
        if len(rest) == 1:
            return rest[0]

        def run_rest():
            for fn in rest:
                fn()

        return run_rest

    def close(self) -> None:
        """Stop the workers (after finishing anything queued) and join.
        Safe to call twice. Raises if any worker is still alive after
        ``join_timeout`` — a wedged job must not be silently abandoned
        with the pipeline's windows unaccounted for."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        deadline = time.monotonic() + self.join_timeout
        for t in self._threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        if any(t.is_alive() for t in self._threads):
            raise RuntimeError(
                "window-collector failed to stop within "
                f"{self.join_timeout:.0f}s — a wedged job is still "
                "holding the pipeline (its windows are NOT committed)"
            )

    def kill(self) -> None:
        """Abort: drop queued jobs at every stage WITHOUT running them
        (nothing else persists) and join. The driver calls this when IT
        failed — windows sealed after the failing block must not be
        committed. Already unwinding, so a wedged worker is logged
        loudly instead of raised over the original failure."""
        with self._cv:
            for q in self._qs:
                q.clear()
            self._closed = True
            self._inflight = 0
            self._update_gauges()
            self._cv.notify_all()
        deadline = time.monotonic() + self.join_timeout
        for t in self._threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        if any(t.is_alive() for t in self._threads):
            import sys

            print(
                "WARNING: window-collector did not stop within "
                f"{self.join_timeout:.0f}s of kill(); abandoning the "
                "wedged daemon thread",
                file=sys.stderr,
            )

    # ------------------------------------------------------- worker side

    def _exit_ready(self, i: int) -> bool:
        """Call under ``_cv``: stage ``i`` may exit once the collector
        is closed and its upstream can never forward again — exited
        normally, or died mid-job (its torn job is take_pending's to
        re-run, never forwarded)."""
        if not self._closed:
            return False
        if i == 0:
            return True
        return self._done[i - 1] or not self._threads[i - 1].is_alive()

    def _run(self, i: int) -> None:
        q = self._qs[i]
        while True:
            with self._cv:
                while (not q and self._failure is None
                       and not self._exit_ready(i)):
                    # timed: an upstream death is silent (no notify)
                    self._cv.wait(timeout=0.5)
                if self._failure is not None or (
                    not q and self._exit_ready(i)
                ):
                    self._done[i] = True
                    self._cv.notify_all()
                    return
                fns = q.popleft()
                self._current[i] = fns
                self._active[i] = True
                self._update_gauges()
            t0 = time.perf_counter()
            try:
                fns[i]()
            except InjectedDeath:
                # simulated process death (chaos `die`): no failure
                # record, no notify — the thread just stops with the
                # job half done, exactly like a SIGKILL. The driver's
                # liveness checks raise CollectorDied; _current stays
                # set so take_pending can re-run the torn job.
                return
            # khipu-lint: ok KL002 InjectedDeath is handled by the
            # dedicated handler above (thread stops, SIGKILL
            # semantics); everything else is RECORDED as _failure and
            # re-raised on the driver by submit()/drain() — fail-stop
            # is preserved, not swallowed
            except BaseException as exc:  # surfaces on the driver
                with self._cv:
                    self._failure = exc
                    self._active[i] = False
                    self._current[i] = None
                    for qq in self._qs:
                        qq.clear()  # abort: NOTHING else persists
                    self._inflight = 0
                    self._update_gauges()
                    self._cv.notify_all()
                return
            dt = time.perf_counter() - t0
            forward = len(fns) > i + 1 and i + 1 < len(self._qs)
            with self._cv:
                self.stage_busy[i] += dt
                PIPELINE_GAUGES["collector_busy_s"] = round(
                    self.busy_seconds, 3
                )
                if forward:
                    # bounded hand-off: wait while downstream is full
                    # (close() still forwards — queued work must
                    # complete; only kill()/failure drop it)
                    while (len(self._qs[i + 1]) >= self.depth
                           and self._failure is None
                           and not self._closed):
                        self._cv.wait(timeout=self.liveness_poll)
                    if self._failure is None:
                        self._qs[i + 1].append(fns)
                else:
                    self._inflight = max(0, self._inflight - 1)
                    PIPELINE_GAUGES["windows_collected"] += 1
                self._active[i] = False
                self._current[i] = None
                self._update_gauges()
                self._cv.notify_all()


class ReplayDriver:
    """Executes a stream of blocks against a target chain DB."""

    def __init__(
        self,
        blockchain: Blockchain,
        config: KhipuConfig,
        log: Optional[Callable[[str], None]] = None,
        validate_headers: bool = True,
        device_commit: bool = False,
        tracer: Optional[Tracer] = None,
        read_view=None,
    ):
        self.blockchain = blockchain
        self.config = config
        # serving-plane read view (serving/readview.py): committed
        # blocks publish their account diffs into it on the driver
        # thread, durable windows retire them on the collector thread,
        # and a pipeline abort invalidates everything above the
        # committed best — RPC reads stay monotonic mid-pipeline
        self.read_view = read_view
        # per-driver recorder: a driver handed its own Tracer (e.g. the
        # bridge server's — bridge.py) records there; the default stays
        # the module-global instance so single-driver processes and the
        # existing khipu_traces surface are unchanged
        self.tracer = tracer if tracer is not None else _default_tracer
        apply_config(config.observability, self.tracer)
        apply_fault_config(getattr(config, "faults", None))
        self.log = log
        self.header_validator = BlockHeaderValidator(
            config.blockchain,
            difficulty_fn=lambda h, p: calc_difficulty(
                h.unix_timestamp, p, config.blockchain
            ),
        )
        self.validate_headers = validate_headers
        # windowed-session epoch: blocks between committer resets (see
        # replay_windowed) — bounds session memory on long replays
        self.session_epoch_blocks = 512
        # route dirty-node hashing of every block commit through the
        # batched device path (Pallas on TPU); save_block's persisted-
        # root == header.state_root check gates it per block
        if device_commit:
            from khipu_tpu.trie.bulk import device_hasher

            self.hasher = device_hasher
        else:
            self.hasher = None
        # lazy per-driver device mirror (the window-commit target when
        # sync.device_mirror_commit is on); built on first windowed
        # replay so chaos configs that never reach a fused dispatch
        # pay no device setup
        self._mirror = None
        # lazy per-driver adaptive commit controller, kept across
        # replays like the mirror: a driver that serves many batches
        # (the bridge) keeps one EWMA history and one flip count
        self._adaptive = None
        # per-driver like the two above: the buckets the fused
        # program's signature has settled on (trie/fused.py
        # HeldBuckets). Every batch builds a committer of its own; the
        # signature its windows settled on must outlive it
        self._fused_held = None
        if self.hasher is not None:
            from khipu_tpu.trie.fused import HeldBuckets

            self._fused_held = HeldBuckets()

    def recover(self):
        """Crash-recovery startup pass (sync/journal.py): settle every
        pending window-commit intent — repair complete windows, roll
        back partial ones, complete or abandon torn chain switches.
        Returns a RecoveryReport."""
        from khipu_tpu.sync.journal import recover

        return recover(self.blockchain, log=self.log, config=self.config)

    def replay(self, blocks: Iterable[Block]) -> ReplayStats:
        """executeAndInsertBlocks: serial fold with full validation."""
        window = self.config.sync.commit_window_blocks
        if window > 1:
            return self.replay_windowed(blocks, window)
        stats = ReplayStats()
        t_start = time.perf_counter()
        sync = self.config.sync
        prefetcher = None
        if sync.sender_prefetch:
            from khipu_tpu.sync.prefetch import SenderPrefetcher

            prefetcher = SenderPrefetcher(
                blocks,
                depth=sync.sender_prefetch_depth,
                cache_entries=sync.sender_cache_entries,
            )
            blocks = prefetcher
        try:
            with use_tracer(self.tracer):
                for block in blocks:
                    self._execute_and_insert(block, stats)
        finally:
            if prefetcher is not None:
                prefetcher.close()
        stats.seconds = time.perf_counter() - t_start
        return stats

    def replay_windowed(
        self, blocks: Iterable[Block], window_size: int
    ) -> ReplayStats:
        """Window-batched PIPELINED replay: runs with THIS driver's
        tracer active on the calling thread (collector jobs re-activate
        it on theirs — the tracer rides the closure like ``seal_tok``),
        so concurrent drivers in one process record to disjoint rings.
        See ``_replay_windowed`` for the pipeline itself."""
        with use_tracer(self.tracer):
            return self._replay_windowed(blocks, window_size)

    def _replay_windowed(
        self, blocks: Iterable[Block], window_size: int
    ) -> ReplayStats:
        """Window-batched PIPELINED replay: execute W blocks against one
        open deferred session, seal the window (pack + async device
        dispatch of the fused fixpoint), then execute the NEXT window's
        transactions on the host while the device resolves the previous
        one — the double-buffering that hides the device round-trip
        behind host execution (SURVEY §7.4-5; the reference overlaps
        execution with persistence the same way via its actor mailbox,
        RegularSyncService.scala:381). Root checks happen at collect —
        up to ``pipeline_depth`` windows later than the serial path, on
        a background collector thread, with identical failure semantics
        (nothing of a window persists before its roots pass; a
        WindowMismatch drains the pipeline and re-raises here with the
        failing block number — docs/window_pipeline.md).
        """
        from khipu_tpu.evm.config import for_block
        from khipu_tpu.ledger.window import WindowCommitter
        from khipu_tpu.trie.bulk import host_hasher

        stats = ReplayStats()
        ph = stats.phases
        for k in ("senders", "validate", "execute", "commit", "seal",
                  "collect", "save", "seal_bg", "collect_bg",
                  "persist_bg", "save_bg", "senders_bg"):
            ph[k] = 0.0
        t_start = time.perf_counter()
        hasher = self.hasher or host_hasher
        # pipelined sender recovery (sync/prefetch.py): the prefetch
        # thread recovers window N+1's senders while this thread
        # executes window N; its busy time lands in senders_bg and the
        # driver's foreground "senders" phase becomes a cache sweep
        sync = self.config.sync
        prefetcher = None
        if sync.sender_prefetch:
            from khipu_tpu.sync.prefetch import SenderPrefetcher

            prefetcher = SenderPrefetcher(
                blocks,
                depth=sync.sender_prefetch_depth,
                cache_entries=sync.sender_cache_entries,
            )
            # the driver's wait on the prefetch queue is sender
            # recovery leaking back onto the critical path (the
            # thread can't keep ahead) — bill it to pipeline.stall so
            # the driver phases still tile the wall clock, and to the
            # senders phase so the phase split attributes it honestly
            blocks = _timed_prefetch_pull(prefetcher, ph)
        blocks = iter(blocks)
        try:
            first = next(blocks)
        except StopIteration:
            if prefetcher is not None:
                prefetcher.close()
            return stats

        parent = self.blockchain.get_header_by_number(first.number - 1)
        window_headers = {}
        window_headers_full = {}
        window_blocks = {}

        def block_hash_of(n: int):
            h = window_headers.get(n)
            return h if h else self.blockchain.get_hash_by_number(n)

        # device-resident commit (docs/window_pipeline.md): on the
        # fused device path the store's mirror becomes the commit
        # target — collect admits windows d2d and the host spill runs
        # async on the persist stage; NodeStorage read-through serves
        # not-yet-spilled nodes. One mirror per driver, reused across
        # epochs/replays (its XLA kernels are process-cached anyway)
        mirror = None
        if (self.hasher is not None
                and self.config.sync.device_mirror_commit):
            mirror = self._mirror
            if mirror is None:
                from khipu_tpu.storage.device_mirror import (
                    DeviceNodeMirror,
                )

                mirror = self._mirror = DeviceNodeMirror(
                    self.config.sync.mirror_capacity_rows
                )
            self.blockchain.storages.attach_mirror(mirror)

        # cost-model-adaptive commit (sync/adaptive.py): ONE controller
        # per driver — it outlives epoch committer rebuilds and later
        # replays so the EWMA keeps its history. device_cap mirrors
        # whether this driver could use the fused device path at all;
        # the probe (when enabled) downgrades to host before window 0
        # on backends whose "device" memory is host RAM
        adaptive = None
        if self.config.sync.adaptive_commit and self.hasher is not None:
            adaptive = self._adaptive
            if adaptive is None:
                from khipu_tpu.sync.adaptive import (
                    AdaptiveCommitController,
                )

                # the probe's calibration upload is seal-path
                # machinery — bill it to the seal phase so the cost
                # model attributes it there instead of to an
                # unattributed "?" row
                with LEDGER.context(window=0, phase="seal"):
                    adaptive = self._adaptive = AdaptiveCommitController(
                        self.config.sync, device_cap=True
                    )

        def make_committer(parent_root: bytes) -> WindowCommitter:
            return WindowCommitter(
                self.blockchain.storages,
                parent_root,
                hasher=hasher,
                account_start_nonce=(
                    self.config.blockchain.account_start_nonce
                ),
                get_block_hash=block_hash_of,
                # device mode: one-dispatch fixpoint finalize — the
                # per-level hasher loop would pay O(levels) blocking
                # host<->device round trips per window
                fused=self.hasher is not None,
                on_block_committed=(
                    self.read_view.publish_block
                    if self.read_view is not None else None
                ),
                mirror=mirror,
                adaptive=adaptive,
                fused_held=self._fused_held,
            )

        committer = make_committer(parent.state_root)
        depth = max(1, self.config.sync.pipeline_depth)
        collector = _WindowCollector(
            depth, join_timeout=self.config.sync.collector_join_timeout
        )
        PIPELINE_GAUGES["depth"] = depth
        # crash consistency: WAL intent before each background job, a
        # commit mark after its best-number advance (docs/recovery.md)
        journal = (
            self.blockchain.storages.window_journal
            if self.config.sync.commit_journal else None
        )
        window_parent_root = parent.state_root
        # graceful degradation: a dead collector thread (CollectorDied
        # from the liveness checks) switches the driver to synchronous
        # commits instead of aborting — unless config says abort
        sync_degraded = False
        degrade_on_death = self.config.sync.degrade_on_collector_death

        def _degrade() -> None:
            nonlocal sync_degraded
            sync_degraded = True
            PIPELINE_GAUGES["collector_deaths"] += 1
            event("pipeline.degrade", reason="collector-died")
            if self.log is not None:
                self.log(
                    "window-collector thread died; degrading to "
                    "synchronous window commits (jobs are idempotent "
                    "— re-running the torn one)"
                )
            for fn in collector.take_pending():
                PIPELINE_GAUGES["sync_fallback_windows"] += 1
                fn()

        def submit_job(run_fns) -> float:
            if sync_degraded:
                PIPELINE_GAUGES["sync_fallback_windows"] += 1
                for fn in run_fns:
                    fn()
                if journal is not None:
                    journal.prune()
                return 0.0
            try:
                return collector.submit(run_fns)
            except CollectorDied:
                if not degrade_on_death:
                    raise
                _degrade()
                PIPELINE_GAUGES["sync_fallback_windows"] += 1
                for fn in run_fns:
                    fn()
                return 0.0

        def drain_pipeline() -> float:
            # with the pipeline empty every intent is settled: drop the
            # committed prefix so the journal stays O(pipeline_depth),
            # not O(chain)
            if sync_degraded:
                if journal is not None:
                    journal.prune()
                return 0.0
            try:
                stall = collector.drain()
            except CollectorDied:
                if not degrade_on_death:
                    raise
                _degrade()
                return 0.0
            if journal is not None:
                journal.prune()
            return stall
        # epoch reset: every N blocks the session committer is rebuilt
        # from the last VALIDATED root, dropping the resolved-
        # placeholder map and all retained refs — with the per-collect
        # staged prune this bounds replay memory to O(epoch), not
        # O(chain) (the reference's analog is its bounded node cache +
        # persisted store)
        epoch = self.session_epoch_blocks
        blocks_since_reset = 0

        def make_stage_jobs(cm: WindowCommitter, job, results, seal_tok,
                            intent_seq):
            # the four per-stage closures one window job flows
            # through, each ON ITS OWN COLLECTOR STAGE THREAD,
            # strictly FIFO within a stage. ``seal_tok`` (the driver's
            # window.seal span id) rides the closures across the
            # queues so the trace links the stages' spans to the seal
            # that produced them (the cross-thread parent edge — flow
            # arrows in the Chrome dump). The driver's tracer rides
            # the same way: stage threads have no thread-local binding
            # of their own, and falling back to the module default
            # would split one driver's trace across two rings.
            lo, hi = results[0][0].number, results[-1][0].number
            tr = self.tracer

            def seal_fn():
                # the OFF-DRIVER seal tail: pack scan + dispatch build
                # + upload, running while the driver executes the next
                # window (and while the previous window's upload is in
                # flight — the double buffering). The journal intent
                # was fsynced on the DRIVER before this job existed,
                # and pack mutates memory only, so the crash contract
                # is unchanged: persist is still the first durable
                # mutation. The LEDGER phase stays "seal" so the
                # per-window cost model keeps attributing the
                # sub-phases to the seal family.
                with use_tracer(tr):
                    fault_point("collector.seal")
                    t0 = time.perf_counter()
                    with span("window.pack", parent=seal_tok,
                              block_lo=lo, block_hi=hi), \
                            LEDGER.context(window=lo, phase="seal"):
                        cm.pack_and_dispatch(job)
                    ph["seal_bg"] += time.perf_counter() - t0

            def collect_fn():
                # chaos seams: a rule at any of the collector.* sites
                # models a failure/death at that phase of the job
                # (docs/recovery.md crash-point table)
                with use_tracer(tr):
                    fault_point("collector.collect")
                    t0 = time.perf_counter()
                    with span("window.collect", parent=seal_tok,
                              block_lo=lo, block_hi=hi), \
                            LEDGER.context(window=lo, phase="collect"):
                        # root checks fetch ONLY the per-block root
                        # digests (32 B x blocks d2h); the window's
                        # live nodes land in the device mirror d2d
                        cm.collect_roots(job)  # raises WindowMismatch
                        cm.admit_mirror(job)
                    ph["collect_bg"] += time.perf_counter() - t0

            def persist_fn():
                with use_tracer(tr):
                    fault_point("collector.persist")
                    t0 = time.perf_counter()
                    with span("window.persist", parent=seal_tok,
                              block_lo=lo, block_hi=hi,
                              live=len(job.live)), \
                            LEDGER.context(window=lo, phase="persist"):
                        # the bulk d2h (full mapping) + host spill,
                        # now OFF the collect critical path
                        cm.persist(job)
                    ph["persist_bg"] += time.perf_counter() - t0

            def save_fn():
                with use_tracer(tr):
                    t0 = time.perf_counter()
                    blocks = txs = gas = ptxs = confl = 0
                    fast = residue = mispred = 0
                    with span("window.save", parent=seal_tok,
                              block_lo=lo, block_hi=hi,
                              blocks=len(results)), \
                            LEDGER.context(window=lo, phase="save"):
                        for block, result in results:
                            td = (
                                self.blockchain.get_total_difficulty(
                                    block.number - 1
                                )
                                or 0
                            ) + block.header.difficulty
                            # world=None: the window already persisted
                            # the nodes
                            t_save = time.perf_counter()
                            self.blockchain.save_block(
                                block, result.receipts, td, world=None
                            )
                            # host-side persistence: classification
                            # traffic for window_report, never a
                            # device crossing
                            LEDGER.record(
                                "block.save", HOST, 0,
                                duration=time.perf_counter() - t_save,
                            )
                            fault_point("collector.save")
                            blocks += 1
                            txs += result.stats.tx_count
                            gas += result.gas_used
                            ptxs += result.stats.parallel_count
                            confl += result.stats.conflict_count
                            fast += result.stats.fast_path_txs
                            residue += result.stats.residue_txs
                            mispred += result.stats.mispredicted_txs
                        # the commit mark is the job's LAST mutation:
                        # a window is durable only after persist+save
                        # — the journal's crash-consistency contract
                        # holds at every stage boundary
                        if intent_seq is not None:
                            fault_point("collector.commit")
                            journal.log_commit(intent_seq)
                        if JOURNEY.enabled:
                            # persist+save done, commit mark down: the
                            # crash-survivable point — the passport's
                            # durable page (feeds the durable-latency
                            # histogram with this ring's trace id)
                            for b, _r in results:
                                for stx in b.body.transactions:
                                    JOURNEY.record(
                                        stx.hash, "durable",
                                        block=b.header.number,
                                    )
                        if self.log is not None:
                            self.log(
                                f"Committed window [{lo}..{hi}] "
                                f"({len(results)} blocks) in one "
                                "batched device pass"
                            )
                        # stats land ONLY here, after the commit mark:
                        # a torn job re-run after a collector death
                        # stays idempotent — no double counting
                        # (nothing below can raise before they apply)
                        stats.blocks += blocks
                        stats.txs += txs
                        stats.gas += gas
                        stats.parallel_txs += ptxs
                        stats.conflicts += confl
                        stats.fast_path_txs += fast
                        stats.residue_txs += residue
                        stats.mispredictions += mispred
                        LEDGER.note_blocks(blocks)
                    # the window is durable (best advanced, commit
                    # mark down): the committed store now serves
                    # same-or-newer state, so the read-view overlay
                    # can let go of it
                    if self.read_view is not None:
                        self.read_view.retire_through(hi)
                    ph["save_bg"] += time.perf_counter() - t0

            return (seal_fn, collect_fn, persist_fn, save_fn)

        def seal_and_submit() -> None:
            nonlocal results_cur, window_parent_root
            lo = results_cur[0][0].number
            hi = results_cur[-1][0].number
            t0 = time.perf_counter()
            intent_seq = None
            LEDGER.note_window(lo, lo, hi)
            with span("window.seal", block_lo=lo, block_hi=hi) as seal_sp, \
                    LEDGER.context(window=lo, phase="seal"):
                job = committer.seal()
                if JOURNEY.enabled:
                    for b, _r in results_cur:
                        for stx in b.body.transactions:
                            JOURNEY.record(stx.hash, "seal",
                                           window_lo=lo, window_hi=hi)
                if journal is not None:
                    # WAL barrier: the intent is durable BEFORE the job
                    # can run (submit enqueues it strictly afterwards).
                    # It is part of sealing — inside the span, so the
                    # driver phase accounting sees the journal cost.
                    _j0 = time.perf_counter()
                    with span("seal.journal", block_lo=lo, block_hi=hi):
                        intent_seq = journal.log_intent(
                            lo, hi, window_parent_root,
                            [b.header.state_root for b, _ in results_cur],
                        )
                    # host-side classification event so the window
                    # report's seal row decomposes WAL cost too
                    LEDGER.record(
                        "seal.journal", HOST, 0,
                        duration=time.perf_counter() - _j0,
                    )
                    if JOURNEY.enabled:
                        # the WAL intent is fsynced: from here a crash
                        # replays the window forward — the passport's
                        # journal-intent page
                        for b, _r in results_cur:
                            for stx in b.body.transactions:
                                JOURNEY.record(stx.hash,
                                               "journal.intent",
                                               seq=intent_seq)
                # stage-job closure build stays inside the span (it
                # is part of sealing, and an unbilled sliver here
                # loses GIL slices to the stage threads — see the
                # bookkeeping note in the build loop)
                run_fns = make_stage_jobs(
                    committer, job, results_cur, seal_sp.token,
                    intent_seq,
                )
            ph["seal"] += time.perf_counter() - t0
            with span("pipeline.stall", block_lo=lo, block_hi=hi,
                      kind="submit"):
                ph["collect"] += submit_job(run_fns)
                # adaptive depth: the controller's seal.upload
                # roofline verdict sizes how many windows may queue
                # ahead of the seal stage (bytes-bound uploads
                # overlap, fixed-overhead ones don't) — applied
                # between windows, never mid-submit
                if adaptive is not None and adaptive.depth_hint:
                    new_depth = max(1, adaptive.depth_hint)
                    if new_depth != collector.depth:
                        collector.depth = new_depth
                        PIPELINE_GAUGES["depth"] = new_depth
                window_parent_root = (
                    results_cur[-1][0].header.state_root
                )
                results_cur = []

        results_cur: List = []
        prev = parent
        import itertools

        # what THIS thread pays on the node stores' miss paths, read
        # from its own book around each block while the ring is on
        thread_misses = self.blockchain.storages.thread_misses
        me = threading.get_ident()
        try:
            for block in itertools.chain((first,), blocks):
                header = block.header
                with span(
                    "window.build",
                    block=header.number,
                    txs=len(block.body.transactions),
                ) as build_sp:
                    traced = build_sp.token is not None
                    if traced:
                        miss0 = thread_misses(me)
                    t0 = time.perf_counter()
                    # cache-fronted recovery (sync/prefetch.py): a
                    # no-op sweep when the prefetch thread already
                    # filled the per-object memos; one batched native
                    # call for anything it missed. The dedicated span
                    # feeds the "senders" phase-share ceiling
                    with span("senders", block=header.number):
                        recover_block_senders(
                            block.body.transactions,
                            sync.sender_cache_entries,
                        )
                    ph["senders"] += time.perf_counter() - t0
                    if JOURNEY.enabled:
                        # passport ingress for imported txs: FIRST
                        # sighting wins, so an RPC-submitted tx keeps
                        # its rpc ingress and a reorg re-import keeps
                        # the original stamp
                        for stx in block.body.transactions:
                            JOURNEY.record(stx.hash, "ingress",
                                           source="import",
                                           block=header.number)
                    t0 = time.perf_counter()
                    if self.validate_headers:
                        self.header_validator.validate(header, prev)
                    BlockValidator.validate_body(block)
                    OmmersValidator.validate(
                        self.blockchain, block,
                        header_lookup=window_headers_full.get,
                        block_lookup=window_blocks.get,
                        header_validator=(
                            self.header_validator
                            if self.validate_headers else None
                        ),
                    )
                    config = for_block(
                        header.number, self.config.blockchain
                    )
                    if not config.byzantium:
                        raise ValueError(
                            "window commits need Byzantium receipts "
                            "(pre-Byzantium receipts embed per-tx roots)"
                        )
                    ph["validate"] += time.perf_counter() - t0
                    t0 = time.perf_counter()
                    with span("execute", block=header.number,
                              txs=len(block.body.transactions)) as sp:
                        result = execute_block(
                            block,
                            b"",  # the open session IS the parent state
                            committer.make_world,
                            self.config,
                            validate=True,
                            check_root=False,  # deferred to finalize
                        )
                        # the lanes whose result stood (they sum to
                        # txs) with the seconds each took, the segments
                        # rolled back and re-run serially with their
                        # txs, and whether the whole scheduled attempt
                        # was thrown away first
                        st = result.stats
                        for lane, n in st.lane_txs.items():
                            sp.set_tag(lane, n)
                            sp.set_tag(lane + "_s", st.lane_seconds[lane])
                        sp.set_tag("batches", st.batches)
                        sp.set_tag("reruns", st.reruns)
                        sp.set_tag("rerun_txs", st.rerun_txs)
                        sp.set_tag("fallback", int(st.fallback))
                        # execute outside its lanes (ledger.py
                        # EXEC_PARTS), and the world copies inside them
                        for part, secs in st.part_seconds.items():
                            sp.set_tag(part + "_s", secs)
                        sp.set_tag("copies", st.copies)
                        sp.set_tag("copy_s", st.copy_seconds)
                    ph["execute"] += time.perf_counter() - t0
                    t0 = time.perf_counter()
                    with span("commit", block=header.number) as sp:
                        # by part: storage tries, account trie, root
                        for tag, v in committer.commit_block(
                            result.world, header,
                            txs=(
                                [stx.hash
                                 for stx in block.body.transactions]
                                if JOURNEY.enabled else None
                            ),
                        ).items():
                            sp.set_tag(tag, v)
                    ph["commit"] += time.perf_counter() - t0
                    # window bookkeeping stays INSIDE the span: each
                    # statement outside a driver phase is a chance to
                    # lose a GIL slice to a collector stage thread,
                    # unbilled — the wall-clock tiling gate
                    # (driver_total_s vs wall_s) holds only if the
                    # driver's inter-span slivers stay negligible
                    window_headers[header.number] = header.hash
                    window_headers_full[header.number] = header
                    window_blocks[header.number] = block
                    results_cur.append((block, result))
                    prev = header
                    if traced:
                        for tag, a, b in zip(
                            ("misses", "miss_s", "miss_wait_s",
                             "miss_engine_s"),
                            miss0, thread_misses(me),
                        ):
                            build_sp.set_tag(tag, b - a)
                if len(results_cur) >= window_size:
                    # NO barrier before seal: cross-window refs resolve
                    # from the in-flight jobs' device digests (resolved-
                    # input tiles); the only wait is submit backpressure
                    # once pipeline_depth windows are queued
                    blocks_since_reset += len(results_cur)
                    seal_and_submit()
                    if blocks_since_reset >= epoch:
                        # drain the pipeline, then restart the session from
                        # the last validated root (memory bound)
                        with span("pipeline.stall", kind="epoch-drain"):
                            stalled = drain_pipeline()
                        ph["collect"] += stalled
                        # bank the retiring committer's persist-stage
                        # counters before the rebuild drops them
                        stats.persist_bytes += committer.persist_bytes
                        stats.persist_store_seconds += (
                            committer.persist_seconds
                        )
                        committer = make_committer(prev.state_root)
                        blocks_since_reset = 0
                        # header/body maps: ommers reach back 6 ancestors,
                        # BLOCKHASH 256 — prune beyond that
                        for d, keep in (
                            (window_headers, 260),
                            (window_headers_full, 8),
                            (window_blocks, 8),
                        ):
                            for n in sorted(d)[:-keep]:
                                del d[n]
            if results_cur:
                seal_and_submit()
            with span("pipeline.stall", kind="final-drain"):
                stalled = drain_pipeline()
            ph["collect"] += stalled
        except BaseException:
            # a driver-side failure (validation, execution, or a
            # re-raised collector failure) aborts the pipeline:
            # queued windows are dropped WITHOUT persisting
            if prefetcher is not None:
                prefetcher.close()
            collector.kill()
            # un-durable overlay state must die with the windows that
            # produced it — reads fall back to the committed store
            # (never a torn window)
            if self.read_view is not None:
                self.read_view.invalidate_above(
                    self.blockchain.best_block_number
                )
            raise
        collector.close()
        if prefetcher is not None:
            prefetcher.close()
            # overlapped sender recovery: background busy time, kept
            # out of the foreground wall-clock phases (like *_bg)
            ph["senders_bg"] += prefetcher.busy_seconds
        # every window is durable: free the last in-flight fused jobs'
        # device buffers (earlier retirees were freed at later seals)
        committer.drain_retired()
        stats.persist_bytes += committer.persist_bytes
        stats.persist_store_seconds += committer.persist_seconds
        stats.seconds = time.perf_counter() - t_start
        # overlap fraction: collector busy seconds NOT spent with the
        # driver blocked on it ((C - stall)/C) — 1.0 means collect+save
        # were fully hidden behind host execution
        stall = ph["collect"] + ph["save"]
        busy = collector.busy_seconds
        occ = (
            max(0.0, min(1.0, (busy - stall) / busy)) if busy > 0 else 0.0
        )
        stats.pipeline_occupancy = occ
        PIPELINE_GAUGES["occupancy"] = round(occ, 4)
        PIPELINE_GAUGES["driver_stall_s"] = round(stall, 3)
        return stats

    def _execute_and_insert(self, block: Block, stats: ReplayStats) -> None:
        header = block.header
        parent = self.blockchain.get_header_by_number(header.number - 1)
        if parent is None:
            raise ValueError(f"no parent for block {header.number}")
        # passport stamps for the per-block import path (live sync,
        # reorg adopt). A replica's tail re-execution runs under
        # use_node("replica:...") and stamps ONLY its own visibility
        # page (serving/replica.py) — ingress/durable belong to the
        # primary plane
        journeys = JOURNEY.enabled and current_node() == "primary"
        if journeys:
            for stx in block.body.transactions:
                JOURNEY.record(stx.hash, "ingress", source="import",
                               block=header.number)
        if self.validate_headers:
            self.header_validator.validate(header, parent)
        BlockValidator.validate_body(block)
        OmmersValidator.validate(
            self.blockchain, block,
            header_validator=(
                self.header_validator if self.validate_headers else None
            ),
        )

        t0 = time.perf_counter()
        result = execute_block(
            block,
            parent.state_root,
            self.blockchain.get_world_state,
            self.config,
            validate=True,
            hasher=self.hasher,  # root check + persist share one flush
        )
        td = (
            self.blockchain.get_total_difficulty(parent.number) or 0
        ) + header.difficulty
        self.blockchain.save_block(
            block, result.receipts, td, result.world, hasher=self.hasher
        )
        if journeys:
            for stx in block.body.transactions:
                JOURNEY.record(stx.hash, "durable",
                               block=header.number)
        dt = time.perf_counter() - t0

        stats.blocks += 1
        stats.txs += result.stats.tx_count
        stats.gas += result.gas_used
        stats.parallel_txs += result.stats.parallel_count
        stats.conflicts += result.stats.conflict_count
        stats.fast_path_txs += result.stats.fast_path_txs
        stats.residue_txs += result.stats.residue_txs
        stats.mispredictions += result.stats.mispredicted_txs

        if self.log is not None:
            # RegularSyncService.scala:429 one-line format
            ntx = result.stats.tx_count
            self.log(
                f"Executed #{header.number} ({block.hash[:4].hex()}) "
                f"{ntx} txs in {dt * 1000:.1f}ms, "
                f"{ntx / dt if dt else 0:.1f} tx/s, "
                f"{result.gas_used / dt / 1e6 if dt else 0:.2f} mgas/s, "
                f"parallel {result.stats.parallel_rate * 100:.0f}%, "
                f"cache hit "
                f"{self.blockchain.storages.account_node_storage.cache_hit_rate * 100:.0f}%"
            )
