"""Driver ``statesync``: the state-download half of fast sync —
``StateSyncer`` as ``FastSyncService.run`` drives it — against an
in-memory peer, into a Kesque store and the device mirror, closed by a
whole-snapshot re-verification on the device.

The window is an operator's resumed fast sync. Untimed set-up puts the
part of the trie the syncer would already have (its first N - remaining
downloads, in its own breadth-first order) into the store and the device
mirror and writes the checkpoint ``FastSyncService`` keeps, whose pending
list is the frontier at that point. The window then runs
``StateSyncer.start``, which resumes from that checkpoint, and ends at
``--seconds`` (the peer raises when time is up; the driver then flushes
the mirror and runs the device verify itself, inside the timed part) or
when the sync completes, whichever is first. Either way the closing
verify runs over a mirror that holds most of the snapshot. The syncer
gets its storages and its mirror through timing proxies, which is how
the per-layer split is taken without touching the program.

The peer keeps a row of clocks some twenty times a window (``SLICES``;
wall, the driver thread's CPU, the process's CPU); the ``window: closed`` line
prints them as rates per slice and artefact ``slices`` hands the rows
over, so that a speed can be seen to hold or to flip inside a window.
Under ``--trace 1`` the program's own tracer is on for the window and
its ring's spans (``fastsync.*``, ``mirror.*``) are handed over as
``spans``, which names the breakdown's idle gaps.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from benchmark.generators import snapshot as gen
from benchmark.lib.outcome import Check, Outcome
from benchmark.reference.keccak import keccak256_batch

# rows of the window's clock: a slice of a twentieth of the window's
# requests (50,000 nodes, ~1.3 s at the cell's size)
SLICES = 20

class Timed:
    """Wraps an object; calls to the named methods are timed and counted
    into ``book[<label>.<method>]``."""

    def __init__(self, inner, label: str, methods, book: Dict):
        self._inner, self._label, self._book = inner, label, book
        for m in methods:
            setattr(self, m, self._wrap(m))

    def _wrap(self, method: str):
        fn = getattr(self._inner, method)
        key = f"{self._label}.{method}"
        self._book.setdefault(key, [0.0, 0, 0])

        def call(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                row = self._book[key]
                row[0] += time.perf_counter() - t0
                row[1] += 1
                if method == "update":
                    row[2] += len(a[1])

        return call

    def __getattr__(self, name):
        return getattr(self._inner, name)


class TimedStorages:
    def __init__(self, storages, book: Dict):
        self._inner = storages
        for name in ("account_node_storage", "storage_node_storage",
                     "evmcode_storage"):
            setattr(self, name, Timed(getattr(storages, name),
                                      f"store.{name}", ["update"], book))

    def __getattr__(self, name):
        return getattr(self._inner, name)


def lying_hasher(claims_of):
    """The control's batch check: answers with the claimed hashes, so a
    forged value passes the per-batch content-address check."""
    def hasher(values):
        return [claims_of[id(v)] for v in values]
    return hasher


def sync_once(storages, mirror, peer, root, batch_size: int, book: Dict,
              env, check_batches: bool = True):
    from khipu_tpu.sync.fast_sync import FastSyncStateStorage, StateSyncer

    fetch = peer.fetch
    hasher = None
    if not check_batches:
        # control `no-batch-check`: a syncer that trusts the peer
        last = {}

        def fetch(hashes, inner=peer.fetch):
            got = inner(hashes)
            last.clear()
            last.update({id(v): h for h, v in got.items()})
            return got

        hasher = lying_hasher(last)
    syncer = StateSyncer(
        TimedStorages(storages, book),
        # where FastSyncService.run keeps its resumable checkpoint
        FastSyncStateStorage(storages.app_state.source),
        fetch, batch_size=batch_size, hasher=hasher,
        mirror=None if mirror is None else Timed(
            mirror, "mirror", ["admit", "flush", "verify"], book),
    )
    try:
        syncer.start(root)
        return True
    except gen.WindowClosed:
        return False
    except Exception as e:
        if check_batches:
            raise
        # the control fed the loop forged nodes: whatever it trips over,
        # the checks after the window have to say "not correct"
        env.log(f"control: sync aborted: {type(e).__name__}: {str(e)[:120]}")
        return False


def make_mirror(rows_by_class: Dict[int, int]):
    """A ``DeviceNodeMirror`` whose size classes (rate blocks of 136 B)
    each have their own capacity. The program takes one capacity for all
    classes (PERF.md, open questions), so each class is built under the
    capacity meant for it."""
    from khipu_tpu.storage.device_mirror import DeviceNodeMirror

    mirror = DeviceNodeMirror(
        capacity_rows_per_class=max(rows_by_class.values()))
    roomiest = mirror.capacity
    for nb, rows in sorted(rows_by_class.items()):
        mirror.capacity = rows
        mirror._class(nb)
    mirror.capacity = roomiest
    return mirror


def prefill(env, src: gen.Source, done: int, storages, mirror) -> None:
    """What the syncer's first ``done`` downloads left behind: every one
    of those nodes in the store and resident on the device. Whole tiles
    go up packed, a class's last rows through ``admit`` + ``flush``."""
    from khipu_tpu.storage.device_mirror import RATE, TILE

    t0 = time.perf_counter()
    keys = src.keys(0, done)
    blob = src.blob.tobytes()
    starts = src.starts[:done].tolist()
    ends = (src.starts[:done] + src.lens[:done]).tolist()
    for lo in range(0, done, 65536):
        storages.account_node_storage.update([], {
            keys[i]: blob[starts[i]:ends[i]]
            for i in range(lo, min(lo + 65536, done))})
    t1 = time.perf_counter()
    nblocks = src.lens[:done] // RATE + 1
    for nb in np.unique(nblocks).tolist():
        idx = np.nonzero(nblocks == nb)[0]
        whole = len(idx) // TILE * TILE
        for lo in range(0, whole, 64 * TILE):
            part = idx[lo:min(lo + 64 * TILE, whole)]
            mirror.admit_packed(
                [keys[i] for i in part.tolist()],
                src.padded_rows(part, nb * RATE),
                lengths=src.lens[part].tolist())
        mirror.admit({keys[i]: blob[starts[i]:ends[i]]
                      for i in idx[whole:].tolist()})
    mirror.flush()
    env.log(f"resume: {done} nodes into the store {t1 - t0:.1f} s, "
            f"into the mirror {time.perf_counter() - t1:.1f} s")


def run(env) -> Outcome:
    from khipu_tpu.config import SyncConfig

    sizes = env.config["sizes"]
    accounts = int(sizes["accounts"])
    batch_size = int(sizes.get("nodes_per_request")
                     or SyncConfig().nodes_per_request)
    rows = {int(nb): int(n) for nb, n in sizes["mirror_rows"].items()}
    cache_file = os.path.join(env.cache_dir, f"{env.seed}-{accounts}.order.npz")
    builder = None
    if not os.path.exists(cache_file):
        # a new seed: a child process (JAX held to the CPU there) builds
        # the source trie while this one compiles the mirror's programs
        env.log(f"seed: building source trie ({accounts} accounts) in a child")
        builder = subprocess.Popen(
            [sys.executable, os.path.abspath(gen.__file__), json.dumps(
                {"accounts": accounts, "seed": env.seed, "out": cache_file})],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=subprocess.DEVNULL)
    try:
        return _run(env, builder, cache_file, rows, batch_size)
    finally:
        if builder is not None and builder.poll() is None:
            builder.kill()
            builder.wait()


def _run(env, builder, cache_file: str, rows: Dict[int, int],
         batch_size: int) -> Outcome:
    import jax

    from khipu_tpu.observability.trace import tracer as program_tracer
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.fast_sync import (
        STATE_NODE, FastSyncStateStorage, SyncState)

    traffic = env.traffic
    # ---- warm-up: one synthetic node of every size class through a
    # mirror of the window's shape (the programs are compiled per
    # capacity: install, partial-tile hash, verify), then freed; and a
    # small separate trie through the download loop, which compiles
    # nothing and has no mirror
    filler = [bytes([7 + nb]) * (136 * nb - 36) for nb in sorted(rows)]
    env.log(f"warm-up: mirror rows per class {rows}")
    warm_mirror = make_mirror(rows)
    warm_mirror.admit(dict(zip(keccak256_batch(filler), filler)))
    warm_mirror.flush()
    if warm_mirror.verify():
        raise RuntimeError("warm-up: the mirror's own filler fails verify")
    del warm_mirror
    gc.collect()
    warm_root, warm_nodes = gen.build_source(
        int(traffic["warmup_accounts"]), env.seed + 1)
    warm_store = Storages(engine="kesque",
                          data_dir=os.path.join(env.run_dir, "warm"))
    sync_once(warm_store, None, gen.Peer(
        warm_nodes, env.seed, int(traffic["forge_one_in"])), warm_root,
        batch_size, {}, env)
    warm_store.stop()
    if builder is not None:
        env.log("seed: waiting for the source builder")
        if builder.wait() != 0:
            raise RuntimeError(f"source builder exited {builder.returncode}")
    src = gen.load_source(cache_file)
    done, pending_end = src.resume_point(int(traffic["resume_remaining_nodes"]))
    env.log(f"source: {len(src)} nodes, {len(src.blob)} bytes; resuming "
            f"after {done} with {pending_end - done} pending")

    storages = Storages(engine="kesque",
                        data_dir=os.path.join(env.run_dir, "store"))
    mirror = make_mirror(rows)
    prefill(env, src, done, storages, mirror)
    resident0 = mirror.resident_count
    bad0 = mirror.verify()
    FastSyncStateStorage(storages.app_state.source).put_sync_state(SyncState(
        target_root=src.root, downloaded_nodes=done,
        pending=[(STATE_NODE, h) for h in src.keys(done, pending_end)]))
    peer = gen.Peer(src.nodes(), env.seed, int(traffic["forge_one_in"]))
    peer.slice_requests = max(1, (len(src) - done) // (SLICES * batch_size))
    root = src.root
    book: Dict[str, List] = {}
    gc.collect()
    tw = env.trace_window() if env.trace else None
    if tw:
        # the program's span ring, sized to hold the whole window: a
        # batch opens seven spans, a tile and a checkpoint a few more
        # (142 k spans for 20,000 batches: my chip run, PR 41)
        program_tracer.enable(capacity=max(
            program_tracer.DEFAULT_CAPACITY,
            10 * (len(src) - done) // batch_size + 4096))
        program_tracer.reset()
        tw.start()
    # ------------------------------------------------------ the window
    env.log("window: open")
    setup_s = time.perf_counter() - env.t_proc0
    t_open = time.perf_counter()
    peer.deadline = t_open + env.seconds
    peer.mark(t_open)
    complete = sync_once(
        storages, mirror, peer, root, batch_size, book, env,
        check_batches=env.control != "no-batch-check")
    t_loop = time.perf_counter()
    peer.mark(t_loop)
    bad = 0
    if not complete:  # a completed sync has flushed and verified itself
        tm = Timed(mirror, "mirror", ["flush", "verify"], book)
        tm.flush()
        bad = tm.verify()
    verify_s = book["mirror.verify"][0]
    t_close = time.perf_counter()
    spans, spans_dropped = [], 0
    if tw:
        tw.stop()
        program_tracer.disable()
        spans, spans_dropped = program_tracer.snapshot(), program_tracer.dropped
        env.log(f"span ring: {len(spans)} kept, {spans_dropped} dropped")
    stored = book.get("store.account_node_storage.update", [0, 0, 0])[2]
    resident = mirror.resident_count - resident0
    env.log(f"window: closed after {t_close - t_open:.3f} s "
            f"(loop {t_loop - t_open:.3f} s), {stored} nodes stored, "
            f"{resident} more resident ({mirror.resident_count} in all), "
            f"complete={complete}; slices, nodes a wall second / a "
            "thread-CPU second / other threads' CPU seconds (the last: "
            "what was left, with a completed sync's flush and verify): "
            + " ".join(f"{n / w:.0f}/{n / c:.0f}/{o:.3f}"
                       for n, w, c, o in slice_rates(peer.slices)))

    # ------------------------------------ after the window: the checks
    rng = np.random.default_rng([env.seed, 0x736E6368])
    store = storages.account_node_storage
    forged_stored = sum(
        1 for h, v in peer.forged.items() if store.get(h) == v)
    forged_resident = sum(
        1 for h, v in peer.forged.items() if mirror.get(h) == v)
    handed = sorted(peer.truthful)
    sample = [handed[int(i)] for i in rng.choice(
        len(handed), min(int(traffic["sample"]), len(handed)),
        replace=False)] if handed else []
    values = [store.get(h) for h in sample]
    missing_store = sum(v is None for v in values)
    digests = keccak256_batch([v or b"" for v in values])
    rehash_bad = sum(d != h for d, h, v in zip(digests, sample, values)
                     if v is not None)
    missing_mirror = sum(not mirror.contains(h) for h in sample[:256])
    checks = [
        Check("resumed_nodes_not_resident", done - resident0, 0),
        Check("resumed_nodes_failing_device_verify", bad0, 0),
        Check("device_verify_mismatches", bad, 0),
        Check("forged_values_stored", forged_stored, 0),
        Check("forged_values_resident", forged_resident, 0),
        Check("truthful_nodes_not_stored", len(peer.truthful) - stored
              if not env.control else 0, 0),
        Check("stored_minus_resident", abs(stored - resident), 0),
        Check("sampled_values_missing_from_store", missing_store, 0),
        Check("sampled_values_missing_from_mirror", missing_mirror, 0),
        Check("sampled_rehash_mismatches_of_%d" % len(sample), rehash_bad, 0),
    ]
    # one forged claim planted after the window counts exactly 1
    import jax.numpy as jnp

    cm = next(iter(mirror._classes.values()))
    poisoned = cm.claimed.at[0, 0, 0, 0].add(jnp.uint32(1))
    planted = int(jax.device_get(cm._verify(cm.resident, poisoned)))
    checks.append(Check("planted_forgery_miscounted", abs(planted - 1), 0))
    if stored == 0:
        checks.append(Check("nothing_synced", 1, 0))

    window_s = t_close - t_open
    e2e = {"setup_s": setup_s,
           "snap_nodes_per_s": min(stored, resident) / window_s}
    art = {
        "window": (t_open, t_close), "nodes": stored, "timers": book,
        "loop_s": t_loop - t_open, "window_s": window_s,
        "fetch_s": peer.seconds, "verify_ms": 1000.0 * verify_s,
        "resident_bytes": resident_bytes(mirror), "trace": tw,
        "spans": [s for s in spans if s.t1 > t_open and s.t0 < t_close],
        "spans_dropped": spans_dropped, "slices": peer.slices,
        "forged_sent": len(peer.forged),
        "requests": peer.requests, "complete": complete,
    }
    attempted = len(peer.truthful) + len(peer.forged)
    failed = attempted - stored - len(peer.forged) if not env.control else 0
    storages.stop()
    return Outcome(e2e, checks, attempted, max(0, failed), art)


def slice_rates(rows) -> List[Tuple[int, float, float, float]]:
    """``Peer.mark`` rows as slices: (nodes handed over, wall seconds,
    the driver thread's CPU seconds, the other threads' CPU seconds =
    the process's minus the thread's) between each row and the next.
    A slice in which no node came (a clock that did not move) is left
    out."""
    out = []
    for a, b in zip(rows, rows[1:]):
        nodes, wall, cpu = b[4] - a[4], b[0] - a[0], b[1] - a[1]
        if nodes > 0 and wall > 0 and cpu > 0:
            out.append((nodes, wall, cpu, (b[2] - a[2]) - cpu))
    return out


def resident_bytes(mirror) -> int:
    """Bytes of the resident nodes a whole-mirror verify reads: every
    class's resident rows times its row width, plus their 32 B claims.
    The verify also reads the unfilled rest of each class (filler rows);
    those bytes are not counted, so the share is the nodes' own."""
    return sum(cm.count * (cm.width + 32)
               for cm in mirror._classes.values())
