"""Fused window finalize: the whole placeholder DAG in ONE device dispatch.

The level-synchronous finalize (deferred.finalize) issues one batched
hash call per trie level — O(levels) dispatches per window, each a
blocking host<->device round trip around a kernel that runs for
microseconds.

This module replaces the level loop with a FIXPOINT iteration compiled
into a single XLA program:

  1. Host packs every staged node's raw encoding (multi-rate padded)
     into per-rate-class u8 buffers and scans each encoding once for
     its placeholder spans -> static substitution triples
     (parent_row, byte_offset, child_index).
  2. One jitted program runs `depth` rounds of
         digests = keccak(all nodes)        # pallas, per class
         encodings[parent, off:off+32] = digests[child]
     After k rounds every node within k levels of the leaves carries
     its final digest — after `depth` rounds all do. The second line
     is no scatter: a row gathers its children's digests side by side
     and shifts them to their offsets (subst_plan / substitute).

Substitution is length-invariant (a placeholder is exactly 32 bytes,
replaced by a 32-byte hash; RLP headers never change — the same
invariant the host substitution relies on), so byte offsets recorded
from the RAW encodings stay valid through every round.

The extra compute (depth x N hashes instead of N) buys one dispatch per
window instead of one per level; whether that trade pays on a given
backend is what sync/adaptive.py measures.

Shapes are bucketed ({1,2,4,8,16} tiles per class, pow-2 substitution
counts, pow-2 depth) so a handful of compiled variants serves every
window.
"""

from __future__ import annotations

import re
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from khipu_tpu.device import named_jit
from khipu_tpu.observability.profiler import D2H, H2D, HOST, LEDGER
from khipu_tpu.observability.recorder import compile_log
from khipu_tpu.observability.registry import REGISTRY
from khipu_tpu.observability.trace import span as _span
from khipu_tpu.ops.keccak_jnp import RATE

MAX_DEPTH = 64  # DAG deeper than this falls back to the level loop

EXT_FLOOR = 64  # min padded rows of the resolved-input tile (pow-2
# bucketing keeps windows with 0..64 cross-refs in ONE compiled shape)

FUSED_GAUGES = REGISTRY.gauge_group("khipu_fused", {
    # dispatches that could not start the eager d2h digest copy (the
    # backend lacks copy_to_host_async) — collect() pays the fetch
    "async_copy_fallbacks": 0,
}, help="fused-dispatch capability state (trie/fused.py)")

# what the fixpoint program is asked to do, always on: one counter add
# per class per dispatch (khipu_fused_hashed_rows_total{nblocks=}) and
# these two. hashed rows = padded rows x rounds (every round
# re-hashes every row of every class); live nodes = the real dirty
# nodes those rows carry
FUSED_LIVE_NODES = REGISTRY.counter(
    "khipu_fused_live_nodes_total",
    help="dirty nodes resolved by fused dispatches (trie/fused.py)")
FUSED_DISPATCHES = REGISTRY.counter(
    "khipu_fused_dispatches_total",
    help="fused fixpoint dispatches (trie/fused.py)")

# per-backend-platform capability: does the runtime support
# copy_to_host_async? Probed on the FIRST dispatch, cached for the
# process — every later window short-circuits instead of paying (and
# silently swallowing) an exception per dispatch
_ASYNC_COPY_SUPPORT: Dict[str, bool] = {}


def _start_async_copy(arr) -> None:
    """Begin streaming ``arr`` device->host so a later blocking fetch
    finds the bytes already on the host. Capability-gated per
    backend: unsupported backends count a gauge instead of raising
    (InjectedDeath is a BaseException and propagates — KL002)."""
    from khipu_tpu import device

    platform = device.platform()
    ok = _ASYNC_COPY_SUPPORT.get(platform)
    if ok is False:
        FUSED_GAUGES["async_copy_fallbacks"] += 1
        return
    try:
        arr.copy_to_host_async()
    except Exception:
        _ASYNC_COPY_SUPPORT[platform] = False
        FUSED_GAUGES["async_copy_fallbacks"] += 1
        return
    if ok is None:
        _ASYNC_COPY_SUPPORT[platform] = True


class FusedUnsupported(Exception):
    """Raised when the fused path cannot handle this window (the caller
    falls back to the per-level hasher loop)."""


def topo_levels(deps: Dict[bytes, List[bytes]]) -> List[List[bytes]]:
    """Topological levels of the dependency DAG, leaves first. The ONE
    implementation of level detection — deferred.finalize's hashing loop
    and the fused fixpoint both consume it. Raises AssertionError on a
    cycle / unresolvable reference."""
    done: set = set()
    pending = dict(deps)
    levels: List[List[bytes]] = []
    while pending:
        level = [
            ph for ph, cs in pending.items()
            if all(c in done for c in cs)
        ]
        if not level:
            raise AssertionError("placeholder dependency cycle")
        for ph in level:
            done.add(ph)
            del pending[ph]
        levels.append(level)
    return levels


def _pow2(n: int, floor: int = 1) -> int:
    v = floor
    while v < n:
        v *= 2
    return v


SITE = 32  # bytes of a placeholder, and of the digest that replaces it


def sorted_sites(row: np.ndarray, off: np.ndarray, child: np.ndarray,
                 width: int):
    """One class's substitutions ordered by ``(row, off)``, which is
    what :func:`subst_plan` counts a row's sites by. Sites are 32 bytes
    that do not overlap and lie inside their node's encoding (a
    placeholder was written as one ref inside one node, and
    ``find_sites`` starts its next search 32 bytes on), so in that order
    their flat positions are strictly increasing and 32 apart or more:
    checked here in one ``diff``, because a site that broke it would be
    dropped or misplaced on the device without a word."""
    key = row * width + off
    order = np.argsort(key, kind="stable")  # O(n) on what is sorted
    key = key[order]
    if key.size and (
        (np.diff(key) < SITE).any()
        or off.min() < 0 or off.max() + SITE >= width
    ):
        raise FusedUnsupported("substitution sites overlap or leave their row")
    return row[order], off[order], child[order]


def subst_plan(nb: int, nrows: int, rows, offs, child):
    """Everything of one class's substitution that no round changes,
    built on the device ONCE per dispatch, before the loop.

    ``rows``, ``offs``, ``child`` are i32[nsubs] in ``(row, off)`` order
    (:func:`sorted_sites`); a padding entry has ``rows == nrows``, out
    of range, and is dropped by the two table scatters, the only ones
    there are: it costs index space and no write. A row of ``width`` bytes has room for at most
    ``slots = (width - 1) // 32`` sites, so the class's children fit a
    table ``[nrows, slots]``: a site's slot is its rank among its row's.
    A round then gathers whole digests by that table into
    ``[nrows, slots * 32]`` (the digests of a row side by side, in
    offset order) and moves byte ``32 * slot + i`` to ``off + i``. The
    shift ``off - 32 * slot`` never decreases along a row, so the move
    is an EXPAND network: one stage per bit of the shift, highest bit
    first, each a shift of the whole buffer by ``2**k`` bytes and a
    select; no two bytes ever meet on the way (a byte further along
    starts further along and has moved at least as far). Stage ``k``'s
    mask lives where its bytes ARRIVE, which is found by running the
    network backwards over the shifts themselves, lowest bit first.

    Returns ``(table, covered, masks)`` for :func:`substitute`."""
    import jax
    import jax.numpy as jnp

    width = nb * RATE
    slots = (width - 1) // SITE
    with jax.named_scope("fused.subst"), jax.named_scope(f"c{nb}"):
        idx = jnp.arange(rows.shape[0], dtype=jnp.int32)
        first = jnp.concatenate(
            [jnp.ones((1,), bool), rows[1:] != rows[:-1]])
        slot = idx - jax.lax.cummax(jnp.where(first, idx, 0))
        at = jnp.stack([rows, slot], axis=1)
        dnums = jax.lax.ScatterDimensionNumbers(
            update_window_dims=(), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1))

        def by_slot(vals, fill):  # [nrows, slots]; padding is dropped
            return jax.lax.scatter(
                jnp.full((nrows, slots), fill, jnp.int32), at, vals,
                dnums, indices_are_sorted=True, unique_indices=True,
                mode="drop")

        table = by_slot(child, 0)
        start = by_slot(offs, width)  # no site: starts past every byte
        # per byte: how many of the row's sites start at or before it,
        # and where the last of them starts
        b = jnp.arange(width, dtype=jnp.int32)[None, :]
        rank = jnp.zeros((nrows, width), jnp.int32)
        site = jnp.full((nrows, width), -SITE, jnp.int32)
        for s in range(slots):
            o = start[:, s, None]
            begun = o <= b
            rank = rank + begun
            site = jnp.where(begun, o, site)  # starts increase along s
        covered = b - site < SITE
        shift = jnp.where(covered, site - SITE * (rank - 1), 0)
        masks = []
        here = covered  # a byte on its way is at this position
        for k in range(max(width - SITE - 1, 1).bit_length()):
            moves = here & ((shift >> k) & 1).astype(bool)
            masks.append(moves)
            came = _shifted(moves, -(1 << k))
            shift = jnp.where(came, _shifted(shift, -(1 << k)), shift)
            here = came | (here & ~moves)
        return table, covered, tuple(masks)


def _shifted(x, by: int):
    """``x`` moved ``by`` positions along its rows (towards the end for
    ``by > 0``), zeros coming in."""
    import jax.numpy as jnp

    if by > 0:
        return jnp.pad(x[:, :-by], ((0, 0), (by, 0)))
    return jnp.pad(x[:, -by:], ((0, 0), (0, -by)))


def substitute(enc, plan, digests):
    """One round of one class: ``enc`` u8[nrows, width] with every site
    of ``plan`` (:func:`subst_plan`) taking its child's row of
    ``digests`` u8[n, 32]. Bytes outside a site, a padding row's among
    them, pass through untouched."""
    import jax
    import jax.numpy as jnp

    table, covered, masks = plan
    nrows, width = enc.shape
    with jax.named_scope("fused.gather"):
        vals = digests[table]  # [nrows, slots, 32] u8
    with jax.named_scope("fused.subst"), \
            jax.named_scope(f"c{width // RATE}"):
        moved = vals.reshape(nrows, -1)
        moved = jnp.pad(moved, ((0, 0), (0, width - moved.shape[1])))
        for k in reversed(range(len(masks))):
            moved = jnp.where(masks[k], _shifted(moved, 1 << k), moved)
        return jnp.where(covered, moved, enc)


# how many windows a held bucket outlives the last window that needed
# it (HeldBuckets). Long against a compile (a signature first met costs
# tens of seconds, ~100 windows' worth of wall), short against a node's
# life: one outlier window pads every later one for ~10-25 minutes of
# sync, not for good
HOLD_WINDOWS = 1024
# the resolved-input tile never takes fewer rows than this (256 KiB of
# digests a dispatch): how many windows are in flight when one is packed
# (none, one, two) is the pipeline's timing and not the workload's
# shape, and under that many rows it moves no signature
EXT_HELD_ROWS = 8192


class HeldBuckets:
    """The fused program's buckets as one owner's earlier windows left
    them. A count of the signature (a class's rows, its substitutions,
    the ext tile's rows) takes the largest bucket any of the owner's
    last ``HOLD_WINDOWS`` windows needed, not its own: a count that sits
    on a bucket's edge would otherwise flip the signature from window to
    window, and several counts that flip independently meet new
    combinations, each a compile of seconds, long after warm-up. Held, a
    dimension changes only when a window outgrows everything in memory,
    or when nothing has needed the held bucket for ``HOLD_WINDOWS``
    windows: it then falls to the largest those windows did need (a
    signature the owner has most likely compiled before).

    The owner is whoever dispatches window after window: the replay
    driver keeps one for its node, a ``WindowCommitter`` built without
    one keeps its own, a lone ``fused_submit`` starts a new one. Each
    change is served as ``khipu_fused_held_bucket{dim=}`` (the newest
    writer's value, like the pipeline gauges)."""

    def __init__(self):
        # dim -> [held, largest asked since `held` was last needed,
        #         askings since then]
        self._dims: Dict[tuple, list] = {}
        self.take(("ext",), EXT_HELD_ROWS)

    def take(self, dim: tuple, bucket: int) -> int:
        """The bucket to use in ``dim`` for a window that needs
        ``bucket``."""
        if dim == ("ext",):
            bucket = max(bucket, EXT_HELD_ROWS)
        rec = self._dims.get(dim)
        if rec is not None and bucket < rec[0]:
            rec[1] = max(rec[1], bucket)
            rec[2] += 1
            if rec[2] < HOLD_WINDOWS:
                return rec[0]
            bucket = rec[1]
        if rec is None or bucket != rec[0]:
            REGISTRY.gauge(
                "khipu_fused_held_bucket",
                help="rows or substitutions the fused program is "
                     "compiled for in this dimension of its signature "
                     "(trie/fused.py HeldBuckets)",
                labels={"dim": ".".join(str(d) for d in dim)},
            ).set(bucket)
        self._dims[dim] = [bucket, 0, 0]
        return bucket

    def snapshot(self) -> Dict[tuple, int]:
        return {dim: rec[0] for dim, rec in self._dims.items()}


class _CompileCache:
    """Bounded LRU over compiled fixpoint programs, keyed by the full
    shape signature (per-class (nblocks, nrows, nsubs), rounds, backend,
    ext-tile rows). Replaces the blind ``functools.lru_cache``: every
    access lands in the observability compile-event log (hit /
    miss+compile-seconds / eviction — recorder.compile_log), which is
    what ROADMAP's "watch compile-cache pressure on very long sessions"
    actually watches. Coarse pow-2 bucketing upstream keeps steady
    state at a handful of signatures; a session whose organic shapes
    churn past ``capacity`` now evicts LRU (and says so) instead of
    growing without bound."""

    def __init__(self, builder, capacity: int = 64):
        self._builder = builder
        self._capacity = max(1, capacity)
        self._od: "OrderedDict[tuple, object]" = OrderedDict()
        self._scopes: Dict[tuple, Dict] = {}  # scope_map memo
        self._lock = threading.Lock()

    @staticmethod
    def _label(key: tuple) -> str:
        sig, rounds, use_jnp, ext_rows = key
        classes = ",".join(
            f"{s[0]}x{s[1]}/{s[2]}+a{s[3] if len(s) > 3 else 0}"
            for s in sig
        )
        return (
            f"classes=[{classes}] rounds={rounds} "
            f"backend={'jnp' if use_jnp else 'pallas'} ext={ext_rows}"
        )

    def __call__(self, sig, rounds, use_jnp, ext_rows=0):
        return self.lookup(sig, rounds, use_jnp, ext_rows)[0]

    def lookup(self, sig, rounds, use_jnp, ext_rows=0):
        """``(program, compile_seconds)`` — seconds is 0.0 on a hit and
        the builder's wall time (trace + lower + XLA compile, or the
        persistent-cache load) on a miss, so the caller can tell a
        dispatch that compiled from one that did not."""
        key = (sig, rounds, use_jnp, ext_rows)
        with self._lock:
            run = self._od.get(key)
            if run is not None:
                self._od.move_to_end(key)
                compile_log.record("hit", self._label(key))
                return run, 0.0
        # build OUTSIDE the lock: an XLA compile takes seconds and must
        # not block a concurrent hit; a racing duplicate compile is
        # wasted work, not an error (first insert wins)
        t0 = time.perf_counter()
        with _span("fused.compile", signature=self._label(key)):
            run = self._builder(sig, rounds, use_jnp, ext_rows)
        dt = time.perf_counter() - t0
        with self._lock:
            if key in self._od:
                return self._od[key], dt
            compile_log.record("miss", self._label(key), dt)
            self._od[key] = run
            self._evict_over_capacity()
        return run, dt

    def _evict_over_capacity(self) -> None:  # lock held
        while len(self._od) > self._capacity:
            old_key, _ = self._od.popitem(last=False)
            self._scopes.pop(old_key, None)
            compile_log.record("evict", self._label(old_key))

    def set_capacity(self, capacity: int) -> None:
        with self._lock:
            self._capacity = max(1, capacity)
            self._evict_over_capacity()

    def clear(self) -> None:
        with self._lock:
            self._od.clear()
            self._scopes.clear()

    def scope_map(self) -> Dict[str, Dict[str, Optional[str]]]:
        """See :func:`scope_map`."""
        with self._lock:
            programs = list(self._od.items())
        out = {}
        for key, run in programs:
            scopes = self._scopes.get(key)
            if scopes is None:
                # outside the lock: the text of a Pallas-backed program
                # is megabytes (the kernels' bodies ride in it)
                scopes = _scopes_of(run.as_text())
                with self._lock:
                    if key in self._od:
                        self._scopes[key] = scopes
            out[self._label(key)] = scopes
        return out

    def stats(self) -> dict:
        with self._lock:
            return {"size": len(self._od), "capacity": self._capacity}


_SCOPE = re.compile(r"fused\.\w+")
_REF = re.compile(r"%[\w.\-]+")


def _scopes_of(hlo_text: str) -> Dict[str, Optional[str]]:
    """{instruction name: ``fused.*`` stage, or None} for every
    instruction in a compiled program's text. The names are the ones a
    profiler trace prints (``%fusion.12``, here without the ``%``). An
    instruction's stage is the first ``fused.*`` scope in its own
    ``op_name``. The TPU compiler gives the fusions it makes of a
    scatter no metadata, though the instructions fused into them keep
    theirs: a fusion without metadata is the one stage found in the
    computation it calls. Whatever else has none (the ``sort`` a
    scatter also becomes, layout copies) stays None: nothing is
    inferred from what an instruction reads."""
    out: Dict[str, Optional[str]] = {}
    inside: Dict[str, set] = {}  # computation -> stages named in it
    comp = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):  # a computation opens or closes
            head = line.split()
            comp = (head[1] if head[0] == "ENTRY" else head[0]) \
                if line.endswith("{") and head else None
            continue
        name, eq, rest = line.partition(" = ")
        if not eq:
            continue
        # the attributes end where backend_config starts (a Mosaic
        # call's is megabytes: the kernel's body rides in it)
        cut = rest.find("backend_config=")
        rest = rest[:cut] if cut >= 0 else rest
        at = rest.find('op_name="')
        own = _SCOPE.search(
            rest, at, rest.find('"', at + 9)) if at >= 0 else None
        if own is not None:
            scope = own.group()
            inside.setdefault(comp, set()).add(scope)
        else:  # callees precede their callers in the text
            called = set().union(
                *(inside.get(r, ()) for r in _REF.findall(rest)))
            scope = called.pop() if len(called) == 1 else None
        out[name.split()[-1].lstrip("%")] = scope
    return out


def _build_fused_impl(sig: Tuple[Tuple[int, int, int, int], ...],
                      rounds: int, use_jnp: bool, ext_rows: int = 0):
    """Compile the fixpoint program for a shape signature — EAGERLY
    (``.lower(...).compile()`` on the signature's shapes), so the
    seconds land in the compile log here and never inside the first
    window's ``seal.upload`` or the adaptive controller's sample.

    sig: per class (nblocks, nrows, nsubs, nadmit), nrows % TILE == 0.
    Inputs: for each class, enc u8[nrows, nblocks*RATE]; then for each
    class rows i32[nsubs], offs i32[nsubs], child i32[nsubs] in
    (row, off) order, padding last with rows == nrows (subst_plan
    turns them into the loop's tables ON DEVICE, before the loop);
    then ext u8[ext_rows, 32] — RESOLVED-INPUT TILES: final digests
    of a previous (possibly still in-flight) window's nodes, consumed
    device-to-device so cross-window placeholder refs resolve without
    a host round-trip (the deep-pipeline seam — ledger/window.seal);
    finally, for each class, aidx i32[nadmit] — the MIRROR-ADMIT rows:
    indices of the class's live nodes, whose final encodings and
    digests are gathered INSIDE this program (the admit gather that
    used to be a separate post-collect d2d pass per window rides the
    dispatch itself — ledger/window.admit_mirror fast path).
    Output: concatenated digests u8[sum nrows, 32], the per-class
    FINAL substituted encodings (still on device) — the payload the
    device-resident commit admits into the store's mirror without any
    node bytes leaving the device (docs/window_pipeline.md) — and the
    per-class admit gathers (enc u8[nadmit, width], claim
    u8[nadmit, 32]; None for classes with nadmit == 0).

    Substitution child indices address the concatenated [G; ext] digest
    space: this window's rows first (class-major), then the ext rows —
    one gather serves both intra-window fixpoint refs and cross-window
    final refs.

    ``use_jnp``: hash via the jnp sponge (XLA-compiled, the CPU/test
    path) instead of the Pallas kernel (TPU) — pallas interpret mode is
    orders of magnitude too slow for a fixpoint loop.
    """
    import jax
    import jax.numpy as jnp

    # legacy 3-tuple signatures (no admit fold) normalize to nadmit=0
    sig = tuple(s if len(s) > 3 else (*s, 0) for s in sig)
    if use_jnp:
        from khipu_tpu.ops.keccak_jnp import hash_padded_u8

        def _mk_runner(nb):
            return lambda padded_u8: hash_padded_u8(padded_u8, nb)

        runners = [_mk_runner(nb) for nb, _, _, _ in sig]
    else:
        from khipu_tpu.ops.keccak_pallas import _build_from_bytes

        runners = [_build_from_bytes(nb, False) for nb, _, _, _ in sig]
    k = len(sig)

    # named_scope puts its name into each instruction's op_name in the
    # compiled program's text; scope_map() hands that out so that a
    # trace reader can split the loop body's device time by stage
    def fused_fixpoint(*args):
        encs = list(args[:k])
        subs = args[k : 4 * k]
        ext = args[4 * k]  # u8[ext_rows, 32] resolved-input tiles
        aidx = args[4 * k + 1 : 4 * k + 1 + k]  # per-class admit rows
        trips = args[5 * k + 1]  # i32[]: this window's DAG depth

        def hash_all(encs):
            with jax.named_scope("fused.hash"):
                return jnp.concatenate(
                    [runners[c](encs[c]) for c in range(k)], axis=0
                )  # [sum rows, 32] u8 — ONE output array, one fetch

        # what no round changes, once, before the loop
        plans = [
            subst_plan(sig[c][0], sig[c][1], *subs[3 * c : 3 * c + 3])
            for c in range(k)
        ]

        def body(_, carry):
            encs, _ = carry
            G = hash_all(encs)
            with jax.named_scope("fused.gather"):
                Gf = jnp.concatenate([G, ext], axis=0)
            return [
                substitute(encs[c], plans[c], Gf) for c in range(k)
            ], G

        # the carried digests are write-only inside the loop (each
        # round recomputes G), so the initial value is never read:
        # zeros, not a hash pass — hashing here traced and lowered
        # every class's unrolled sponge a second time per program
        total_rows = sum(s[1] for s in sig)
        encs, digs = jax.lax.fori_loop(
            0, jnp.minimum(trips, rounds), body,
            (encs, jnp.zeros((total_rows, 32), jnp.uint8)),
        )
        # trips >= depth, so both digs (= hash of the encodings after
        # trips-1 substitution passes) and encs (trips passes) are at
        # the fixpoint: encs carry only real child digests and
        # keccak(encs[c][r]) == digs row r of class c
        #
        # fold the mirror-admit gather into THIS program: live-row
        # encodings and their claimed digests come out pre-gathered, so
        # admit_mirror issues zero extra device work per window
        admit = []
        gbase = 0
        with jax.named_scope("fused.admit"):
            for c in range(k):
                nadmit = sig[c][3]
                if nadmit:
                    admit.append(
                        (encs[c][aidx[c]], digs[gbase + aidx[c]]))
                else:
                    admit.append(None)
                gbase += sig[c][1]
        return digs, encs, admit

    u8 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint8)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    shapes = [u8(nrows, nb * RATE) for nb, nrows, _, _ in sig]
    for _, _, nsubs, _ in sig:
        shapes += [i32(nsubs)] * 3
    shapes.append(u8(ext_rows, 32))
    shapes += [i32(nadmit) for _, _, _, nadmit in sig]
    shapes.append(i32())
    return named_jit("fused_fixpoint", fused_fixpoint).lower(
        *shapes).compile()


# the bounded, instrumented successor of `lru_cache(maxsize=64)`;
# capacity follows ObservabilityConfig.compile_cache_capacity
# (observability.trace.apply_config calls set_capacity)
_build_fused = _CompileCache(_build_fused_impl)
compile_cache = _build_fused  # public handle: stats() / set_capacity()


def scope_map() -> Dict[str, Dict[str, Optional[str]]]:
    """For every program in the compile cache, by its signature label:
    ``{instruction name: "fused.hash" | "fused.gather" | "fused.subst"
    | "fused.admit" | None}`` — the stage of the fixpoint program each
    compiled instruction came from (None: none could be told), read
    out of the executable's own text. A profiler trace names device
    events by instruction and carries no scope; a reader joins the two
    by name, and tells which program a dispatch ran by the names it
    executed. Parsed on the first call after a program was compiled
    and kept with its cache entry; never on the compile or dispatch
    path."""
    return _build_fused.scope_map()


_ROW_GATHER = None


def _take_rows(table, rows: np.ndarray):
    """``table[rows]`` device-to-device through ONE jitted program per
    (table shape, row count). Eager ``table[rows]`` compiles a handful
    of small index-normalisation programs per distinct shape — on a
    chip ~0.5 s per window whose row count differs from the last."""
    global _ROW_GATHER
    if _ROW_GATHER is None:
        _ROW_GATHER = named_jit("row_gather", lambda t, r: t[r])
    return _ROW_GATHER(table, rows)


def gather_ext_tile(sources, held: HeldBuckets) -> Tuple[object, List[int]]:
    """Build the resolved-input tile for ``fused_submit`` from
    ``[(digest_table, row_indices), ...]``: each part is gathered
    device-to-device with its row list padded to a multiple of
    EXT_FLOOR (padding repeats row 0) and the whole tile to a pow-2
    row count, so the gather programs come from a small set of shapes
    and the tile's row count — part of the fused program's signature
    — does not follow each window's exact cross-ref count, nor (it is
    one of ``held``'s dimensions) how many windows happened to be in
    flight.
    Returns ``(tile u8[n, 32], part offsets)``: part ``i``'s real rows
    start at ``offsets[i]``."""
    import jax.numpy as jnp

    parts, offsets, total = [], [], 0
    for table, rows in sources:
        padded = np.zeros(
            -(-len(rows) // EXT_FLOOR) * EXT_FLOOR, dtype=np.int32
        )
        padded[: len(rows)] = rows
        parts.append(_take_rows(table, padded))
        offsets.append(total)
        total += len(padded)
    whole = held.take(("ext",), _pow2(total, floor=EXT_FLOOR))
    if whole > total:
        parts.append(jnp.zeros((whole - total, 32), dtype=jnp.uint8))
    tile = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return tile, offsets


class FusedJob:
    """In-flight fused finalize: the device dispatch has been issued
    (asynchronously — JAX returns before the TPU finishes) but digests
    have not been fetched. ``collect`` blocks on the single device->host
    transfer. This is the double-buffering seam: the caller executes the
    NEXT window's transactions on the host while this window's fixpoint
    program runs on device (SURVEY §7.4-5).

    ``digests`` stays referenced after collect so a LATER window's
    dispatch can gather rows from it device-to-device (resolved-input
    tiles — the deep-pipeline cross-window mechanism); ``dpos`` maps
    each placeholder to its row for that gather. Once the window
    retires past the pipeline (its rows can no longer be gathered),
    ``release()`` drops the device buffers so HBM stays O(in-flight
    windows), not O(replayed chain).

    ``encs`` are the per-class FINAL substituted encodings, still on
    device, in the same class/row order as ``class_rows`` — the
    device-resident commit gathers live rows out of them straight into
    the store mirror (storage/device_mirror.py) with zero node bytes
    leaving the device."""

    __slots__ = ("digests", "encs", "class_rows", "dpos", "_mapping",
                 "admit_tiles", "upload_nbytes", "upload_seconds",
                 "compile_seconds")

    def __init__(self, digests, class_rows, dpos=None, encs=None,
                 admit_tiles=None):
        self.digests = digests  # device u8[sum rows, 32]
        self.encs = encs  # per-class device u8[nrows, nb*RATE] or None
        self.class_rows = class_rows  # [(phs in row order, global base)]
        self.dpos = dpos or {}  # ph -> global row (cross-window gather)
        self._mapping: Dict[bytes, bytes] = None
        # mirror-admit tiles gathered INSIDE the dispatch:
        # [(nblocks, keys, enc_dev, claim_dev, lengths)] or None when
        # the dispatch ran without admit_live (ledger/window.py)
        self.admit_tiles = admit_tiles
        # what the dispatch uploaded and how long the enqueue took —
        # the adaptive controller's seal.upload roofline input
        self.upload_nbytes = 0
        self.upload_seconds = 0.0
        # > 0 when this dispatch built its program (in-process cache
        # miss): such a window is not a timing sample for the adaptive
        # controller (ledger/window.py)
        self.compile_seconds = 0.0

    def fetch_rows(self, refs) -> Dict[bytes, bytes]:
        """Digests of ``refs`` ONLY: a device-to-device row gather plus
        a 32 B x n host copy — the collect-stage root check's entire
        d2h traffic, vs. ``collect``'s full-tile haul (which the staged
        pipeline defers to the async persist stage)."""
        if self._mapping is not None:
            m = self._mapping
            return {r: m[r] for r in refs if r in m}
        out: Dict[bytes, bytes] = {}
        if self.digests is None:
            return out
        present = [r for r in refs if r in self.dpos]
        if not present:
            return out
        import jax

        rows = np.asarray(
            [self.dpos[r] for r in present], dtype=np.int32
        )
        sub = _take_rows(self.digests, rows)  # d2d — no tile crosses
        with _span("fused.rootcheck", rows=len(present)):
            with LEDGER.transfer("seal.rootcheck", D2H, sub.size):
                d = np.asarray(jax.device_get(sub))
        for i, r in enumerate(present):
            out[r] = d[i].tobytes()
        return out

    def release_encs(self) -> None:
        """Drop the final-encoding buffers (after the mirror admit has
        gathered what it needs — the gathered tiles are independent
        arrays)."""
        self.encs = None

    def release(self) -> None:
        """Drop ALL device references (digest tile + encodings). Called
        when the window retires from the pipeline: its rows left
        ``_inflight_rows`` so no later seal can gather from it, and
        ``_mapping`` (host bytes) is what any late reader needs.
        Without this the digest tiles of every replayed window stayed
        referenced and HBM grew O(replayed chain)."""
        self.encs = None
        self.digests = None
        self.admit_tiles = None

    def collect(self) -> Dict[bytes, bytes]:
        if self._mapping is not None:
            return self._mapping
        if self.digests is None:
            return {}
        import jax

        from khipu_tpu.chaos import fault_point

        fault_point("fused.collect")
        with _span("fused.collect", rows=int(self.digests.shape[0])):
            # the ONE device->host crossing of the collect phase — what
            # the movement ledger classifies as placeholder-resolution
            with LEDGER.transfer(
                "fused.collect", D2H, self.digests.size
            ):
                d = np.asarray(jax.device_get(self.digests))
            # ONE device fetch, ONE bytes copy, then pure slicing — the
            # per-row `d[i].tobytes()` loop paid a numpy indexing round
            # per node and dominated the collect phase
            blob = d.tobytes()
            out: Dict[bytes, bytes] = {}
            for rows, base in self.class_rows:
                o = base * 32
                out.update(
                    zip(
                        rows,
                        (blob[o + 32 * r : o + 32 * r + 32]
                         for r in range(len(rows))),
                    )
                )
        self._mapping = out
        return out


def fused_resolve(
    to_resolve: Dict[bytes, bytes],
    deps: Dict[bytes, List[bytes]],
    prefix: bytes,
    use_jnp: bool = False,
    depth: int = None,
) -> Dict[bytes, bytes]:
    return fused_submit(to_resolve, deps, prefix, use_jnp, depth).collect()


def fused_submit(
    to_resolve: Dict[bytes, bytes],
    deps: Dict[bytes, List[bytes]],
    prefix: bytes,
    use_jnp: bool = False,
    depth: int = None,
    ext=None,
    admit_live=None,
    held: Optional[HeldBuckets] = None,
    sites=None,
) -> FusedJob:
    """Pack + dispatch the fixpoint program that resolves placeholder ->
    real Keccak-256 hash for every entry of ``to_resolve`` (placeholder
    -> raw encoding); returns without waiting for the device.

    ``deps`` is the child map from deferred.finalize (already restricted
    to session-known placeholders); ``prefix`` is the session's
    placeholder prefix for the offset scan. Callers that know the DAG
    depth (bulk build has it from the height pass) pass ``depth`` to
    skip the O(depth x nodes) topological scan.

    ``ext``: optional ``(digests, pos)`` resolved-input tile — a device
    u8[n, 32] array of FINAL digests from earlier windows (typically
    gathered from an in-flight FusedJob's output, device-to-device) and
    a ``ph -> row`` map. Encodings that still embed those windows'
    placeholder bytes get them substituted ON DEVICE from the tile, so
    a window can be sealed and dispatched while its predecessor is
    still hashing (the seal/collect barrier removal).

    ``admit_live``: optional set/dict of placeholders whose FINAL
    encodings + digests the caller wants gathered into whole mirror
    tiles INSIDE the dispatch (``FusedJob.admit_tiles``) — the
    device-resident commit's admit pass folded into this program so it
    costs no extra device round-trip per window.

    ``held``: the caller's :class:`HeldBuckets`, kept across its
    windows; a call without one starts a new record.

    ``sites``: optional ``(node, off, child)`` arrays, one entry per
    substitution: the 32 bytes at ``off`` of node ``node`` take the
    digest of node ``child`` (both index ``to_resolve``'s order), or,
    for ``child >= len(to_resolve)``, of row ``child -
    len(to_resolve)`` of the ext tile. The window committer's pack has
    scanned its encodings already and hands what it found; a call
    without them scans here (:func:`_scan_sites`).
    """
    from khipu_tpu.chaos import fault_point

    # chaos seam: a `raise` rule here models a runtime device-dispatch
    # failure (window.py degrades that window to the host hasher)
    fault_point("fused.dispatch")
    with _span(
        "fused.dispatch",
        nodes=len(to_resolve),
        ext_rows=int(ext[0].shape[0]) if ext is not None else 0,
        admit=len(admit_live) if admit_live else 0,
        backend="jnp" if use_jnp else "pallas",
    ) as sp:
        return _fused_submit(
            to_resolve, deps, prefix, use_jnp, depth, ext, admit_live, sp,
            held if held is not None else HeldBuckets(), sites,
        )


def _scan_sites(to_resolve, prefix, ext_pos):
    """``fused_submit``'s ``sites`` for a caller that hands none: one
    :func:`find_sites` scan, and own children matched by counter, all
    sites at once against the keys' sorted counters. What that leaves
    (a ref into the ext tile, a counter past 63 bits) is matched by its
    bytes, as every site once was."""
    from khipu_tpu.trie.deferred import find_sites, placeholder_counters

    phs = list(to_resolve)
    n = len(phs)
    found = find_sites(list(to_resolve.values()), prefix)
    # a key that is no 32-byte ref of this prefix is no site's child
    refs = [i for i, ph in enumerate(phs)
            if len(ph) == 32 and ph.startswith(prefix)]
    key_ctr = np.full(n, -1, np.int64)
    key_ctr[refs] = placeholder_counters(
        b"".join(phs[i] for i in refs), np.arange(0, 32 * len(refs), 32),
        len(prefix))
    order = np.argsort(key_ctr, kind="stable")
    ranked = key_ctr[order]
    k = np.minimum(np.searchsorted(ranked, found.ctr), n - 1)
    child = np.where(
        (found.ctr >= 0) & (ranked[k] == found.ctr), order[k], -1)
    rest = np.flatnonzero(child < 0)
    if rest.size:
        index = dict(zip(phs, range(n)))
        joined = found.joined
        for i, p in zip(rest.tolist(), found.pos[rest].tolist()):
            key = joined[p : p + 32]
            c = index.get(key)
            if c is None and key in ext_pos:
                c = n + ext_pos[key]
            if c is not None:
                child[i] = c
    keep = child >= 0
    return found.node[keep], found.off[keep], child[keep]


def _fused_submit(to_resolve, deps, prefix, use_jnp, depth, ext,
                  admit_live, sp, held, sites) -> FusedJob:
    if not to_resolve:
        return FusedJob(None, [])
    if depth is None:
        depth = len(topo_levels(deps))
    if depth > MAX_DEPTH:
        raise FusedUnsupported(f"DAG depth {depth} > {MAX_DEPTH}")

    from khipu_tpu.ops.keccak_pallas import (
        MAX_PALLAS_BLOCKS,
        _pallas_target_count,
    )

    _build_t0 = time.perf_counter() if LEDGER.enabled else 0.0
    with _span("seal.dispatch_build", nodes=len(to_resolve)):
        phs = list(to_resolve)
        n_nodes = len(phs)
        enc_len = np.fromiter(
            map(len, to_resolve.values()), np.int64, n_nodes)
        node_nb = enc_len // RATE + 1

        # bucket rows by rate-block class; the class set is pinned to a
        # CANONICAL {1..4} (a state-trie node never exceeds 4 rate blocks:
        # max branch ~532 B) so every window shares one compiled signature —
        # windows whose organic class sets differ would otherwise each pay a
        # fresh multi-second XLA compile. Larger classes appear only for
        # exotic long-value tries and extend the signature organically.
        class_list = sorted({1, 2, 3, 4, *np.unique(node_nb).tolist()})
        if not use_jnp and class_list[-1] > MAX_PALLAS_BLOCKS:
            raise FusedUnsupported(
                f"rate class {class_list[-1]} exceeds the Pallas bound"
            )

        # global digest index = class-major position (class order, row
        # order); a node's row in its class is its rank among the
        # class's nodes in to_resolve's order
        members: Dict[int, np.ndarray] = {}
        classes: Dict[int, List[bytes]] = {}
        node_row = np.empty(n_nodes, np.int64)
        node_gpos = np.empty(n_nodes, np.int64)
        dpos: Dict[bytes, int] = {}
        base = 0
        nrows_pad: Dict[int, int] = {}
        for nb in class_list:
            of_class = np.flatnonzero(node_nb == nb)
            members[nb] = of_class
            rows = classes[nb] = [phs[i] for i in of_class.tolist()]
            # +1 guarantees at least one spare padding row, the filler
            # of the admit slots; pallas needs whole 1024-row tiles, the
            # jnp path only pow-2
            n = len(rows) + 1
            nrows_pad[nb] = held.take(
                (nb, "rows"),
                _pow2(n, floor=16) if use_jnp
                else _pallas_target_count(nb, n))
            node_row[of_class] = np.arange(len(rows))
            node_gpos[of_class] = base + node_row[of_class]
            dpos.update(zip(rows, range(base, base + len(rows))))
            base += nrows_pad[nb]

        total_rows = base  # ext tiles are indexed past this window's rows
        ext_pos: Dict[bytes, int] = {}
        ext_dev = None
        if ext is not None:
            ext_dev, ext_pos = ext

        # every substitution of the window as (node, off, child), then
        # each class's (row, off, child_gpos) by index mapping: no
        # per-site look-up. A child past the nodes is an ext tile row
        if sites is None:
            sites = _scan_sites(to_resolve, prefix, ext_pos)
        site_node, site_off, site_child = sites
        site_nb = node_nb[site_node]
        site_row = node_row[site_node]
        own = site_child < n_nodes
        site_gpos = np.where(
            own, node_gpos[np.where(own, site_child, 0)],
            total_rows + site_child - n_nodes,
        )

        # mirror-admit fold: per class, the row indices of live nodes
        # padded out to whole 1024-row mirror tiles (the dummy points
        # at the class's guaranteed padding row — a valid multi-rate-
        # padded filler whose digest is self-consistent, so filler
        # slots verify). The slots are as many as the class's row
        # bucket, so the live set's size moves no compiled signature.
        from khipu_tpu.storage.device_mirror import TILE as _MTILE

        enc_bufs: List[np.ndarray] = []
        sub_arrays: List[np.ndarray] = []
        admit_bufs: List[np.ndarray] = []
        admit_meta: List = []  # per class: (keys, lengths) or None
        sig: List[Tuple[int, int, int, int]] = []
        live: List[str] = []  # sig's counts before padding, same layout
        live_subs = 0
        for nb in class_list:
            rows = classes[nb]
            width = nb * RATE
            npad = nrows_pad[nb]
            # ONE joined buffer + frombuffer instead of a numpy row-
            # assignment per node (the row loop was the dominant host cost
            # of seal); the multi-rate pad bits apply as two vector xors
            parts = [to_resolve[ph].ljust(width, b"\0") for ph in rows]
            lens = np.zeros(npad, dtype=np.int64)
            lens[: len(rows)] = enc_len[members[nb]]
            in_class = site_nb == nb
            subs = np.stack(sorted_sites(
                site_row[in_class], site_off[in_class],
                site_gpos[in_class], width,
            ), axis=1)  # (row, off, child_gpos) by (row, off)
            # padding rows still need valid keccak padding (their digests
            # are discarded, but the kernel hashes them): lens 0
            if npad > len(rows):
                parts.append(bytes(width * (npad - len(rows))))
            buf = (
                np.frombuffer(b"".join(parts), dtype=np.uint8)
                .reshape(npad, width)
                .copy()
            )
            buf[np.arange(npad), lens] ^= 0x01  # multi-rate pad (fixed
            buf[:, width - 1] ^= 0x80  # region: substitution never touches)
            # coarse floor: windows of similar size must land in the SAME
            # compiled signature (every distinct shape costs a fresh XLA
            # compile on the first window that hits it)
            nsubs = held.take((nb, "subs"), _pow2(
                len(subs) + 1, floor=1024 if use_jnp else 4096))
            # a padding substitution names the row past the last: the
            # device drops it, and it stays last in (row, off) order
            sub_np = np.full((nsubs, 3), (npad, 0, 0), dtype=np.int32)
            sub_np[: len(subs)] = subs
            live_subs += len(subs)
            enc_bufs.append(buf)
            sub_arrays.extend(
                [
                    np.ascontiguousarray(sub_np[:, 0]),
                    np.ascontiguousarray(sub_np[:, 1]),
                    np.ascontiguousarray(sub_np[:, 2]),
                ]
            )
            n_live = 0
            if admit_live:
                aidx = np.flatnonzero(np.fromiter(
                    map(admit_live.__contains__, rows), bool, len(rows)))
                n_live = aidx.size
                akeys: List = [rows[r] for r in aidx.tolist()]
                alens: List[int] = enc_len[members[nb]][aidx].tolist()
                # as many admit slots as the class has rows, in whole
                # mirror tiles: the live rows fill the front, the
                # mirror takes the tiles that hold one (admit_device),
                # and how many of a class's rows are live is no count
                # of the signature
                nadmit = -(-nrows_pad[nb] // _MTILE) * _MTILE
                aidx_np = np.full(nadmit, npad - 1, dtype=np.int32)
                aidx_np[:n_live] = aidx
                akeys.extend([None] * (nadmit - n_live))
                alens.extend([0] * (nadmit - n_live))
                admit_bufs.append(aidx_np)
                admit_meta.append((akeys, alens))
            else:
                nadmit = 0
                admit_bufs.append(np.zeros(0, dtype=np.int32))
                admit_meta.append(None)
            sig.append((nb, nrows_pad[nb], nsubs, nadmit))
            live.append(f"{nb}x{len(rows)}/{len(subs)}+a{n_live}")

        # resolved-input tile: always an input (a dummy zero tile when the
        # window has no cross-refs) so every window shares one compiled
        # signature family regardless of pipeline depth
        # (a tile from gather_ext_tile has taken its held size already)
        n_ext = ext_dev.shape[0] if ext_dev is not None else 0
        ext_rows = (_pow2(n_ext, floor=EXT_FLOOR) if ext_dev is not None
                    else held.take(("ext",), EXT_FLOOR))
        if ext_dev is None:
            ext_buf = np.zeros((ext_rows, 32), dtype=np.uint8)
        elif n_ext != ext_rows:
            import jax.numpy as jnp

            ext_buf = (
                jnp.zeros((ext_rows, 32), dtype=jnp.uint8)
                .at[:n_ext]
                .set(ext_dev)
            )
        else:
            ext_buf = ext_dev

        # the program loops `depth` times (its last input), under the
        # one cap every window shares: a DAG one level deeper than any
        # before is no new signature, and no round hashes for the sake
        # of a bucket
        rounds = depth
        run, compile_s = _build_fused.lookup(
            tuple(sig), MAX_DEPTH, use_jnp, ext_rows
        )
        # padded work against live work: what the buckets cost
        sp.set_tag("rows_padded", total_rows)
        sp.set_tag("rounds", rounds)
        # the signature's counts before their buckets: how far this
        # window sits from the edge at which it would compile anew
        sp.set_tag("live", ",".join(live))
        sp.set_tag("ext_live", len(ext_pos))
        sp.set_tag("subs", live_subs)
        sp.set_tag("subs_padded", sum(s[2] for s in sig))
        for nb, nrows, _, _ in sig:
            REGISTRY.counter(
                "khipu_fused_hashed_rows_total",
                help="rows x rounds the fused fixpoint program hashed "
                     "(trie/fused.py)",
                labels={"nblocks": str(nb)},
            ).inc(nrows * rounds)
        FUSED_LIVE_NODES.inc(len(phs))
        FUSED_DISPATCHES.inc()

        # host->device upload = every host-built input buffer (the ext tile
        # counts only when host-built — gathered device-to-device tiles
        # never leave the device, which is the whole point of the deep
        # pipeline). Dispatch is async, so the measured duration is the
        # enqueue+transfer handoff, not the device compute.
        up = sum(b.nbytes for b in enc_bufs) + sum(a.nbytes for a in sub_arrays)
        up += sum(a.nbytes for a in admit_bufs)
        if isinstance(ext_buf, np.ndarray):
            up += ext_buf.nbytes
    if LEDGER.enabled:
        # host-side classification event: bytes of input buffers the
        # build step packed, with its wall duration (the cost model's
        # fixed-overhead join for seal.dispatch_build)
        LEDGER.record("seal.dispatch_build", HOST, up,
                      duration=time.perf_counter() - _build_t0)
    _up_t0 = time.perf_counter()
    with _span("seal.upload", nbytes=up):
        with LEDGER.transfer("seal.upload", H2D, up):
            # async: no host sync
            digests, final_encs, admit_out = run(
                *[*enc_bufs, *sub_arrays, ext_buf, *admit_bufs,
                  np.int32(rounds)]
            )
    _up_s = time.perf_counter() - _up_t0
    # start the device->host copy NOW: it streams as soon as the
    # fixpoint finishes, so collect()'s device_get finds the digests
    # already on the host
    _start_async_copy(digests)
    class_rows = []
    base = 0
    for nb in class_list:
        class_rows.append((classes[nb], base))
        base += nrows_pad[nb]
    admit_tiles = None
    if admit_live:
        admit_tiles = []
        for c, nb in enumerate(class_list):
            meta = admit_meta[c]
            if meta is None or admit_out[c] is None:
                continue
            akeys, alens = meta
            enc_g, claim_g = admit_out[c]
            admit_tiles.append((nb, akeys, enc_g, claim_g, alens))
    job = FusedJob(digests, class_rows, dpos, encs=list(final_encs),
                   admit_tiles=admit_tiles)
    job.upload_nbytes = up
    job.upload_seconds = _up_s
    job.compile_seconds = compile_s
    return job
