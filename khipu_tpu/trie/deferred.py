"""Deferred-hash MPT commit: incremental updates with level-synchronous
batched hashing.

``bulk.py`` builds *fresh* tries batch-wise; block execution instead
produces a few hundred dirty keys against an EXISTING trie. The eager
MPT hashes each rebuilt node on the host as it goes (HOT LOOP 3,
SURVEY §3.2); here the same update machinery runs with hashing
*deferred*: ``_ref`` hands out 32-byte placeholder refs and records the
encoding, and ``finalize`` resolves the placeholder DAG bottom-up — one
batched Keccak call per dependency level (khipu_tpu.ops.keccak — the
Pallas kernel on TPU). This is SURVEY §2.8(c)'s level-synchronous
commit applied to incremental updates, and reuses MerklePatriciaTrie's
insert/delete/capping logic verbatim so bit-exactness is inherited, not
re-proven.

Placeholders are 32 bytes (same length as a real hash), so every RLP
length — and therefore every <32-byte inline ("capped") decision — is
identical to the eager path. A node containing a placeholder child is
necessarily >= 33 bytes encoded, so placeholders can never hide inside
an inlined child.
"""

from __future__ import annotations

import itertools
import operator
import os
from typing import Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

from khipu_tpu.base.rlp import rlp_decode, rlp_encode
from khipu_tpu.trie.bulk import Hasher, host_hasher
from khipu_tpu.trie.mpt import BLANK, MerklePatriciaTrie

# UNFORGEABLE per-process prefix: leaf values are attacker-controlled
# (a contract can SSTORE any 32-byte word), so a fixed magic could be
# forged to make finalize() substitute a real node hash into stored
# data or crash the level loop. 14 random bytes make a collision
# 2^-112; detection additionally requires membership in the session's
# own staged-placeholder set (see _collect_placeholders).
_PLACEHOLDER_PREFIX = b"\xfe\xc0" + os.urandom(14) + b"\xc0\xfe"  # 18 bytes


def _make_placeholder(counter: int) -> bytes:
    return _PLACEHOLDER_PREFIX + counter.to_bytes(14, "big")


def _is_placeholder(ref) -> bool:
    return (
        isinstance(ref, bytes)
        and len(ref) == 32
        and ref.startswith(_PLACEHOLDER_PREFIX)
    )


class DeferredMPT(MerklePatriciaTrie):
    """MerklePatriciaTrie whose freshly created nodes get placeholder
    refs instead of eager keccak256 calls. Call :func:`finalize` (or
    :meth:`commit`) to resolve."""

    def __init__(self, source, root_hash=None, _root_ref=None,
                 _logs=None, _staged=None, counter=None, ref_sink=None):
        super().__init__(
            source, root_hash=root_hash, _root_ref=_root_ref,
            _logs=_logs, _staged=_staged,
        )
        # The base class defensively COPIES _logs/_staged; a deferred
        # session must share them BY REFERENCE — the window commits
        # several trie sessions into one placeholder namespace, and a
        # read-through source resolves staged nodes across blocks.
        if _logs is not None:
            self._logs = _logs
        if _staged is not None:
            self._staged = _staged
        # counter may be SHARED across sessions too; ref_sink tags which
        # session created each placeholder so persist can route nodes to
        # the right store
        self._counter = counter if counter is not None else [0]
        self._ref_sink = ref_sink
        # SESSION-local decode cache, never the source-attached one:
        # placeholder refs are NOT content-addressed (the per-process
        # prefix + a restarting counter reuses the same byte strings
        # across sessions with different content), so a cross-session
        # cache would serve stale structures. Within one session each
        # placeholder is staged write-once, so caching is sound.
        self._dcache = {}

    def _child(self) -> "DeferredMPT":
        t = DeferredMPT(self.source)
        t._root_ref = self._root_ref
        t._logs = self._logs
        t._staged = self._staged
        t._counter = self._counter
        t._ref_sink = self._ref_sink
        t._dcache = self._dcache
        return t

    def _ref(self, node):
        if node == BLANK:
            return BLANK
        encoded = rlp_encode(node)
        if len(encoded) < 32:
            return node
        ph = _make_placeholder(self._counter[0])
        self._counter[0] += 1
        self._staged[ph] = encoded
        self._log_update(ph, encoded)
        if self._ref_sink is not None:
            self._ref_sink.add(ph)
        return ph

    def force_hashed_root(self) -> bytes:
        """32-byte root ref: placeholders/real hashes pass through; an
        inline (<32 B) root gets its own placeholder (the eager path
        hashes inline roots too — mpt.persist parity). BLANK roots are
        the empty-trie hash."""
        from khipu_tpu.trie.mpt import EMPTY_TRIE_HASH

        ref = self._root_ref
        if ref == BLANK:
            return EMPTY_TRIE_HASH
        if isinstance(ref, bytes):
            return ref
        encoded = rlp_encode(ref)
        ph = _make_placeholder(self._counter[0])
        self._counter[0] += 1
        self._staged[ph] = encoded
        self._log_update(ph, encoded)
        if self._ref_sink is not None:
            self._ref_sink.add(ph)
        return ph

    def commit(self, hasher: Hasher = host_hasher) -> MerklePatriciaTrie:
        """Resolve all placeholders; returns an ordinary trie whose
        logs/staged/root carry real hashes."""
        return finalize(self, hasher)


def _substitute_bytes(value: bytes, mapping: Dict[bytes, bytes]) -> bytes:
    """Replace EMBEDDED placeholders inside an opaque byte string (leaf
    values may contain them: an account's RLP embeds its storage root,
    which is a placeholder while the window is open)."""
    pos = value.find(_PLACEHOLDER_PREFIX)
    if pos < 0:
        return value
    out = bytearray(value)
    while pos >= 0:
        ph = bytes(out[pos : pos + 32])
        real = mapping.get(ph)
        if real is not None:
            out[pos : pos + 32] = real
        pos = bytes(out).find(_PLACEHOLDER_PREFIX, pos + 1)
    return bytes(out)


class Sites(NamedTuple):
    """Every placeholder-shaped site of a list of encodings, as
    :func:`find_sites` met them: a 32-byte run that starts with the
    prefix and lies inside one encoding."""

    joined: bytes  # the encodings end to end
    # int64[n + 1]: encoding i is joined[starts[i]:starts[i + 1]]
    starts: np.ndarray
    node: np.ndarray  # int64[m]: index of the encoding that holds the site
    off: np.ndarray  # int64[m]: the site's offset inside that encoding
    # int64[m]: the counter after the prefix; -1 where it does not fit
    # 63 bits (no session hands such a one out: the site's bytes decide)
    ctr: np.ndarray

    @property
    def pos(self) -> np.ndarray:
        """Each site's offset in ``joined``."""
        return self.starts[self.node] + self.off


def _runs(blob: bytes, dtype, offset: int) -> np.ndarray:
    """``blob`` as one element of ``dtype`` per 32-byte run: element i
    starts at byte ``i + offset``. Overlapping views of the one buffer,
    nothing copied: indexing it gathers whole prefixes, counters or
    refs in one call."""
    return np.ndarray((len(blob) - 31,), dtype, blob, offset, (1,))


def placeholder_counters(blob: bytes, at: np.ndarray, prefix_len: int
                         ) -> np.ndarray:
    """The big-endian counters of the 32-byte refs that start at ``at``
    in ``blob``, as int64; -1 where one does not fit 63 bits."""
    if not at.size:
        return np.empty(0, np.int64)
    low = _runs(blob, ">u8", 24)[at].astype(np.int64)  # wraps past 63
    high = _runs(blob, f"S{24 - prefix_len}", prefix_len)[at]
    return np.where((low >= 0) & (high == b""), low, -1)


def find_sites(encs: Sequence[bytes], prefix: bytes = _PLACEHOLDER_PREFIX
               ) -> Sites:
    """ONE numpy scan of the joined encodings for every placeholder
    site: a compare against the prefix's first byte, then one compare
    of the whole prefix over the survivors. The scalar ``find`` loop's
    semantics hold exactly: a match that would run past its encoding's
    end is no site (in the joined buffer it could straddle two
    encodings; a real placeholder never does, it was written as one
    32-byte ref inside one node), and matches are taken left to right
    with the next search starting 32 bytes on. What a site's 32 bytes
    MEAN (this window's node, an earlier window's, opaque data that
    collided with the prefix) is the caller's to decide."""
    if not 0 < len(prefix) < 24:
        raise ValueError("a placeholder prefix leaves 9 to 31 counter bytes")
    n = len(encs)
    starts = np.zeros(n + 1, np.int64)
    np.cumsum(np.fromiter(map(len, encs), np.int64, n), out=starts[1:])
    joined = b"".join(encs)
    if len(joined) < 32:
        none = np.empty(0, np.int64)
        return Sites(joined, starts, none, none, none)
    cand = np.flatnonzero(_runs(joined, np.uint8, 0) == prefix[0])
    cand = cand[_runs(joined, f"S{len(prefix)}", 0)[cand] == prefix]
    node = np.searchsorted(starts, cand, side="right") - 1
    inside = cand + 32 <= starts[node + 1]
    cand, node = cand[inside], node[inside]
    if cand.size > 1 and (np.diff(cand) < 32).any():
        # overlapping matches (crafted data only: the second one's
        # prefix lies in the first one's counter): left to right, a
        # match inside the 32 bytes of one already taken is skipped
        keep, free = [], 0
        for i, p in enumerate(cand.tolist()):
            if p >= free:
                keep.append(i)
                free = p + 32
        cand, node = cand[keep], node[keep]
    return Sites(
        joined, starts, node, cand - starts[node],
        placeholder_counters(joined, cand, len(prefix)),
    )


def splice_sites(sites: Sites, pos: np.ndarray, reals: np.ndarray
                 ) -> List[bytes]:
    """The encodings of ``sites`` with the 32 bytes at each ``pos``
    (offsets in the joined buffer) replaced by the matching element of
    ``reals`` (dtype ``V32``): one indexed assignment of whole refs,
    then a slice per encoding."""
    blob = sites.joined
    if pos.size:
        spliced = bytearray(blob)
        _runs(spliced, "V32", 0)[pos] = reals
        blob = bytes(spliced)
    bounds = sites.starts.tolist()
    return [blob[s:e] for s, e in zip(bounds, bounds[1:])]


def found_digests(reals: Sequence) -> Tuple[np.ndarray, np.ndarray]:
    """Which entries of ``reals`` (32-byte digests, or None where a
    look-up found nothing) are digests, as a mask, and those digests as
    one ``V32`` array: what :func:`splice_sites` takes."""
    found = np.fromiter(
        map(operator.is_not, reals, itertools.repeat(None)), bool,
        len(reals),
    )
    return found, np.frombuffer(b"".join(filter(None, reals)), "V32")


def _substitute_many(encs: List[bytes], lookup) -> List[bytes]:
    """Batched :func:`_substitute_bytes` over many encodings, from
    :func:`find_sites`' one scan instead of a Python ``find`` loop per
    node. ``lookup(ph) -> real | None`` decides substitution; a site
    whose 32 bytes are not a known placeholder (opaque data that
    collided with the prefix, or a foreign counter range) is left
    untouched, exactly like the scalar path."""
    sites = find_sites(encs)
    joined = sites.joined
    pos = sites.pos
    known, digests = found_digests(
        [lookup(joined[p : p + 32]) for p in pos.tolist()])
    return splice_sites(sites, pos[known], digests)


def _substitute(structure, mapping: Dict[bytes, bytes]):
    """Replace placeholder refs (and embedded ones) inside a decoded
    node structure."""
    if isinstance(structure, bytes):
        direct = mapping.get(structure)
        if direct is not None:
            return direct
        return _substitute_bytes(structure, mapping)
    return [_substitute(item, mapping) for item in structure]


def _collect_placeholders(structure, out: List[bytes], known) -> None:
    """Collect placeholder refs, direct or embedded. ``known`` is the
    session's own placeholder set — a prefix match that is NOT a key the
    session handed out is opaque user data, never a dependency."""
    if isinstance(structure, bytes):
        if _is_placeholder(structure):
            if structure in known:
                out.append(structure)
        else:
            pos = structure.find(_PLACEHOLDER_PREFIX)
            while pos >= 0:
                ph = structure[pos : pos + 32]
                if ph in known:
                    out.append(ph)
                pos = structure.find(_PLACEHOLDER_PREFIX, pos + 32)
        return
    for item in structure:
        _collect_placeholders(item, out, known)


def resolution_inputs(trie: DeferredMPT, subset=None):
    """(to_resolve, deps, structures) for a deferred session — the
    placeholder set a resolver must hash and its dependency map. The
    decode-based derivation used by finalize (both paths), the sharded
    resolver, the dryrun and the tests; ``subset`` restricts to given
    placeholders (finalize's live-only mode) while membership (`known`)
    always spans every placeholder the session handed out.
    WindowCommitter.seal keeps a raw-byte-scan sibling (counter-range +
    pre-substitution, no decode) — test_seal_scan_matches_resolution_
    inputs pins the two against divergence."""
    staged = {
        ph: enc for ph, enc in trie._staged.items() if _is_placeholder(ph)
    }
    if subset is None:
        to_resolve = staged
    else:
        to_resolve = {ph: staged[ph] for ph in subset}
    known = frozenset(staged)
    structures = {ph: rlp_decode(enc) for ph, enc in to_resolve.items()}
    deps: Dict[bytes, List[bytes]] = {}
    for ph, struct in structures.items():
        children: List[bytes] = []
        _collect_placeholders(struct, children, known)
        deps[ph] = children
    return to_resolve, deps, structures


def finalize(
    trie: DeferredMPT,
    hasher: Hasher = host_hasher,
    return_mapping: bool = False,
    fused: bool = False,
):
    """Hash the live placeholder DAG bottom-up, one batch per level.

    Dead placeholders (created then superseded within the same session;
    net refcount 0) were already dropped by the MPT's refcount log.
    With ``return_mapping``, returns (trie, {placeholder: real_hash})
    — the window committer resolves per-block root refs through it.

    With ``fused``, the whole DAG resolves in ONE device dispatch
    (trie/fused.py fixpoint program) instead of one hasher call per
    level — the dispatch-latency fix for windowed device commit; falls
    back to the level loop when the window shape is unsupported.
    """
    # live placeholders: positive log entries with placeholder keys
    live: Dict[bytes, bytes] = {}  # placeholder -> encoded (raw)
    removed: Dict[bytes, List] = {}
    for h, rec in trie._logs.items():
        if _is_placeholder(h):
            if rec[0] > 0:
                live[h] = rec[1]
            # negative placeholder records are impossible: a placeholder
            # starts at +1 and a net removal deletes the entry
        else:
            removed[h] = rec

    if return_mapping:
        # Resolve EVERY placeholder the session created (the staged
        # store retains them): a window's intermediate block roots are
        # superseded by later blocks (net refcount 0 — dead for
        # PERSISTING) yet their resolved hashes are what the per-block
        # root checks compare against. Only live ones persist below.
        to_resolve, deps, structures = resolution_inputs(trie)
    else:
        # plain batch commit: nobody reads dead placeholders — hash
        # only the live set (work scales with live nodes, not churn)
        to_resolve, deps, structures = resolution_inputs(trie, subset=live)

    resolved: Dict[bytes, bytes] = {}  # placeholder -> real hash
    final_encoded: Dict[bytes, bytes] = {}  # real hash -> final rlp
    if fused and to_resolve:
        try:
            from khipu_tpu import device
            from khipu_tpu.trie.fused import (
                FusedUnsupported,
                fused_resolve,
            )

            jnp_path = device.platform() != "tpu"
            resolved = fused_resolve(
                to_resolve, deps, _PLACEHOLDER_PREFIX, use_jnp=jnp_path
            )
            # substitution is length-invariant, so the byte-level
            # substitute over the RAW encoding equals the loop path's
            # decode -> substitute -> re-encode
            for ph, enc in to_resolve.items():
                final_encoded[resolved[ph]] = _substitute_bytes(
                    enc, resolved
                )
        except FusedUnsupported:
            resolved = {}
    if not resolved and deps:
        from khipu_tpu.trie.fused import topo_levels

        for level in topo_levels(deps):
            encodings = []
            for ph in level:
                final = rlp_encode(_substitute(structures[ph], resolved))
                encodings.append(final)
            digests = hasher(encodings)
            for ph, enc, digest in zip(level, encodings, digests):
                resolved[ph] = digest
                final_encoded[digest] = enc

    # rebuild logs: resolved placeholders become Updated(real) records;
    # removal records for pre-existing hashes pass through. Two
    # placeholders can resolve to the SAME hash (identical subtrees) —
    # refcounts add.
    new_logs: Dict[bytes, List] = {h: [rec[0], rec[1]] for h, rec in removed.items()}
    for ph, enc in live.items():
        digest = resolved[ph]
        count = trie._logs[ph][0]
        rec = new_logs.get(digest)
        if rec is None:
            new_logs[digest] = [count, final_encoded[digest]]
        else:
            rec[0] += count
            rec[1] = final_encoded[digest]
            if rec[0] == 0:
                del new_logs[digest]

    new_staged = {
        resolved[ph]: final_encoded[resolved[ph]] for ph in live
    }
    root_ref = trie._root_ref
    if _is_placeholder(root_ref):
        root_ref = resolved[root_ref]
    elif isinstance(root_ref, list):
        root_ref = rlp_decode(
            rlp_encode(_substitute(root_ref, resolved))
        )
    out = MerklePatriciaTrie(
        trie.source, _root_ref=root_ref, _logs=new_logs, _staged=new_staged
    )
    if return_mapping:
        return out, resolved
    return out


def batch_commit(
    trie: MerklePatriciaTrie,
    upserts: Sequence[Tuple[bytes, bytes]],
    removes: Sequence[bytes] = (),
    hasher: Hasher = host_hasher,
) -> MerklePatriciaTrie:
    """Apply a batch of updates to an existing trie with all node
    hashing deferred into level batches. Drop-in replacement for a
    put/remove fold — roots are bit-exact (tests fuzz the equality)."""
    # deep-copy log records: the MPT mutates them in place, and the
    # caller's trie must stay untouched
    d = DeferredMPT(
        trie.source,
        _root_ref=trie._root_ref,
        _logs={h: [c, e] for h, (c, e) in trie._logs.items()},
        _staged=dict(trie._staged),
    )
    return d.update_many(removes, upserts).commit(hasher)
