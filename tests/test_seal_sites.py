"""The seal stage's one site scan (trie/deferred.py ``find_sites``) and
its two readers (ledger/window.py ``_pack_sites``, trie/fused.py
``_fused_submit``) against the scalar ``bytes.find`` loops they
replaced. The loops live on HERE, as the oracle."""

import os
import random

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from khipu_tpu.base.rlp import rlp_encode  # noqa: E402
from khipu_tpu.chaos import (  # noqa: E402
    FaultPlan,
    FaultRule,
    InjectedDeath,
    active,
)
from khipu_tpu.ledger.window import WindowCommitter  # noqa: E402
from khipu_tpu.storage.storages import Storages  # noqa: E402
from khipu_tpu.trie import fused  # noqa: E402
from khipu_tpu.trie.bulk import host_hasher  # noqa: E402
from khipu_tpu.trie.deferred import (  # noqa: E402
    _PLACEHOLDER_PREFIX as PREFIX,
    _make_placeholder as ph,
    _substitute_many,
    find_sites,
)
from khipu_tpu.trie.mpt import EMPTY_TRIE_HASH  # noqa: E402

RATE = fused.RATE


# ------------------------------------------------------------- oracles
# (the loops of ledger/window.py pack_and_dispatch and trie/fused.py
# _fused_submit as they stood before the one scan, kept to compare)

def scalar_sites(encs):
    """[(node, off, 32 bytes)] by one ``find`` at a time."""
    out = []
    for i, enc in enumerate(encs):
        pos = enc.find(PREFIX)
        while pos >= 0:
            if pos + 32 <= len(enc):
                out.append((i, pos, enc[pos : pos + 32]))
            pos = enc.find(PREFIX, pos + 32)
    return out


def scalar_pack(staged, start, end, resolved_global, inflight_rows):
    to_resolve, deps, depth_of, ext_refs = {}, {}, {}, {}
    max_depth = 0
    for idx in range(start, end):
        p = ph(idx)
        enc = staged.get(p)
        if enc is None:
            continue
        pos = enc.find(PREFIX)
        if pos < 0:
            to_resolve[p] = enc
            deps[p] = []
            depth_of[p] = 1
            max_depth = max(max_depth, 1)
            continue
        out = bytearray(enc)
        children = []
        d = 1
        while pos >= 0:
            child = bytes(out[pos : pos + 32])
            real = resolved_global.get(child)
            if real is not None:
                out[pos : pos + 32] = real
            else:
                cd = depth_of.get(child)
                if cd is not None:
                    children.append(child)
                    if cd >= d:
                        d = cd + 1
                else:
                    src = inflight_rows.get(child)
                    if src is not None:
                        ext_refs[child] = src
                    else:
                        real = resolved_global.get(child)
                        if real is not None:
                            out[pos : pos + 32] = real
                        elif child in staged:
                            raise AssertionError("unresolvable ref")
            pos = out.find(PREFIX, pos + 32)
        to_resolve[p] = bytes(out)
        deps[p] = children
        depth_of[p] = d
        max_depth = max(max_depth, d)
    return to_resolve, deps, depth_of, ext_refs, max_depth


def scalar_subs(to_resolve, ext_pos, sig):
    """{class: sorted [(row, off, child_gpos)]} under the row buckets
    the dispatch took (``sig``), one ``find`` and one ``dpos`` probe
    at a time."""
    classes = {c: [] for c in (1, 2, 3, 4)}
    for p, enc in to_resolve.items():
        classes.setdefault(len(enc) // RATE + 1, []).append(p)
    padded = {nb: nrows for nb, nrows, _, _ in sig}
    dpos, base = {}, 0
    for nb in sorted(classes):
        for r, p in enumerate(classes[nb]):
            dpos[p] = base + r
        base += padded[nb]
    total_rows = base
    out = {}
    for nb in sorted(classes):
        subs = []
        for r, p in enumerate(classes[nb]):
            enc = to_resolve[p]
            pos = enc.find(PREFIX)
            while pos >= 0:
                child = enc[pos : pos + 32]
                cp = dpos.get(child)
                if cp is None and child in ext_pos:
                    cp = total_rows + ext_pos[child]
                if cp is not None:
                    subs.append((r, pos, cp))
                pos = enc.find(PREFIX, pos + 32)
        out[nb] = sorted(subs)
    return out


# ------------------------------------------------------------ builders

def _filler(rng, n):
    return bytes(rng.getrandbits(8) for _ in range(n))


def _node(rng, refs, size=None):
    """Random bytes with the 32-byte ``refs`` at random offsets, none
    overlapping (what a branch or a leaf's value looks like to a byte
    scan)."""
    gaps = [rng.randrange(1, 24) for _ in range(len(refs) + 1)]
    if size is not None:
        gaps[-1] += max(0, size - sum(gaps) - 32 * len(refs))
    parts = [_filler(rng, gaps[0])]
    for ref, gap in zip(refs, gaps[1:]):
        parts += [ref, _filler(rng, gap)]
    return b"".join(parts)


class _Window:
    """A committer whose staged namespace holds two windows: counters
    ``[0, start)`` are an earlier window's (each either resolved, in
    flight, or neither), ``[start, end)`` the one to pack."""

    def __init__(self, seed, n=120, start=60, refs=(0, 9),
                 p_resolved=0.3, p_ext=0.2, p_opaque=0.05, sizes=None):
        rng = random.Random(seed)
        self.committer = c = WindowCommitter(
            Storages(), EMPTY_TRIE_HASH, hasher=host_hasher
        )
        self.start, self.end = start, start + n
        src = object()  # stands in for the in-flight WindowJob
        for i in range(start):
            c._staged[ph(i)] = _node(rng, [])
            if i % 2:
                c._resolved_global[ph(i)] = _filler(rng, 32)
            else:
                c._inflight_rows[ph(i)] = (src, i)
        for i in range(start, self.end):
            kids = []
            for _ in range(rng.randrange(*refs) if refs[1] else 0):
                roll = rng.random()
                if roll < p_opaque:
                    # prefix + a counter nobody handed out
                    kids.append(PREFIX + _filler(rng, 14))
                elif roll < p_opaque + p_resolved and start:
                    kids.append(ph(rng.randrange(1, start, 2)))
                elif roll < p_opaque + p_resolved + p_ext and start:
                    kids.append(ph(rng.randrange(0, start, 2)))
                elif i > start:
                    kids.append(ph(rng.randrange(start, i)))
            size = rng.choice(sizes) if sizes else None
            c._staged[ph(i)] = _node(rng, kids, size)
        c._counter[0] = self.end

    def oracle(self):
        c = self.committer
        return scalar_pack(c._staged, self.start, self.end,
                           dict(c._resolved_global), dict(c._inflight_rows))

    def pack(self):
        return self.committer._pack_sites(self.start, self.end)

    def raw(self):
        staged = self.committer._staged
        return [staged[ph(i)] for i in range(self.start, self.end)
                if ph(i) in staged]

    def check(self, pack=None, want=None):
        _assert_pack_equal(pack or self.pack(), want or self.oracle(),
                           self.raw())


def _assert_pack_equal(pack, want, raw):
    """``raw``: the staged encodings of the packed nodes, in order."""
    to_resolve, deps, depth_of, ext_refs, max_depth = want
    assert list(pack.to_resolve.items()) == list(to_resolve.items())
    assert pack.ext_refs == ext_refs
    assert pack.max_depth == max_depth
    got = pack.deps()
    assert list(got) == list(deps)
    assert {p: sorted(v) for p, v in got.items()} == {
        p: sorted(v) for p, v in deps.items()
    }
    # what the scan says it met, against each site's fate in the oracle
    met = dict.fromkeys(("local", "resolved", "ext", "opaque"), 0)
    phs = list(to_resolve)
    for node, off, key in scalar_sites(raw):
        packed = to_resolve[phs[node]][off : off + 32]
        if packed != key:
            met["resolved"] += 1
        elif key in deps[phs[node]]:
            met["local"] += 1
        elif key in ext_refs:
            met["ext"] += 1
        else:
            met["opaque"] += 1
    assert pack.met == met


# --------------------------------------------------------- finder cases

def _leaf_with_storage_root(storage_root):
    account = rlp_encode([b"\x01", b"\x0d\xe0\xb6\xb3\xa7\x64", storage_root,
                          b"\xc5" * 32])
    return rlp_encode([b"\x20" + b"\x11" * 31, account])


def _finder_cases():
    rng = random.Random(7)
    big = ph((1 << 32) + 5)
    wide = PREFIX + b"\x01" + bytes(13)  # counter 2**104
    edge = PREFIX + bytes(6) + b"\x80" + bytes(7)  # counter 2**63
    yield "no_site", [_filler(rng, 70), _filler(rng, 200)]
    yield "empty", []
    yield "shorter_than_a_ref", [b"\xfe", PREFIX[:5]]
    yield "one_node", [_node(rng, [ph(3), ph(4)])]
    yield "leaf_embeds_storage_root", [
        _leaf_with_storage_root(ph(9)), _filler(rng, 40)]
    yield "opaque_prefix_match", [
        _node(rng, [PREFIX + _filler(rng, 14), ph(2)])]
    # the joined buffer reads prefix + 14 bytes across the boundary
    yield "straddles_two_encodings", [
        _filler(rng, 50) + ph(5)[:10], ph(5)[10:] + _filler(rng, 50)]
    yield "straddle_after_full_prefix", [
        _filler(rng, 50) + ph(5)[:20], ph(5)[20:] + _filler(rng, 50)]
    yield "match_in_last_31_bytes", [
        _filler(rng, 40) + ph(6)[:31], _filler(rng, 60) + ph(7)]
    yield "site_ends_with_its_node", [_filler(rng, 40) + ph(6), ph(8)]
    # a second prefix inside the first match's counter bytes: skipped
    yield "overlapping_matches", [
        _filler(rng, 9) + PREFIX + PREFIX + bytes(14) + ph(1)
        + _filler(rng, 5)]
    yield "overlapping_chain", [
        _filler(rng, 3) + PREFIX * 3 + bytes(14) + _filler(rng, 20),
        PREFIX * 2 + bytes(14)]
    yield "back_to_back", [ph(1) + ph(2) + ph(3)]
    yield "counter_above_2_32", [_node(rng, [big, ph(1)])]
    yield "counter_past_63_bits", [_node(rng, [wide, edge, ph(1)])]
    for seed in range(6):
        r = random.Random(100 + seed)
        yield f"random_{seed}", [
            _node(r, [r.choice([ph(r.randrange(1 << 40)), wide,
                                PREFIX + _filler(r, 14)])
                      for _ in range(r.randrange(0, 17))])
            for _ in range(r.randrange(1, 80))
        ]


@pytest.mark.parametrize(
    "encs", [pytest.param(e, id=n) for n, e in _finder_cases()])
def test_find_sites_matches_the_scalar_find_loop(encs):
    sites = find_sites(encs)
    want = scalar_sites(encs)
    got = [
        (int(n), int(o), sites.joined[p : p + 32])
        for n, o, p in zip(sites.node, sites.off, sites.pos)
    ]
    assert got == want
    for (_, _, ref), ctr in zip(want, sites.ctr.tolist()):
        full = int.from_bytes(ref[len(PREFIX):], "big")
        assert ctr == (full if full < 1 << 63 else -1)
    assert sites.joined == b"".join(encs)
    assert [sites.joined[s:e] for s, e in zip(sites.starts, sites.starts[1:])
            ] == list(encs)


@pytest.mark.parametrize(
    "encs", [pytest.param(e, id=n) for n, e in _finder_cases()])
def test_substitute_many_rides_the_same_scan(encs):
    """persist's fall-back substitution, now a caller of the finder:
    every site a lookup knows is replaced, the rest left as they are."""
    known = {ref: bytes([i % 251 + 1]) * 32
             for i, (_, _, ref) in enumerate(scalar_sites(encs)) if i % 3}
    want = []
    for enc in encs:
        out = bytearray(enc)
        pos = out.find(PREFIX)
        while pos >= 0:
            real = known.get(bytes(out[pos : pos + 32]))
            if real is not None and pos + 32 <= len(out):
                out[pos : pos + 32] = real
            pos = out.find(PREFIX, pos + 32)
        want.append(bytes(out))
    assert _substitute_many(list(encs), known.get) == want


# ----------------------------------------------------------- pack cases

_PACK_CASES = {
    "no_site": dict(refs=(0, 0)),
    "one_node": dict(n=1, start=0),
    "one_node_with_refs": dict(n=1, start=8, refs=(3, 4)),
    "first_window": dict(start=0, p_resolved=0, p_ext=0),
    "all_resolved": dict(p_resolved=1.0, p_ext=0, p_opaque=0),
    "all_in_flight": dict(p_resolved=0, p_ext=1.0, p_opaque=0),
    "all_opaque": dict(p_opaque=1.0),
    "four_rate_classes": dict(sizes=(60, 200, 330, 500), n=200),
    "wide": dict(n=600, start=300, refs=(0, 17)),
}
_PACK_CASES.update({f"random_{s}": dict() for s in range(8)})


@pytest.mark.parametrize("name", list(_PACK_CASES))
def test_pack_matches_the_scalar_pack(name):
    w = _Window(seed=sum(map(ord, name)), **_PACK_CASES[name])
    pack = w.pack()
    w.check(pack)
    # in-flight children stay placeholder bytes, resolved ones do not
    blob = b"".join(pack.to_resolve.values())
    assert all(key in blob for key in pack.ext_refs)
    assert all(key not in blob for key in w.committer._resolved_global)


@pytest.mark.parametrize("depth", range(1, 18))
def test_depth_of_a_dag(depth):
    """A chain ``depth`` deep under a fan of side branches: the pass
    over the site table reads what the ascending scan read."""
    rng = random.Random(depth)
    c = WindowCommitter(Storages(), EMPTY_TRIE_HASH, hasher=host_hasher)
    chain = []
    n = 0
    for level in range(depth):
        kids = [chain[-1]] if chain else []
        # shallow side leaves (none under the chain's own leaf)
        for _ in range(rng.randrange(0, 3) if chain else 0):
            c._staged[ph(n)] = _node(rng, [])
            kids.append(ph(n))
            n += 1
        rng.shuffle(kids)
        c._staged[ph(n)] = _node(rng, kids)
        chain.append(ph(n))
        n += 1
    pack = c._pack_sites(0, n)
    want = scalar_pack(c._staged, 0, n, {}, {})
    assert pack.max_depth == want[4] == depth
    _assert_pack_equal(pack, want, [c._staged[ph(i)] for i in range(n)])


def test_counters_above_2_32():
    base = (1 << 32) + 1000
    w = _Window(seed=5, n=40, start=0, p_resolved=0, p_ext=0, p_opaque=0)
    c = w.committer
    # the same window, re-staged at counters past 32 bits
    shifted = {}
    for i in range(40):
        enc = c._staged[ph(i)]
        for j in range(i):
            enc = enc.replace(ph(j), ph(base + j))
        shifted[ph(base + i)] = enc
    c._staged.clear()
    c._staged.update(shifted)
    pack = c._pack_sites(base, base + 40)
    _assert_pack_equal(
        pack, scalar_pack(c._staged, base, base + 40, {}, {}),
        list(shifted.values()))
    assert pack.met["local"] > 0 and pack.met["opaque"] == 0


def test_gaps_in_the_counter_range_are_skipped():
    """Another session's counters inside the range: not staged here,
    not packed, and a ref to one is left as it is."""
    w = _Window(seed=11, n=80, start=10)
    c = w.committer
    for i in range(w.start + 3, w.end, 7):
        del c._staged[ph(i)]
    w.check()


@pytest.mark.parametrize("where", ["earlier_window", "forward_in_range"])
def test_foreign_session_staged_child_raises(where):
    w = _Window(seed=3, n=30, start=10)
    c = w.committer
    if where == "earlier_window":
        # staged, but neither resolved nor in flight
        orphan = ph(4)
        del c._inflight_rows[orphan]
    else:
        orphan = ph(w.end - 1)  # a parent that names a LATER node
    victim = ph(w.start + 5)
    c._staged[victim] = _node(random.Random(1), [orphan])
    with pytest.raises(AssertionError):
        w.oracle()
    with pytest.raises(AssertionError, match="unresolvable placeholder"):
        w.pack()


def test_child_resolved_between_the_two_probes_is_spliced():
    """The collector publishes a window's hashes BEFORE it drops its
    in-flight rows. A child that is in neither map at the first two
    probes and resolved at the re-check is spliced: not an ext ref,
    not left raw."""
    w = _Window(seed=21, n=30, start=10, p_ext=0.6, p_resolved=0.1)
    c = w.committer
    late = {key: bytes([7]) * 32 for key in list(c._inflight_rows)[:3]}

    class Dropping(dict):
        def get(self, key, default=None):
            if key in late:  # persisted meanwhile: published, then dropped
                c._resolved_global[key] = late[key]
                return default
            return dict.get(self, key, default)

    c._inflight_rows = Dropping(c._inflight_rows)
    for key in late:
        dict.pop(c._inflight_rows, key)
    want_resolved = {**c._resolved_global, **late}
    want = scalar_pack(c._staged, w.start, w.end, want_resolved,
                       dict(c._inflight_rows))
    pack = w.pack()
    w.check(pack, want)
    blob = b"".join(pack.to_resolve.values())
    assert not set(late) & set(pack.ext_refs)
    assert all(key not in blob for key in late)
    assert any(real in blob for real in late.values())


def _two_window_committer(kill_pack):
    from khipu_tpu.domain.account import Account, address_key

    c = WindowCommitter(Storages(), EMPTY_TRIE_HASH, hasher=host_hasher)
    jobs = []
    for lo, hi in ((0, 40), (40, 90)):
        trie = c.account_trie
        for i in range(lo, hi):
            trie = trie.put(address_key(i.to_bytes(20, "big")),
                            Account(nonce=i, balance=10**18 + i).encode())
        c.account_trie = trie
        jobs.append(c.seal())
    c.pack_and_dispatch(jobs[0])
    if kill_pack:
        plan = FaultPlan(seed=1, rules=[
            FaultRule("collector.pack", "die", times=1)])
        with active(plan), pytest.raises(InjectedDeath):
            c.pack_and_dispatch(jobs[1])
        assert not jobs[1]._packed
    c.pack_and_dispatch(jobs[1])
    return c, jobs[1]


def test_pack_is_idempotent_across_a_death_at_collector_pack():
    _, clean = _two_window_committer(kill_pack=False)
    _, rerun = _two_window_committer(kill_pack=True)
    assert rerun._packed
    assert list(rerun.to_resolve.items()) == list(clean.to_resolve.items())
    assert rerun.mapping == clean.mapping
    assert set(rerun.mapping) == set(rerun.to_resolve)


# ------------------------------------------------- dispatch-build cases

class _Captured(BaseException):
    """Carries the dispatch's inputs out past every handler."""


class _Capture:
    """Stands in for the compile cache: hands out a program that keeps
    its inputs and stops the dispatch there."""

    def lookup(self, sig, rounds, use_jnp, ext_rows=0):
        self.sig = sig

        def run(*inputs):
            self.inputs = inputs
            raise _Captured

        return run, 0.0

    def subs(self):
        """{class: [(row, off, child_gpos)]} as uploaded, in (row, off)
        order, the padding (the row past the class's last, which the
        device drops) taken out of the end."""
        n = len(self.sig)
        out = {}
        for c, (nb, nrows, nsubs, _) in enumerate(self.sig):
            row, off, child = (
                np.asarray(a) for a in
                self.inputs[n + 3 * c : n + 3 * c + 3])
            assert row.shape == off.shape == child.shape == (nsubs,)
            assert row.dtype == off.dtype == child.dtype == np.int32
            real = row < nrows
            assert (row[~real] == nrows).all() and not real[real.sum():].any()
            assert not off[~real].any() and not child[~real].any()
            out[nb] = list(zip(row[real].tolist(), off[real].tolist(),
                               child[real].tolist()))
        return out

    def encodings(self):
        return [np.asarray(a) for a in self.inputs[: len(self.sig)]]


def _dispatch(monkeypatch, to_resolve, depth, ext=None, sites=None):
    cap = _Capture()
    monkeypatch.setattr(fused, "_build_fused", cap)
    with pytest.raises(_Captured):
        fused.fused_submit(to_resolve, {}, PREFIX, use_jnp=True,
                           depth=depth, ext=ext, sites=sites)
    return cap


_BUILD_CASES = {
    "no_site": dict(refs=(0, 0)),
    "one_node": dict(n=1, start=0),
    "first_window": dict(start=0, p_resolved=0, p_ext=0),
    "ext_refs": dict(p_ext=0.5),
    "four_rate_classes": dict(sizes=(60, 200, 330, 500), n=200,
                              refs=(0, 5)),
    "five_rate_classes": dict(sizes=(60, 200, 330, 500, 600), n=100,
                              refs=(0, 4)),
}
_BUILD_CASES.update({f"random_{s}": dict() for s in range(4)})


@pytest.mark.parametrize("handed", ["sites_handed", "scans_itself"])
@pytest.mark.parametrize("name", list(_BUILD_CASES))
def test_dispatch_build_matches_the_scalar_build(monkeypatch, name, handed):
    w = _Window(seed=sum(map(ord, name)) + 1, **_BUILD_CASES[name])
    pack = w.pack()
    ext_pos = {key: 3 * i + 1 for i, key in enumerate(pack.ext_refs)}
    ext = (np.zeros((fused.EXT_HELD_ROWS, 32), np.uint8), ext_pos)
    sites = pack.subs(ext_pos) if handed == "sites_handed" else None
    cap = _dispatch(monkeypatch, pack.to_resolve, pack.max_depth,
                    ext=ext if ext_pos else None, sites=sites)
    assert cap.subs() == scalar_subs(pack.to_resolve, ext_pos, cap.sig)
    # the encoding buffers: each node's bytes, the multi-rate pad bits
    rows = {}
    for p, enc in pack.to_resolve.items():
        rows.setdefault(len(enc) // RATE + 1, []).append(enc)
    for (nb, nrows, _, _), buf in zip(cap.sig, cap.encodings()):
        assert buf.shape == (nrows, nb * RATE)
        for r, enc in enumerate(rows.get(nb, [])):
            want = bytearray(enc.ljust(nb * RATE, b"\0"))
            want[len(enc)] ^= 0x01
            want[-1] ^= 0x80
            assert buf[r].tobytes() == bytes(want)
        filler = bytearray(nb * RATE)
        filler[0] ^= 0x01
        filler[-1] ^= 0x80
        assert buf[nrows - 1].tobytes() == bytes(filler)


def test_lone_dispatch_takes_keys_that_are_no_counters(monkeypatch):
    """``fused_submit`` for a caller that hands no sites and whose keys
    are not all ``prefix + counter`` (or whose counters pass 63 bits):
    matched by their bytes, as the scalar build matched every site."""
    rng = random.Random(2)
    wide = PREFIX + b"\x01" + bytes(13)
    odd = b"\xaa" * 32
    short = b"k"
    to_resolve = {
        ph(0): _node(rng, []),
        wide: _node(rng, [ph(0)]),
        odd: _node(rng, [wide, ph(0)]),
        short: _node(rng, [wide, odd, PREFIX + _filler(rng, 14)]),
        ph(9): _node(rng, [ph(0), wide], size=300),
    }
    cap = _dispatch(monkeypatch, to_resolve, 3)
    assert cap.subs() == scalar_subs(to_resolve, {}, cap.sig)
    assert sum(map(len, cap.subs().values())) == 6


@pytest.mark.parametrize("seed", range(3))
def test_fused_digests_from_handed_sites_match_the_host_hasher(seed):
    """End to end on the jnp backend: a window packed from the table
    and dispatched with the sites handed resolves to the level loop's
    digests."""
    from khipu_tpu.trie.deferred import _substitute_bytes
    from khipu_tpu.trie.fused import fused_submit, topo_levels

    w = _Window(seed=seed, n=40, start=0, p_resolved=0, p_ext=0,
                p_opaque=0.1)
    pack = w.pack()
    got = fused_submit(pack.to_resolve, {}, PREFIX, use_jnp=True,
                       depth=pack.max_depth, sites=pack.subs({})).collect()
    want = {}
    for level in topo_levels(pack.deps()):
        digests = host_hasher(
            [_substitute_bytes(pack.to_resolve[p], want) for p in level])
        want.update(zip(level, digests))
    assert got == want
