"""The fused fixpoint's substitution stage (trie/fused.py ``subst_plan``
/ ``substitute``) against the host: generated windows resolved by the
program (jnp backend, CPU) and by ``_substitute_many`` + host Keccak,
one node at a time, children first. Equal digests, equal final
encodings byte for byte, the padding row untouched; and what the
dispatch build promises the device about its ``(row, off)`` arrays."""

import random

import numpy as np
import pytest

from khipu_tpu.base.crypto import keccak256
from khipu_tpu.trie import fused
from khipu_tpu.trie.deferred import _PLACEHOLDER_PREFIX as PREFIX
from khipu_tpu.trie.deferred import _substitute_many

RATE = fused.RATE
EXT_BASE = 1 << 40  # counters of the ext tile's keys: no node's own


def ph(i: int) -> bytes:
    return PREFIX + i.to_bytes(32 - len(PREFIX), "big")


def _filler(rng, n: int) -> bytes:
    # never the prefix's first byte: no site but the ones placed
    return bytes(rng.choice(range(1, 0xFE)) for _ in range(n))


class Window:
    """``specs``: one ``(length, [(off, child)])`` a node; ``child`` is
    an earlier node's index, or ``("ext", row)`` for a row of the ext
    tile."""

    def __init__(self, seed, specs, ext_rows=0):
        rng = random.Random(seed)
        self.tile = np.frombuffer(
            _filler(rng, 32 * max(ext_rows, 1)), np.uint8
        ).reshape(-1, 32).copy()
        self.ext_pos = {ph(EXT_BASE + r): r for r in range(ext_rows)}
        self.to_resolve = {}
        for i, (length, sites) in enumerate(specs):
            enc = bytearray(_filler(rng, length))
            for off, child in sites:
                key = (ph(EXT_BASE + child[1]) if isinstance(child, tuple)
                       else ph(child))
                assert isinstance(child, tuple) or child < i
                enc[off : off + 32] = key
            self.to_resolve[ph(i)] = bytes(enc)
        depth = []
        for _, sites in specs:
            depth.append(1 + max(
                (depth[c] for _, c in sites if not isinstance(c, tuple)),
                default=0))
        self.depth = max(depth)

    def oracle(self):
        """(ph -> digest, ph -> final encoding) by the host, children
        first (a child has a lower index than its parent)."""
        known = {key: self.tile[r].tobytes()
                 for key, r in self.ext_pos.items()}
        final = {}
        for key, enc in self.to_resolve.items():
            final[key] = _substitute_many([enc], known.get)[0]
            known[key] = keccak256(final[key])
        return {k: known[k] for k in self.to_resolve}, final

    def dispatch(self, held=None):
        ext = (self.tile, self.ext_pos) if self.ext_pos else None
        return fused.fused_submit(
            self.to_resolve, {}, PREFIX, use_jnp=True, depth=self.depth,
            ext=ext, held=held)


def check(window, job):
    """The dispatch's digests and final encodings against the host's;
    every padding row as the dispatch build made it."""
    digests, final = window.oracle()
    assert job.collect() == digests
    widths = sorted({1, 2, 3, 4} | {
        len(e) // RATE + 1 for e in window.to_resolve.values()})
    for nb, buf, (rows, _) in zip(widths, job.encs, job.class_rows):
        buf = np.asarray(buf)
        assert buf.shape[1] == nb * RATE
        for r, key in enumerate(rows):
            want = bytearray(final[key].ljust(nb * RATE, b"\0"))
            want[len(final[key])] ^= 0x01
            want[-1] ^= 0x80
            assert buf[r].tobytes() == bytes(want), (nb, r)
        filler = bytearray(nb * RATE)
        filler[0] ^= 0x01
        filler[-1] ^= 0x80
        assert len(rows) < buf.shape[0]
        for r in range(len(rows), buf.shape[0]):
            assert buf[r].tobytes() == bytes(filler), (nb, r)


def _leaves(n, length=60):
    return [(length, [])] * n


def _random_specs(seed, n=90):
    rng = random.Random(seed)
    specs = []
    for i in range(n):
        length = rng.choice((40, 100, 135, 136, 200, 271, 300, 407, 420,
                             543))
        room = length // 33
        offs = sorted(rng.sample(range(room), rng.randrange(0, room + 1))
                      ) if i else []
        sites = []
        for slot in offs:
            off = 33 * slot + rng.randrange(0, 2)
            child = (("ext", rng.randrange(8)) if rng.random() < 0.2
                     else rng.randrange(i))
            sites.append((off, child))
        specs.append((length, sites))
    return specs


CASES = {
    # the site's last byte is the encoding's, and the row's last before
    # the pad byte, in the narrowest class and the widest
    "site_ends_on_the_last_byte": (
        _leaves(2) + [(135, [(103, 0)]), (543, [(0, 1), (511, 2)])], 0),
    # 32 apart (touching) and 33 apart (a branch's children)
    "adjacent_sites": (
        _leaves(3) + [(135, [(3, 0), (35, 1), (68, 2)]),
                      (300, [(10, 3), (42, 0), (74, 1), (107, 2)])], 0),
    "a_class_with_no_site": (
        _leaves(3) + [(200, [])] * 3 + [(100, [(5, 0), (40, 4)])], 0),
    "no_site_at_all": (_leaves(5) + [(500, [])], 0),
    "all_padding_but_one": (_leaves(4) + [(420, [(77, 2)])], 0),
    "ext_children": (
        _leaves(2) + [(135, [(1, ("ext", 3)), (50, 0)]),
                      (543, [(3, ("ext", 0)), (36, 2), (300, ("ext", 7))]),
                      (271, [(200, ("ext", 7))])], 8),
    # as many sites as a row of the class has room for
    "full_rows": (
        _leaves(16) + [(135, [(3 + 33 * j, j) for j in range(4)]),
                       (543, [(3 + 33 * j, j) for j in range(16)]),
                       (543, [(31 + 32 * j, 15 - j) for j in range(16)])],
        0),
    "a_fifth_class": (
        _leaves(3) + [(600, [(0, 0), (290, 1), (567, 2)]),
                      (679, [(647, 3)])], 0),
    "a_chain": ([(60, [])] + [
        (rng_len, [(7, i)]) for i, rng_len in
        enumerate((60, 140, 300, 420, 100, 543, 135, 271))], 0),
}
CASES.update({f"random_{s}": (_random_specs(s), 8) for s in range(6)})


@pytest.mark.parametrize("name", list(CASES))
def test_substitution_matches_the_host(name):
    specs, ext_rows = CASES[name]
    window = Window(sum(map(ord, name)), specs, ext_rows)
    check(window, window.dispatch())


def test_a_held_rows_bucket_keeps_the_smaller_window_exact():
    """A class whose rows bucket an earlier, larger window grew: the
    smaller window runs in the held bucket, its padding (rows and
    substitutions) further from its live counts."""
    held = fused.HeldBuckets()
    big = Window(1, _leaves(40) + [
        (100, [(3, i), (40, i + 1)]) for i in range(30)])
    check(big, big.dispatch(held))
    rows_held = held.snapshot()[(1, "rows")]
    small = Window(2, _leaves(3) + [(135, [(103, 0)]), (100, [(9, 3)])])
    job = small.dispatch(held)
    assert held.snapshot()[(1, "rows")] == rows_held > 16
    assert np.asarray(job.encs[0]).shape[0] == rows_held
    check(small, job)


class _Inputs:
    """The compile cache, handing out programs that keep their inputs."""

    def __init__(self):
        self.calls = []

    def lookup(self, sig, rounds, use_jnp, ext_rows=0):
        run, dt = fused.compile_cache.lookup(sig, rounds, use_jnp, ext_rows)

        def keeping(*inputs):
            self.calls.append((sig, inputs))
            return run(*inputs)

        return keeping, dt


@pytest.mark.parametrize("name", list(CASES))
def test_emitted_sites_are_what_the_device_is_promised(monkeypatch, name):
    """``indices_are_sorted`` / ``unique_indices`` of the one scatter,
    and the expand network's order: per class the real ``(row, off)``
    are strictly increasing as flat positions and 32 apart or more,
    inside their row, and the padding is one run at the end that names
    the row past the last."""
    specs, ext_rows = CASES[name]
    keep = _Inputs()
    monkeypatch.setattr(fused, "_build_fused", keep)
    window = Window(sum(map(ord, name)), specs, ext_rows)
    window.dispatch()
    (sig, inputs), = keep.calls
    n = len(sig)
    live = 0
    for c, (nb, nrows, nsubs, _) in enumerate(sig):
        row, off, child = (np.asarray(a, np.int64)
                           for a in inputs[n + 3 * c : n + 3 * c + 3])
        assert row.shape == (nsubs,)
        real = int((row < nrows).sum())
        live += real
        assert (row[real:] == nrows).all()
        assert not off[real:].any() and not child[real:].any()
        flat = row[:real] * nb * RATE + off[:real]
        assert (np.diff(flat) >= 32).all()
        assert (off[:real] >= 0).all()
        assert (off[:real] + 32 < nb * RATE).all()
    assert live == sum(len(sites) for _, sites in specs)


def test_sites_out_of_order_or_overlapping():
    rows = np.array([2, 0, 0, 1])
    offs = np.array([5, 40, 3, 100])
    kids = np.array([7, 8, 9, 10])
    r, o, c = fused.sorted_sites(rows, offs, kids, 136)
    assert (r.tolist(), o.tolist(), c.tolist()) == (
        [0, 0, 1, 2], [3, 40, 100, 5], [9, 8, 10, 7])
    for bad_rows, bad_offs in (
        ([0, 0], [3, 34]),  # overlap by one byte
        ([0, 0], [3, 3]),  # the same site twice
        ([0], [104]),  # runs into the pad byte's place
        ([1], [-1]),
    ):
        with pytest.raises(fused.FusedUnsupported):
            fused.sorted_sites(np.array(bad_rows), np.array(bad_offs),
                               np.zeros(len(bad_rows), np.int64), 136)
    none = np.empty(0, np.int64)
    assert fused.sorted_sites(none, none, none, 136)[0].size == 0


def _primitives(jaxpr, out=None):
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for sub in eqn.params.values():
            for j in sub if isinstance(sub, (list, tuple)) else [sub]:
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


@pytest.mark.parametrize("nb", [1, 2, 3, 4, 5])
def test_a_round_scatters_and_sorts_nothing(nb):
    """The two scatters of a class (children and offsets by slot) run
    once, in ``subst_plan``, with both promises and out-of-range
    entries dropped; ``substitute``, the loop's part, has none."""
    import jax
    import jax.numpy as jnp

    nrows, nsubs = 16, 64
    i32 = jax.ShapeDtypeStruct((nsubs,), jnp.int32)
    plan = jax.make_jaxpr(
        lambda r, o, c: fused.subst_plan(nb, nrows, r, o, c))(i32, i32, i32)
    scatters = [e for e in _primitives(plan.jaxpr)
                if e.primitive.name.startswith("scatter")]
    assert len(scatters) == 2
    for e in scatters:
        assert e.params["indices_are_sorted"] and e.params["unique_indices"]
        assert "FILL_OR_DROP" in str(e.params["mode"])
    assert not [e for e in _primitives(plan.jaxpr)
                if e.primitive.name == "sort"]
    shapes = jax.eval_shape(
        lambda r, o, c: fused.subst_plan(nb, nrows, r, o, c), i32, i32, i32)
    table, covered, masks = shapes
    assert table.shape == (nrows, (nb * RATE - 1) // 32)
    assert covered.shape == (nrows, nb * RATE)
    assert len(masks) == (nb * RATE - 33).bit_length()
    round_ = jax.make_jaxpr(
        lambda e, p, g: fused.substitute(e, p, g))(
        jax.ShapeDtypeStruct((nrows, nb * RATE), jnp.uint8), shapes,
        jax.ShapeDtypeStruct((100, 32), jnp.uint8))
    names = {e.primitive.name for e in _primitives(round_.jaxpr)}
    assert not {n for n in names if n.startswith(("scatter", "sort"))}
    assert "gather" in names


def test_scope_map_names_the_stage():
    """``fused_subst_share_of_busy.sync`` reads instructions mapped to
    ``fused.subst``: the program still has them, the prologue's two
    scatters among them, beside the hash and the gather."""
    window = Window(5, *CASES["random_0"])
    window.dispatch()
    per_program = fused.scope_map()
    assert per_program
    for label, scopes in per_program.items():
        stages = set(scopes.values())
        assert {"fused.hash", "fused.gather", "fused.subst"} <= stages
        # (a scatter's combiner has parameters named after it: no stage)
        scatters = [scopes[name] for name in scopes
                    if name.startswith("scatter") and scopes[name]]
        # two a class; the label has one "/" a class
        assert scatters == ["fused.subst"] * (2 * label.count("/"))


def test_the_sharded_resolver_shares_the_helper():
    """parallel/fused_sharded.py through ``subst_plan`` / ``substitute``:
    the same digests as the host, rows dealt over 8 devices."""
    from khipu_tpu.parallel import fused_sharded
    from khipu_tpu.parallel.mesh import device_mesh

    assert fused_sharded.substitute is fused.substitute
    assert fused_sharded.subst_plan is fused.subst_plan
    specs = [
        (length, [s for s in sites if not isinstance(s[1], tuple)])
        for length, sites in CASES["full_rows"][0] + _random_specs(3, n=60)]
    window = Window(11, specs)
    deps = {ph(i): [ph(c) for _, c in sites]
            for i, (_, sites) in enumerate(specs)}
    got = fused_sharded.fused_resolve_sharded(
        window.to_resolve, deps, PREFIX, device_mesh(8))
    assert got == window.oracle()[0]


def test_roots_of_a_real_session_match_the_host_trie():
    """A deferred-trie session finalized through the fused program:
    the root the host's level loop gives, and a plain host MPT's."""
    from khipu_tpu.storage.datasource import MemoryNodeDataSource
    from khipu_tpu.trie.bulk import host_hasher
    from khipu_tpu.trie.deferred import DeferredMPT, finalize
    from khipu_tpu.trie.mpt import MerklePatriciaTrie

    rng = random.Random(33)
    pairs = [(keccak256(rng.randbytes(8)), rng.randbytes(rng.randrange(1, 80)))
             for _ in range(300)]

    def session():
        d = DeferredMPT(MemoryNodeDataSource())
        for k, v in pairs:
            d = d.put(k, v)
        return d

    plain = MerklePatriciaTrie(MemoryNodeDataSource())
    for k, v in pairs:
        plain = plain.put(k, v)
    by_loop, loop_map = finalize(session(), host_hasher, return_mapping=True)
    by_program, program_map = finalize(
        session(), host_hasher, return_mapping=True, fused=True)
    assert program_map == loop_map
    assert by_program.root_hash == by_loop.root_hash == plain.root_hash
