"""Multi-chip fused window finalize: the fixpoint program under shard_map.

Single-chip `trie/fused.py` resolves a window's whole placeholder DAG in
one dispatch. This module is its SPMD form for a device mesh (SURVEY
§2.8b/c): node rows shard round-robin across the "nodes" axis, each
round every chip hashes ITS rows and `all_gather`s the digest table so
the child substitution (which references arbitrary rows) sees
every digest — the same hash-local/gather-global shape the sharded bulk
build uses for level boundaries (parallel/keccak_sharded.py).

Per round per chip: hash(rows/n_dev) + one all_gather of [rows, 32]
digests over ICI. Work scales 1/n_dev; the gathered table is tiny
(32 B/node) next to the encodings, so the collective stays cheap.

Row assignment is ROUND-ROBIN (global row r -> device r % n_dev, local
slot r // n_dev): padding rows land at every device's local tail.
The substitution itself is trie/fused.py's (subst_plan / substitute):
one helper for both programs.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np

from khipu_tpu.observability.profiler import D2H, H2D, LEDGER
from khipu_tpu.ops.keccak_jnp import RATE
from khipu_tpu.parallel.mesh import AXIS
from khipu_tpu.trie.fused import (
    FusedUnsupported,
    MAX_DEPTH,
    _pow2,
    _scan_sites,
    sorted_sites,
    subst_plan,
    substitute,
    topo_levels,
)


@functools.lru_cache(maxsize=32)
def _build_fused_sharded(sig: Tuple[Tuple[int, int, int], ...],
                         rounds: int, n_dev: int, mesh):
    """sig: per class (nblocks, rows_per_dev, nsubs_per_dev).

    Inputs (leading dim = n_dev, sharded on the nodes axis):
      per class: enc u8[n_dev, rpd, nblocks*RATE]
      per class: rows i32[n_dev, nsubs], offs i32[n_dev, nsubs],
                 child i32[n_dev, nsubs]: each device's sites in (row,
                 off) order, padding last with rows == rows_per_dev
                 (child indices are GLOBAL positions in the gathered
                 digest table)
    Output: per-class digests u8[n_dev, rpd, 32] (gathered layout).
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from khipu_tpu.ops.keccak_jnp import hash_padded_u8 as _hash

    k = len(sig)

    def shard_body(*args):
        # shards keep the (now size-1) leading device axis: drop it
        encs = [a[0] for a in args[:k]]
        subs = [a[0] for a in args[k:]]

        def all_digests(encs):
            local = jnp.concatenate(
                [_hash(encs[c], sig[c][0]) for c in range(k)], axis=0
            )  # [sum_c rpd_c, 32]
            return jax.lax.all_gather(local, AXIS, tiled=True)

        plans = [
            subst_plan(sig[c][0], sig[c][1], *subs[3 * c : 3 * c + 3])
            for c in range(k)
        ]

        def body(_, encs):
            G = all_digests(encs)
            return [substitute(encs[c], plans[c], G) for c in range(k)]

        encs = jax.lax.fori_loop(0, rounds, body, encs)
        return all_digests(encs)  # replicated full table

    in_specs = tuple([P(AXIS)] * (4 * k))
    run = jax.jit(
        shard_map(
            shard_body, mesh=mesh, in_specs=in_specs,
            # all_gather(tiled) replicates the table on every device;
            # the vma checker can't infer that statically
            out_specs=P(None, None), check_vma=False,
        )
    )
    return run


def fused_resolve_sharded(
    to_resolve: Dict[bytes, bytes],
    deps: Dict[bytes, List[bytes]],
    prefix: bytes,
    mesh,
) -> Dict[bytes, bytes]:
    """Resolve placeholder -> Keccak-256 hash for every entry across the
    mesh. Same contract as trie.fused.fused_resolve."""
    if not to_resolve:
        return {}
    depth = len(topo_levels(deps))
    if depth > MAX_DEPTH:
        raise FusedUnsupported(f"DAG depth {depth} > {MAX_DEPTH}")

    n_dev = int(np.prod(mesh.devices.shape))
    phs = list(to_resolve)

    classes: Dict[int, List[bytes]] = {c: [] for c in (1, 2, 3, 4)}
    for ph in phs:
        nb = len(to_resolve[ph]) // RATE + 1
        classes.setdefault(nb, []).append(ph)
    class_list = sorted(classes)

    # rows per device per class; +n_dev guarantees a spare (padding)
    # local tail row on EVERY device under round-robin assignment
    rpd: Dict[int, int] = {}
    for nb in class_list:
        # _pow2 with floor 16*n_dev returns 16*n_dev*2^k — always a
        # multiple of n_dev, so the per-device split below is exact
        total = _pow2(len(classes[nb]) + n_dev, floor=16 * n_dev)
        rpd[nb] = total // n_dev

    # global digest position in the gathered table:
    # [device d][class c][local slot] with d-major ordering
    sum_rpd = sum(rpd.values())
    offset_c: Dict[int, int] = {}
    acc = 0
    for nb in class_list:
        offset_c[nb] = acc
        acc += rpd[nb]

    def gpos(nb: int, r: int) -> int:
        d, local = r % n_dev, r // n_dev
        return d * sum_rpd + offset_c[nb] + local

    dpos: Dict[bytes, int] = {}
    row_of: Dict[bytes, int] = {}
    for nb in class_list:
        for r, ph in enumerate(classes[nb]):
            dpos[ph] = gpos(nb, r)
            row_of[ph] = r

    # every substitution, from the one site scan (trie/fused.py
    # _scan_sites): where the node that holds it lives, and its
    # child's place in the gathered table
    site_node, site_off, site_child = _scan_sites(to_resolve, prefix, {})
    node_gpos = np.fromiter((dpos[ph] for ph in phs), np.int64, len(phs))
    node_nb = np.fromiter(
        (len(enc) // RATE + 1 for enc in to_resolve.values()),
        np.int64, len(phs))
    node_row = np.fromiter(map(row_of.__getitem__, phs), np.int64, len(phs))
    site_nb = node_nb[site_node]
    site_row = node_row[site_node]
    site_gpos = node_gpos[site_child]

    enc_bufs: List[np.ndarray] = []
    sub_arrays: List[np.ndarray] = []
    sig: List[Tuple[int, int, int]] = []
    for nb in class_list:
        rows = classes[nb]
        width = nb * RATE
        buf = np.zeros((n_dev, rpd[nb], width), dtype=np.uint8)
        # keccak padding on every row (real rows re-pad below)
        buf[:, :, 0] ^= 0x01
        buf[:, :, width - 1] ^= 0x80
        for r, ph in enumerate(rows):
            enc = to_resolve[ph]
            d, local = r % n_dev, r // n_dev
            buf[d, local, :] = 0
            buf[d, local, : len(enc)] = np.frombuffer(enc, dtype=np.uint8)
            buf[d, local, len(enc)] ^= 0x01
            buf[d, local, width - 1] ^= 0x80
        per_dev = []  # each device's (local row, off, child), ordered
        for d in range(n_dev):
            on = (site_nb == nb) & (site_row % n_dev == d)
            per_dev.append(sorted_sites(
                site_row[on] // n_dev, site_off[on], site_gpos[on], width))
        nsubs = _pow2(max(len(s[0]) for s in per_dev) + 1, floor=256)
        # padding names the row past the device's last: dropped there
        subs = np.zeros((3, n_dev, nsubs), dtype=np.int32)
        subs[0] = rpd[nb]
        for d, dev_subs in enumerate(per_dev):
            subs[:, d, : len(dev_subs[0])] = dev_subs
        enc_bufs.append(buf)
        sub_arrays.extend(subs)
        sig.append((nb, rpd[nb], nsubs))

    rounds = _pow2(depth, floor=8)
    run = _build_fused_sharded(tuple(sig), rounds, n_dev, mesh)
    import jax

    # shard dispatch uploads the per-device buffers, the all_gather
    # result comes back as one table — both crossings are ledger sites
    up = sum(b.nbytes for b in enc_bufs) + sum(a.nbytes for a in sub_arrays)
    with LEDGER.transfer("shard.dispatch", H2D, up):
        fut = run(*[*enc_bufs, *sub_arrays])
    with LEDGER.transfer("shard.gather", D2H, int(fut.size)):
        table = np.asarray(jax.device_get(fut))
    out: Dict[bytes, bytes] = {}
    for nb in class_list:
        for r, ph in enumerate(classes[nb]):
            out[ph] = table[gpos(nb, r)].tobytes()
    return out
