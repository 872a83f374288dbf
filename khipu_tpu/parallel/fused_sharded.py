"""Multi-chip fused window finalize: the fixpoint program under shard_map.

Single-chip `trie/fused.py` resolves a window's whole placeholder DAG in
one dispatch. This module is its SPMD form for a device mesh (SURVEY
§2.8b/c): node rows shard round-robin across the "nodes" axis, each
round every chip hashes ITS rows and `all_gather`s the digest table so
the child-substitution scatter (which references arbitrary rows) sees
every digest — the same hash-local/gather-global shape the sharded bulk
build uses for level boundaries (parallel/keccak_sharded.py).

Per round per chip: hash(rows/n_dev) + one all_gather of [rows, 32]
digests over ICI. Work scales 1/n_dev; the gathered table is tiny
(32 B/node) next to the encodings, so the collective stays cheap.

Row assignment is ROUND-ROBIN (global row r -> device r % n_dev, local
slot r // n_dev): padding rows land at every device's local tail, so
each device always owns a spare row for dummy (padding) substitutions.
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
    topo_levels,
)


@functools.lru_cache(maxsize=32)
def _build_fused_sharded(sig: Tuple[Tuple[int, int, int], ...],
                         rounds: int, n_dev: int, mesh):
    """sig: per class (nblocks, rows_per_dev, nsubs_per_dev).

    Inputs (leading dim = n_dev, sharded on the nodes axis):
      per class: enc u8[n_dev, rpd, nblocks*RATE]
      per class: rows32 i32[n_dev, nsubs*32], cols32 i32[n_dev, nsubs*32],
                 child i32[n_dev, nsubs]   (child indices are GLOBAL
                 positions in the gathered digest table)
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

        def body(_, encs):
            G = all_digests(encs)
            out = []
            for c in range(k):
                rows32 = subs[3 * c]
                cols32 = subs[3 * c + 1]
                child = subs[3 * c + 2]
                vals = G[child].reshape(-1)
                out.append(encs[c].at[rows32, cols32].set(vals))
            return out

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
    for nb in class_list:
        for r, ph in enumerate(classes[nb]):
            dpos[ph] = gpos(nb, r)

    # every substitution, from the one site scan (trie/fused.py
    # _scan_sites), grouped by the node that holds it
    site_node, site_off, site_child = _scan_sites(to_resolve, prefix, {})
    node_gpos = np.fromiter((dpos[ph] for ph in phs), np.int64, len(phs))
    subs_of: Dict[bytes, List[Tuple[int, int]]] = {}
    for i, off, cp in zip(site_node.tolist(), site_off.tolist(),
                          node_gpos[site_child].tolist()):
        subs_of.setdefault(phs[i], []).append((off, cp))

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
        per_dev_subs: List[List[Tuple[int, int, int]]] = [
            [] for _ in range(n_dev)
        ]
        for r, ph in enumerate(rows):
            enc = to_resolve[ph]
            d, local = r % n_dev, r // n_dev
            buf[d, local, :] = 0
            buf[d, local, : len(enc)] = np.frombuffer(enc, dtype=np.uint8)
            buf[d, local, len(enc)] ^= 0x01
            buf[d, local, width - 1] ^= 0x80
            for off, cp in subs_of.get(ph, ()):
                per_dev_subs[d].append((local, off, cp))
        nsubs = _pow2(
            max(max((len(s) for s in per_dev_subs), default=0), 1),
            floor=256,
        )
        rows32 = np.empty((n_dev, nsubs * 32), dtype=np.int32)
        cols32 = np.empty((n_dev, nsubs * 32), dtype=np.int32)
        child = np.empty((n_dev, nsubs), dtype=np.int32)
        for d in range(n_dev):
            subs = list(per_dev_subs[d])
            while len(subs) < nsubs:  # dummies hit the local spare row
                subs.append((rpd[nb] - 1, 0, 0))
            for m, (local, off, cp) in enumerate(subs):
                rows32[d, m * 32 : (m + 1) * 32] = local
                cols32[d, m * 32 : (m + 1) * 32] = np.arange(
                    off, off + 32, dtype=np.int32
                )
                child[d, m] = cp
        enc_bufs.append(buf)
        sub_arrays.extend([rows32, cols32, child])
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
