"""The source of a fast-sync state download: a state trie of seeded
accounts built on the host, and the in-memory peer that serves it.

The trie is ``bulk_build`` with the host hasher over account leaves
(no storage tries, no code). The peer answers ``fetch(hashes)`` from the
trie's node map and forges a seeded 1 in ``forge_one_in`` of its answers
on their first request (one flipped byte); a retry is answered
truthfully, as a second, honest peer would. Requests of fewer than
``forge_min_request`` hashes are never forged, so that no request comes
back without a single good node (the syncer treats that as a dead peer
set and gives up). Every ``slice_requests``-th request (the driver sets
it; 0: never) the peer keeps one row of clocks (``Peer.mark``), so that a
window's speed can be read slice by slice.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List, Tuple

import numpy as np


class WindowClosed(Exception):
    """Raised by the peer when the measured window's time is up."""


def build_source(accounts: int, seed: int, log=None) -> Tuple[bytes, Dict[bytes, bytes]]:
    from khipu_tpu.domain.account import Account, address_key
    from khipu_tpu.trie.bulk import bulk_build, host_hasher

    rng = np.random.default_rng([seed, 0x736E6170])
    addr = rng.integers(0, 256, (accounts, 20), dtype=np.uint8)
    addr[:, 16:] = np.arange(accounts, dtype=">u4").view(np.uint8).reshape(
        accounts, 4)
    balance = rng.integers(1, 1 << 62, accounts).tolist()
    nonce = rng.integers(0, 1 << 10, accounts).tolist()
    pairs = [
        (address_key(addr[i].tobytes()),
         Account(nonce=nonce[i], balance=balance[i]).encode())
        for i in range(accounts)
    ]
    root, nodes = bulk_build(pairs, hasher=host_hasher)
    return root, nodes


def child_refs(enc: bytes) -> List[bytes]:
    """The 32-byte child hashes an account-trie node refers to, in slot
    order: the benchmark's own reading of the node's RLP (a branch is a
    list of 17 items, an extension a list of 2 whose path lacks the leaf
    flag; a leaf refers to nothing here: no storage tries, no code)."""
    b0 = enc[0]
    pos = 1 if b0 <= 0xF7 else 1 + (b0 - 0xF7)
    items = []
    while pos < len(enc):
        c = enc[pos]
        if c < 0x80:
            size, body = 1, pos
        elif c <= 0xB7:
            size, body = 1 + (c - 0x80), pos + 1
        elif c <= 0xBF:
            ll = c - 0xB7
            n = int.from_bytes(enc[pos + 1: pos + 1 + ll], "big")
            size, body = 1 + ll + n, pos + 1 + ll
        else:
            raise ValueError("inline child node: not an account trie")
        items.append((pos, size, body))
        pos += size
    if len(items) == 17:
        return [enc[p + 1: p + 33] for p, size, _ in items[:16] if size == 33]
    if len(items) != 2:
        raise ValueError(f"a trie node of {len(items)} items")
    (_, _, path_body), (p, size, _) = items
    if enc[path_body] & 0x20 or size != 33:
        return []
    return [enc[p + 1: p + 33]]


def download_order(root: bytes, nodes: Dict[bytes, bytes]):
    """The order in which ``StateSyncer`` meets the trie's nodes (it pops
    the front of its pending list and appends children: breadth first),
    and for each node the position of the node that revealed it."""
    order, parent = [root], [-1]
    i = 0
    while i < len(order):
        for h in child_refs(nodes[order[i]]):
            order.append(h)
            parent.append(i)
        i += 1
    if len(order) != len(nodes) or len(set(order)) != len(order):
        raise RuntimeError(
            f"walk met {len(order)} nodes, the trie has {len(nodes)}")
    return order, parent


class Source:
    """One seed's source trie, held in download order."""

    def __init__(self, root: bytes, hashes: np.ndarray, lens: np.ndarray,
                 blob: np.ndarray, parent: np.ndarray):
        self.root = root
        self.hashes = hashes            # u8[N, 32]
        self.lens = lens.astype(np.int64)
        self.starts = np.concatenate(([0], np.cumsum(self.lens)[:-1]))
        self.blob = blob                # u8[sum(lens)]
        self.parent = parent            # i32[N]; -1 for the root

    def __len__(self) -> int:
        return len(self.lens)

    def keys(self, lo: int = 0, hi: int = None) -> List[bytes]:
        raw = self.hashes[lo:hi].tobytes()
        return [raw[i: i + 32] for i in range(0, len(raw), 32)]

    def nodes(self) -> Dict[bytes, bytes]:
        blob, ends = self.blob.tobytes(), (self.starts + self.lens).tolist()
        out, pos = {}, 0
        for h, end in zip(self.keys(), ends):
            out[h] = blob[pos:end]
            pos = end
        return out

    def resume_point(self, remaining: int):
        """(done, pending_end): the syncer's state after its first
        ``done = N - remaining`` downloads. Its pending list is then
        positions [done, pending_end): every node already revealed by a
        downloaded parent and not yet downloaded."""
        done = max(1, len(self) - int(remaining))
        pending_end = int(np.searchsorted(self.parent, done, side="left"))
        return done, pending_end

    def padded_rows(self, idx: np.ndarray, width: int) -> np.ndarray:
        """Rows ``idx`` as u8[len(idx), width] in Keccak's multi-rate
        padding (0x01 after the data, 0x80 on the last byte)."""
        lens, starts = self.lens[idx], self.starts[idx]
        cols = np.arange(width)
        at = np.minimum(starts[:, None] + cols[None, :], len(self.blob) - 1)
        out = self.blob[at]
        out[cols[None, :] >= lens[:, None]] = 0
        out[np.arange(len(idx)), lens] = 0x01
        out[:, width - 1] ^= 0x80
        return out


def save_source(cache_file: str, root: bytes, nodes: Dict[bytes, bytes]) -> None:
    order, parent = download_order(root, nodes)
    tmp = cache_file + ".tmp.npz"
    np.savez(
        tmp, root=np.frombuffer(root, dtype=np.uint8),
        hashes=np.frombuffer(b"".join(order), dtype=np.uint8),
        lens=np.array([len(nodes[k]) for k in order], dtype=np.int32),
        blob=np.frombuffer(b"".join(nodes[k] for k in order), dtype=np.uint8),
        parent=np.array(parent, dtype=np.int32))
    os.replace(tmp, cache_file)


def load_source(cache_file: str) -> Source:
    with np.load(cache_file) as z:
        return Source(z["root"].tobytes(), z["hashes"].reshape(-1, 32),
                      z["lens"], z["blob"], z["parent"])


def main(argv) -> int:
    """``python snapshot.py '<json>'``: build one seed's source trie into
    ``out``. Run by the driver as a child process (JAX held to the CPU
    there) while the parent compiles the mirror's programs."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    a = json.loads(argv[1])
    root, nodes = build_source(a["accounts"], a["seed"])
    save_source(a["out"], root, nodes)
    return 0


class Peer:
    """The fetch callback. Counts what it handed over truthfully and
    keeps every forged value it ever sent."""

    def __init__(self, nodes: Dict[bytes, bytes], seed: int,
                 forge_one_in: int, forge_min_request: int = 8):
        self.nodes = nodes
        self.forge_one_in = int(forge_one_in)
        self.forge_min_request = int(forge_min_request)
        self.slice_requests = 0
        self.slices: List[Tuple[float, float, float, int, int]] = []
        self.pick = seed % self.forge_one_in if self.forge_one_in else -1
        self.forged: Dict[bytes, bytes] = {}
        self.truthful: set = set()
        self.deadline = None          # perf_counter; None = no limit
        self.seconds = 0.0            # time spent inside fetch
        self.requests = 0

    def _forge(self, h: bytes, value: bytes) -> bytes:
        pos = h[5] % len(value)
        return value[:pos] + bytes([value[pos] ^ 0x40]) + value[pos + 1:]

    def mark(self, now: float = None) -> None:
        """One row of the window's clock, read on the calling thread
        (the driver thread: ``fetch`` is the syncer's call-back): wall,
        that thread's CPU seconds, the whole process's, requests
        answered and nodes handed over truthfully so far."""
        self.slices.append((
            time.perf_counter() if now is None else now, time.thread_time(),
            time.process_time(), self.requests, len(self.truthful)))

    def fetch(self, hashes: List[bytes]) -> Dict[bytes, bytes]:
        t0 = time.perf_counter()
        if self.deadline is not None and t0 >= self.deadline:
            raise WindowClosed()
        out = {}
        may_forge = (self.forge_one_in
                     and len(hashes) >= self.forge_min_request)
        for h in hashes:
            value = self.nodes.get(h)
            if value is None:   # a hash this trie never had: no answer
                continue
            if (may_forge and h not in self.forged and
                    int.from_bytes(h[:4], "big") % self.forge_one_in
                    == self.pick):
                self.forged[h] = out[h] = self._forge(h, value)
            else:
                out[h] = value
                self.truthful.add(h)
        self.requests += 1
        t1 = time.perf_counter()
        self.seconds += t1 - t0
        if self.slice_requests and self.requests % self.slice_requests == 0:
            self.mark(t1)
        return out


if __name__ == "__main__":
    sys.exit(main(sys.argv))
