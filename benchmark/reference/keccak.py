"""Keccak-256 (original padding 0x01, as Ethereum uses) over a batch of
messages, vectorised with numpy. The benchmark's own: it imports nothing
of the program, so a sample re-hashed with it is an independent check of
what the program stored."""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

RATE = 136  # bytes absorbed per permutation at 256-bit capacity/2

_RC = np.array([
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
], dtype=np.uint64)

# rotation offsets r[x][y] and the pi permutation, lane index = x + 5*y
_ROT = [[0, 36, 3, 41, 18], [1, 44, 10, 45, 2], [62, 6, 43, 15, 61],
        [28, 55, 25, 21, 56], [27, 20, 39, 8, 14]]


def _rol(a: np.ndarray, n: int) -> np.ndarray:
    if n == 0:
        return a
    return (a << np.uint64(n)) | (a >> np.uint64(64 - n))


def _permute(s: List[np.ndarray]) -> List[np.ndarray]:
    """Keccak-f[1600] on 25 lanes, each a u64 vector over the batch."""
    for rnd in range(24):
        c = [s[x] ^ s[x + 5] ^ s[x + 10] ^ s[x + 15] ^ s[x + 20]
             for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        s = [s[i] ^ d[i % 5] for i in range(25)]
        b = [None] * 25
        for x in range(5):
            for y in range(5):
                b[y + 5 * ((2 * x + 3 * y) % 5)] = _rol(s[x + 5 * y],
                                                        _ROT[x][y])
        s = [b[i] ^ (~b[(i % 5 + 1) % 5 + 5 * (i // 5)]
                     & b[(i % 5 + 2) % 5 + 5 * (i // 5)])
             for i in range(25)]
        s[0] = s[0] ^ _RC[rnd]
    return s


def keccak256_batch(messages: Sequence[bytes]) -> List[bytes]:
    """Digest of every message; messages of one block count are hashed
    together."""
    out: List[bytes] = [b""] * len(messages)
    groups = {}
    for i, m in enumerate(messages):
        groups.setdefault(len(m) // RATE + 1, []).append(i)
    for nblocks, idx in groups.items():
        width = nblocks * RATE
        buf = np.zeros((len(idx), width), dtype=np.uint8)
        for r, i in enumerate(idx):
            m = messages[i]
            buf[r, : len(m)] = np.frombuffer(m, dtype=np.uint8)
            buf[r, len(m)] ^= 0x01
        buf[:, width - 1] ^= 0x80
        lanes = buf.view("<u8").reshape(len(idx), nblocks, RATE // 8)
        state = [np.zeros(len(idx), dtype=np.uint64) for _ in range(25)]
        for blk in range(nblocks):
            for j in range(RATE // 8):
                state[j] = state[j] ^ lanes[:, blk, j]
            state = _permute(state)
        digest = np.stack(state[:4], axis=1).astype("<u8").view(np.uint8)
        for r, i in enumerate(idx):
            out[i] = digest[r].tobytes()
    return out


def keccak256(message: bytes) -> bytes:
    return keccak256_batch([message])[0]
