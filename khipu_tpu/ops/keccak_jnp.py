"""Batched Keccak-256 in pure jax.numpy (runs on TPU, CPU, anywhere).

Design (SURVEY.md §7.2 step 2): performance comes purely from batch
width — the sponge is bitwise-serial per message, so we hash B messages
simultaneously, one message per vector lane. 64-bit lanes are emulated
as (hi, lo) uint32 pairs: the TPU VPU has no 64-bit integer unit, and
all Keccak ops (xor/and/not/rotl) decompose exactly onto u32 pairs.

State layout: 25 lanes x 2 u32 halves, kept as Python lists of 25
arrays each of shape ``batch_shape`` — XLA sees 50 independent
elementwise dataflows and fuses the whole permutation.

Scalar oracle: khipu_tpu.base.crypto.keccak (tests assert bit-equality).
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from khipu_tpu.base.crypto.keccak import ROTATION, ROUND_CONSTANTS
from khipu_tpu.observability.profiler import D2H, H2D, LEDGER

RATE = 136  # keccak-256 rate in bytes
LANES_PER_BLOCK = RATE // 8  # 17 u64 lanes absorbed per block

# (rc_lo, rc_hi) u32 pairs, static Python ints so they fold into the graph.
_RC32 = tuple((rc & 0xFFFFFFFF, rc >> 32) for rc in ROUND_CONSTANTS)


def class_tag(nblocks: int, message_words: int = None) -> str:
    """How a size class reads in the names of the device programs that
    serve it: ``nb<rate blocks>``, plus ``w<message words>`` where the
    rows are stored unpadded and the kernel fuses the pad."""
    return f"nb{nblocks}" + (f"w{message_words}" if message_words else "")


def _rotl64(lo, hi, n: int):
    """Rotate-left a u64 expressed as (lo, hi) u32 halves by static n."""
    n &= 63
    if n == 0:
        return lo, hi
    if n == 32:
        return hi, lo
    if n < 32:
        return (
            (lo << n) | (hi >> (32 - n)),
            (hi << n) | (lo >> (32 - n)),
        )
    m = n - 32
    return (
        (hi << m) | (lo >> (32 - m)),
        (lo << m) | (hi >> (32 - m)),
    )


def _round(lo: List, hi: List, rc_lo, rc_hi) -> Tuple[List, List]:
    """One Keccak-f[1600] round over 25 (lo, hi) u32 lane arrays."""
    # theta
    c_lo = [lo[x] ^ lo[x + 5] ^ lo[x + 10] ^ lo[x + 15] ^ lo[x + 20] for x in range(5)]
    c_hi = [hi[x] ^ hi[x + 5] ^ hi[x + 10] ^ hi[x + 15] ^ hi[x + 20] for x in range(5)]
    for x in range(5):
        r_lo, r_hi = _rotl64(c_lo[(x + 1) % 5], c_hi[(x + 1) % 5], 1)
        d_lo = c_lo[(x - 1) % 5] ^ r_lo
        d_hi = c_hi[(x - 1) % 5] ^ r_hi
        for y in range(5):
            lo[x + 5 * y] = lo[x + 5 * y] ^ d_lo
            hi[x + 5 * y] = hi[x + 5 * y] ^ d_hi
    # rho + pi
    b_lo: List = [None] * 25
    b_hi: List = [None] * 25
    for x in range(5):
        for y in range(5):
            r_lo, r_hi = _rotl64(lo[x + 5 * y], hi[x + 5 * y], ROTATION[x][y])
            idx = y + 5 * ((2 * x + 3 * y) % 5)
            b_lo[idx], b_hi[idx] = r_lo, r_hi
    # chi
    for x in range(5):
        for y in range(5):
            i0, i1, i2 = x + 5 * y, (x + 1) % 5 + 5 * y, (x + 2) % 5 + 5 * y
            lo[i0] = b_lo[i0] ^ (~b_lo[i1] & b_lo[i2])
            hi[i0] = b_hi[i0] ^ (~b_hi[i1] & b_hi[i2])
    # iota
    lo[0] = lo[0] ^ rc_lo
    hi[0] = hi[0] ^ rc_hi
    return lo, hi


_RC_LO_NP = np.asarray([p[0] for p in _RC32], np.uint32)
_RC_HI_NP = np.asarray([p[1] for p in _RC32], np.uint32)


def f1600(lo: List, hi: List, unroll: bool = False) -> Tuple[List, List]:
    """Keccak-f[1600]: 24 rounds via lax.fori_loop (or fully unrolled).

    The loop form keeps the traced graph ~24x smaller (fast compiles);
    rotation amounts stay static inside the body, only the round
    constant is a traced lookup. Constants are created per trace — a
    cached global would leak tracers between jit scopes.
    """
    if unroll:
        for rc_lo, rc_hi in _RC32:
            lo, hi = _round(lo, hi, jnp.uint32(rc_lo), jnp.uint32(rc_hi))
        return lo, hi

    rc_lo_arr = jnp.asarray(_RC_LO_NP)
    rc_hi_arr = jnp.asarray(_RC_HI_NP)

    def body(i, carry):
        clo, chi = carry
        nlo, nhi = _round(list(clo), list(chi), rc_lo_arr[i], rc_hi_arr[i])
        return tuple(nlo), tuple(nhi)

    flo, fhi = jax.lax.fori_loop(0, 24, body, (tuple(lo), tuple(hi)))
    return list(flo), list(fhi)


@functools.partial(jax.jit, static_argnames=("nblocks",))
def absorb(blocks: jax.Array, nblocks: int) -> jax.Array:
    """Absorb ``nblocks`` rate-blocks per message and squeeze 256 bits.

    blocks: uint32[nblocks, 34, B] — per block, 17 lanes x (lo, hi)
            interleaved as [lo0, hi0, lo1, hi1, ...], batch minor.
    returns: uint32[8, B] — digest words [lo0, hi0, .., lo3, hi3].
    """
    # Derive the zero state from the input (x ^ x) rather than
    # jnp.zeros: under shard_map the capacity lanes (17-24, never
    # absorbed) must carry the same varying-over-mesh-axis type as the
    # data lanes or the fori_loop carry fails vma typechecking; XLA
    # folds x^x to 0 so this costs nothing.
    zero = blocks[0, 0] ^ blocks[0, 0]
    lo = [zero] * 25
    hi = [zero] * 25
    for b in range(nblocks):
        for i in range(LANES_PER_BLOCK):
            lo[i] = lo[i] ^ blocks[b, 2 * i]
            hi[i] = hi[i] ^ blocks[b, 2 * i + 1]
        lo, hi = f1600(lo, hi)
    out = []
    for i in range(4):
        out.append(lo[i])
        out.append(hi[i])
    return jnp.stack(out)


def hash_padded_u8(padded_u8, nblocks: int):
    """Traceable batch hash of already multi-rate-padded byte rows:
    u8[N, nblocks*RATE] -> u8[N, 32]. THE shared jnp formulation for
    every fixpoint/sharded consumer (trie/fused.py,
    parallel/fused_sharded.py) — one place owns the bitcast/absorb
    packing."""
    n = padded_u8.shape[0]
    nwords = nblocks * 2 * LANES_PER_BLOCK
    w = jax.lax.bitcast_convert_type(
        padded_u8.reshape(n, nwords, 4), jnp.uint32
    )
    blocks = w.reshape(n, nblocks, 2 * LANES_PER_BLOCK).transpose(1, 2, 0)
    d = absorb(blocks, nblocks)  # [8, N]
    return jax.lax.bitcast_convert_type(d.T, jnp.uint8).reshape(n, 32)


def pad_to_blocks(messages: Sequence[bytes], nblocks: int) -> np.ndarray:
    """Host-side multi-rate padding + u32-lane packing.

    All messages must need exactly ``nblocks`` rate blocks
    (i.e. nblocks = len(m)//RATE + 1). Returns uint32[nblocks, 34, B].
    """
    batch = len(messages)
    buf32 = pad_to_words(messages, nblocks)
    # -> (nblocks, 34, B)
    return np.ascontiguousarray(
        buf32.reshape(batch, nblocks, 34).transpose(1, 2, 0)
    )


def pad_to_words(messages: Sequence[bytes], nblocks: int) -> np.ndarray:
    """Host-side multi-rate padding in the batch-major layout the
    device words path consumes directly: uint32[B, nblocks*34]. No
    host transpose — the word-major retile happens on device where it
    runs near HBM bandwidth."""
    batch = len(messages)
    buf = np.zeros((batch, nblocks * RATE), dtype=np.uint8)
    for j, m in enumerate(messages):
        if len(m) // RATE + 1 != nblocks:
            raise ValueError(
                f"message {j} needs {len(m)//RATE + 1} blocks, "
                f"class is {nblocks}"
            )
        buf[j, : len(m)] = np.frombuffer(m, dtype=np.uint8)
        buf[j, len(m)] ^= 0x01
        buf[j, nblocks * RATE - 1] ^= 0x80
    return buf.view("<u4")  # (B, nblocks*34)


def digests_to_bytes(words: np.ndarray) -> List[bytes]:
    """uint32[8, B] digest words -> list of 32-byte digests."""
    arr = np.asarray(words, dtype="<u4")  # (8, B)
    return [arr[:, j].tobytes() for j in range(arr.shape[1])]


def pad_batch_count(n: int, floor: int = 16) -> int:
    """Round a bucket's message count up to a power of two.

    Every distinct batch shape jit-specializes the absorb graph; trie
    commits produce arbitrary bucket sizes per block, so without this
    the compile count is unbounded (and each compile dwarfs hash time).
    """
    target = floor
    while target < n:
        target *= 2
    return target


def bucketed_batch(messages, target_count, run_bucket) -> List[bytes]:
    """Shared bucket/pad/scatter frame for every batched-hash backend.

    Buckets messages by rate-block class, pads each bucket with minimal-
    size filler messages up to ``target_count(nblocks, n)`` (bounding
    jit specializations), dispatches ``run_bucket(nblocks, msgs) ->
    digests`` (may return extra padding digests), and scatters results
    back into input order. Backends: jnp absorb (here), the Pallas tile
    kernel (ops.keccak_pallas), and the mesh-sharded absorb
    (parallel.keccak_sharded) — one frame, three dispatchers.
    """
    if not messages:
        return []
    buckets = {}
    for idx, m in enumerate(messages):
        buckets.setdefault(len(m) // RATE + 1, []).append(idx)
    out: List = [None] * len(messages)
    for nblocks, idxs in sorted(buckets.items()):
        msgs = [messages[i] for i in idxs]
        filler = b"\x00" * ((nblocks - 1) * RATE)
        msgs += [filler] * (target_count(nblocks, len(msgs)) - len(msgs))
        digests = run_bucket(nblocks, msgs)
        for i, digest in zip(idxs, digests):
            out[i] = digest
    return out


def keccak256_batch_jnp(messages: Sequence[bytes]) -> List[bytes]:
    """Hash a batch of variable-length messages, bucketing by block count."""

    def run_bucket(nblocks, msgs):
        blocks = pad_to_blocks(msgs, nblocks)
        with LEDGER.transfer("ops.keccak", H2D, blocks.nbytes):
            words = absorb(jnp.asarray(blocks), nblocks)
        with LEDGER.transfer("ops.keccak", D2H, int(words.size) * 4):
            got = jax.device_get(words)
        return digests_to_bytes(got)

    return bucketed_batch(
        messages, lambda nblocks, n: pad_batch_count(n), run_bucket
    )
