"""Pallas TPU kernel: batched Keccak-256 with the sponge state in VMEM.

One grid step hashes a tile of 8*128 = 1024 messages: each of the 50
u32 state half-lanes is an (8, 128) VPU-shaped tile, so every Keccak op
is a full-width elementwise VPU instruction and the 24-round permutation
never touches HBM. This is the TPU replacement for the reference's
scalar JVM sponge hot loop (khipu-base/.../crypto/hash/KeccakCore.scala
invoked per trie node at trie/Node.scala:111-112).

Kernel input layout: uint32[tiles, nwords, 8, 128] — word-major planes,
batch in the (sublane, lane) dims. Callers ship batch-major
uint32[N, nwords] (host-packed by keccak_jnp.pad_to_words, or generated
on device) and the retile to word-major runs on device near HBM
bandwidth; the multi-rate pad is fused into the kernel for fixed-size
classes. Output: uint32[tiles, 8, 8, 128] digest words.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from khipu_tpu.device import named_jit
from khipu_tpu.native.keccak import keccak256_batch as _host_keccak_batch
from khipu_tpu.observability.profiler import D2H, H2D, LEDGER
from khipu_tpu.ops.keccak_jnp import (
    _RC32,
    _round,
    LANES_PER_BLOCK,
    RATE,
    class_tag,
    pad_batch_count,
    pad_to_words,
)

TILE = 8 * 128  # messages per grid step

# Largest rate-block class the kernel is built for. State-trie nodes
# need classes 1-4 (a full branch is ~532 B) and the 576 B snapshot
# class is 5. The sponge is unrolled in Python, so compile time grows
# with the class (~2.4-3 s per block on a v5e: 16 blocks ~45 s, a
# 24 KB contract-creation pre-image at 181 blocks ~9 min) and the
# (1, nwords, 8, 128) u32 input block outgrows scoped VMEM. Messages
# past the bound hash on the host's native Keccak instead, so no
# caller can ask Mosaic for such a program.
MAX_PALLAS_BLOCKS = 5


def _make_kernel(nblocks: int, nwords_in: int = None):
    """Sponge kernel over word-major planes.

    With ``nwords_in`` set, the input carries only the message words and
    the multi-rate padding is fused: pad words are per-size-class
    constants (0x01 right after the message, 0x80 in the last byte), so
    they xor into the state in registers instead of being materialized
    as an HBM concatenate (roofline attack plan item 2).
    """
    total_words = nblocks * 2 * LANES_PER_BLOCK
    if nwords_in is None:
        nwords_in = total_words
    pad_words = {}
    if nwords_in < total_words:
        pad_words[nwords_in] = 0x00000001
        last = total_words - 1
        pad_words[last] = pad_words.get(last, 0) | 0x80000000

    def kernel(blocks_ref, out_ref):
        zero = jnp.zeros((8, 128), jnp.uint32)
        lo: List = [zero] * 25
        hi: List = [zero] * 25
        for b in range(nblocks):
            base = b * 2 * LANES_PER_BLOCK
            for i in range(LANES_PER_BLOCK):
                for half, st in ((0, lo), (1, hi)):
                    w = base + 2 * i + half
                    if w < nwords_in:
                        st[i] = st[i] ^ blocks_ref[0, w]
                    if w in pad_words:
                        st[i] = st[i] ^ jnp.uint32(pad_words[w])
            for rc_lo, rc_hi in _RC32:
                lo, hi = _round(lo, hi, jnp.uint32(rc_lo), jnp.uint32(rc_hi))
        for k in range(4):
            out_ref[0, 2 * k] = lo[k]
            out_ref[0, 2 * k + 1] = hi[k]

    return kernel


def _build(nblocks: int, interpret: bool, nwords_in: int = None):
    """Compile the sponge for ``nblocks`` rate blocks. With
    ``nwords_in``, input planes carry only the message words and the
    pad is fused in-kernel. Normalizes the default BEFORE memoizing so
    `_build(n, i)` and `_build(n, i, nwords_in=full)` share one compile."""
    if nblocks > MAX_PALLAS_BLOCKS:
        raise ValueError(
            f"rate class {nblocks} exceeds the Pallas bound "
            f"({MAX_PALLAS_BLOCKS} blocks); hash it on the host"
        )
    full = nblocks * 2 * LANES_PER_BLOCK
    if nwords_in is not None and nwords_in >= full:
        nwords_in = None
    return _build_cached(nblocks, interpret, nwords_in)


@functools.lru_cache(maxsize=32)
def _build_cached(nblocks: int, interpret: bool, nwords_in):
    nwords = (
        nwords_in
        if nwords_in is not None
        else nblocks * 2 * LANES_PER_BLOCK
    )
    tag = class_tag(nblocks, nwords_in)

    def run(blocks):  # uint32[tiles, nwords, 8, 128]
        tiles = blocks.shape[0]
        return pl.pallas_call(
            _make_kernel(nblocks, nwords_in),
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((1, nwords, 8, 128), lambda i: (i, 0, 0, 0))
            ],
            out_specs=pl.BlockSpec((1, 8, 8, 128), lambda i: (i, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((tiles, 8, 8, 128), jnp.uint32),
            interpret=interpret,
            # names the Mosaic custom-call instruction (else it takes
            # the innermost jitted wrapper's name). Ends in a word:
            # trace readers strip a trailing ".<n>" and digits
            name=f"keccak_{tag}_sponge",
        )(blocks)

    return named_jit(f"keccak_tiles_{tag}", run)


@functools.lru_cache(maxsize=32)
def _build_from_bytes(nblocks: int, interpret: bool):
    """Fused device-side pack + hash for fixed-size padded messages.

    Takes uint8[N, nblocks*RATE] already multi-rate padded (host does the
    two xor bytes, vectorized); does the u8->u32 bitcast and the
    word-major retile on device, where they are cheap HBM shuffles, then
    runs the kernel. Avoids the multi-second host-side numpy transposes.
    """
    nwords = nblocks * 2 * LANES_PER_BLOCK
    run = _build(nblocks, interpret)

    def go(padded_u8):  # uint8[N, nblocks*RATE], N % TILE == 0
        n = padded_u8.shape[0]
        tiles = n // TILE
        w = jax.lax.bitcast_convert_type(
            padded_u8.reshape(n, nwords, 4), jnp.uint32
        )  # little-endian on TPU/x86 -> matches '<u4'
        tiled = w.reshape(tiles, 8, 128, nwords).transpose(0, 3, 1, 2)
        out = run(tiled)  # (tiles, 8, 8, 128)
        # back to digest-major: (N, 8) words -> bitcast to bytes
        d = out.transpose(0, 2, 3, 1).reshape(n, 8)
        return jax.lax.bitcast_convert_type(d, jnp.uint8).reshape(n, 32)

    return named_jit(f"keccak_from_bytes_nb{nblocks}", go)


def _words_runner(nblocks: int, interpret: bool, nwords_in: int = None):
    """u32-native full path: batch-major words -> digest words.

    The byte-granular path (`_build_from_bytes`) costs ~4x the sponge
    itself in pure HBM relayout (u8 tiling is (32, 128); every
    reshape/bitcast across the u8/u32 boundary is a gather). Staying in
    u32 end to end, the only layout op left is the word-major tile
    transpose, which XLA runs near memory bandwidth. With ``nwords_in``
    the input carries message words only and the pad is fused
    in-kernel.
    """
    nwords = (
        nwords_in
        if nwords_in is not None
        else nblocks * 2 * LANES_PER_BLOCK
    )
    run = _build(nblocks, interpret, nwords_in=nwords_in)

    def go(words):  # uint32[N, nwords], N % TILE == 0
        n = words.shape[0]
        tiles = n // TILE
        tiled = words.reshape(tiles, 8, 128, nwords).transpose(0, 3, 1, 2)
        out = run(tiled)  # (tiles, 8, 8, 128)
        return out.transpose(0, 2, 3, 1).reshape(n, 8)  # digest words

    return named_jit(
        f"keccak_from_words_{class_tag(nblocks, nwords_in)}", go)


@functools.lru_cache(maxsize=32)
def _build_from_words(nblocks: int, interpret: bool):
    """Already-padded batch-major words -> digest words."""
    return _words_runner(nblocks, interpret)


@functools.lru_cache(maxsize=32)
def _build_device_fixed_words(length: int, interpret: bool):
    """Device-resident full path for fixed-size messages given as u32
    words: retile + sponge with the multi-rate pad fused in-kernel (no
    HBM pad materialization at all). uint32[N, length//4] ->
    uint32[N, 8] digest words. Requires length % 4 == 0.
    """
    if length % 4:
        raise ValueError("u32 path requires length % 4 == 0")
    nblocks = length // RATE + 1
    return _words_runner(nblocks, interpret, nwords_in=length // 4)


@functools.lru_cache(maxsize=32)
def _build_device_fixed(length: int, interpret: bool):
    """Fully device-resident: pad + pack + hash uint8[N, length] on device.

    No host round-trip: use when the node bytes already live on device
    (or are generated there, as in the microbench). Returns uint8[N, 32].
    For length % 4 == 0 the words path (`_build_device_fixed_words`)
    avoids every u8-granular layout op; this wrapper only pays one
    bitcast at each edge.
    """
    nblocks = length // RATE + 1
    if length % 4 == 0:
        run_words = _build_device_fixed_words(length, interpret)

        def go(data_u8):  # uint8[N, length], N % TILE == 0
            n = data_u8.shape[0]
            words = jax.lax.bitcast_convert_type(
                data_u8.reshape(n, length // 4, 4), jnp.uint32
            )
            digest = run_words(words)
            return jax.lax.bitcast_convert_type(digest, jnp.uint8).reshape(
                n, 32
            )

        return named_jit(f"keccak_fixed_len{length}", go)

    run_bytes = _build_from_bytes(nblocks, interpret)

    def go(data_u8):  # uint8[N, length], N % TILE == 0
        n = data_u8.shape[0]
        tail = np.zeros(nblocks * RATE - length, dtype=np.uint8)
        tail[0] ^= 0x01
        tail[-1] ^= 0x80
        pad = jnp.broadcast_to(jnp.asarray(tail), (n, tail.shape[0]))
        return run_bytes(jnp.concatenate([data_u8, pad], axis=1))

    return named_jit(f"keccak_fixed_len{length}", go)


def keccak256_fixed(
    data: np.ndarray, interpret: bool = False
) -> np.ndarray:
    """Hash N equal-length messages: uint8[N, L] -> uint8[N, 32].

    The bulk-commit fast path (all dirty trie nodes of one size class in
    one device call). Pads on host (vectorized), ships batch-major u32
    words, retiles + hashes on device (no byte-granular device op).
    """
    n, length = data.shape
    nblocks = length // RATE + 1
    if nblocks > MAX_PALLAS_BLOCKS:
        digests = _host_keccak_batch([row.tobytes() for row in data])
        return np.frombuffer(b"".join(digests), dtype=np.uint8).reshape(
            n, 32
        )
    padded = np.zeros((n, nblocks * RATE), dtype=np.uint8)
    padded[:, :length] = data
    padded[:, length] ^= 0x01
    padded[:, nblocks * RATE - 1] ^= 0x80
    pad_rows = pad_batch_count(n, floor=TILE) - n
    if pad_rows:
        extra = np.zeros((pad_rows, nblocks * RATE), dtype=np.uint8)
        extra[:, length] ^= 0x01
        extra[:, nblocks * RATE - 1] ^= 0x80
        padded = np.concatenate([padded, extra], axis=0)
    with LEDGER.transfer("ops.keccak", H2D, padded.nbytes):
        out = _build_from_words(nblocks, interpret)(
            jnp.asarray(padded.view("<u4"))
        )
    with LEDGER.transfer("ops.keccak", D2H, int(out.size) * 4):
        got = jax.device_get(out)
    # contiguous copy: a TPU fetch may hand back a strided array, which
    # cannot be re-viewed as bytes
    digest_words = np.ascontiguousarray(got, dtype="<u4")[:n]
    return digest_words.view(np.uint8).reshape(n, 32)


def retile(blocks: np.ndarray) -> np.ndarray:
    """uint32[nblocks, 34, B] (B % 1024 == 0) -> [tiles, nblocks*34, 8, 128]."""
    nblocks, nwords_per_block, batch = blocks.shape
    tiles = batch // TILE
    # -> (B, nblocks*34)
    flat = blocks.reshape(nblocks * nwords_per_block, batch).T
    # -> (tiles, 8, 128, nwords) -> (tiles, nwords, 8, 128)
    return np.ascontiguousarray(
        flat.reshape(tiles, 8, 128, nblocks * nwords_per_block).transpose(0, 3, 1, 2)
    )


# Largest per-dispatch tile count: batches above this are CHUNKED into
# equal dispatches of exactly MAX_TILES tiles, so the set of compiled
# shapes per rate-block class is {1, 2, 4, 8, 16} tiles — a one-off
# compile budget instead of a new 10s+ XLA compile per batch size
# (bulk-build levels arrive in arbitrary sizes).
MAX_TILES = 16


def _pallas_target_count(nblocks: int, n: int) -> int:
    """Whole tiles, power-of-two tile count up to MAX_TILES, then whole
    multiples of MAX_TILES (bounds compiled shapes to {1,2,4,8,16})."""
    n_tiles_raw = (n + TILE - 1) // TILE
    if n_tiles_raw <= MAX_TILES:
        return pad_batch_count(n, floor=TILE)
    n_chunks = (n_tiles_raw + MAX_TILES - 1) // MAX_TILES
    return n_chunks * MAX_TILES * TILE


def keccak256_batch_pallas(
    messages: Sequence[bytes], interpret: bool = False
) -> List[bytes]:
    """Hash variable-length messages via the Pallas kernel.

    Buckets by rate-block count, zero-pads each bucket to a whole
    1024-message tile (padding digests discarded), chunks at MAX_TILES.
    Messages past MAX_PALLAS_BLOCKS rate blocks never reach the kernel:
    they hash on the host and scatter back into input order.
    """
    from khipu_tpu.ops.keccak_jnp import bucketed_batch

    limit = MAX_PALLAS_BLOCKS * RATE  # first length of the next class
    is_long = [len(m) >= limit for m in messages]
    if any(is_long):
        long_d = iter(_host_keccak_batch(
            [m for m, lg in zip(messages, is_long) if lg]
        ))
        short_d = iter(keccak256_batch_pallas(
            [m for m, lg in zip(messages, is_long) if not lg], interpret
        ))
        return [next(long_d if lg else short_d) for lg in is_long]

    def run_bucket(nblocks, msgs):
        packed = pad_to_words(msgs, nblocks)  # (B, nwords) batch-major
        run = _build_from_words(nblocks, interpret)
        rows_per_chunk = MAX_TILES * TILE
        chunks = []
        for start in range(0, packed.shape[0], rows_per_chunk):
            chunk = packed[start : start + rows_per_chunk]
            with LEDGER.transfer("ops.keccak", H2D, chunk.nbytes):
                words = run(jnp.asarray(chunk))
            with LEDGER.transfer("ops.keccak", D2H, int(words.size) * 4):
                chunks.append(np.asarray(jax.device_get(words), dtype="<u4"))
        arr = np.concatenate(chunks, axis=0)  # (B, 8) digest words
        return [arr[j].tobytes() for j in range(len(msgs))]

    return bucketed_batch(messages, _pallas_target_count, run_bucket)
