"""ctypes bindings for the native Keccak (csrc/keccak.cc)."""

from __future__ import annotations

import ctypes
from typing import List, Optional, Sequence

from khipu_tpu.native.build import load_library

_RATE_256 = 136
_RATE_512 = 72

_configured = False
_lib = None


def _get_lib():
    global _configured, _lib
    if not _configured:
        _configured = True
        lib = load_library()
        if lib is not None:
            lib.khipu_keccak.argtypes = [
                ctypes.c_int, ctypes.c_char_p, ctypes.c_uint64,
                ctypes.c_char_p, ctypes.c_int,
            ]
            lib.khipu_keccak_batch.argtypes = [
                ctypes.c_int, ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
                ctypes.c_char_p, ctypes.c_int,
            ]
            lib.khipu_keccak_absorb.argtypes = [
                ctypes.c_int, ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_char_p, ctypes.c_uint64,
            ]
            lib.khipu_keccak_peek.argtypes = [
                ctypes.c_int, ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
                ctypes.c_int,
            ]
        _lib = lib
    return _lib


def available() -> bool:
    return _get_lib() is not None


def _digest(data: bytes, rate: int, out_len: int) -> bytes:
    lib = _get_lib()
    if lib is None:
        # Same self-healing as keccak256_batch: pure sponge directly
        # (base.crypto.keccak's public fns may be bound to this module).
        from khipu_tpu.base.crypto.keccak import keccak256_py, keccak512_py

        return keccak256_py(data) if rate == _RATE_256 else keccak512_py(data)
    out = ctypes.create_string_buffer(out_len)
    lib.khipu_keccak(rate, bytes(data), len(data), out, out_len)
    return out.raw


def keccak256(data: bytes) -> bytes:
    return _digest(data, _RATE_256, 32)


def keccak512(data: bytes) -> bytes:
    return _digest(data, _RATE_512, 64)


def keccak256_batch(messages: Sequence[bytes]) -> List[bytes]:
    lib = _get_lib()
    n = len(messages)
    if n == 0:
        return []
    if lib is None:
        # Use the pure sponge directly — base.crypto.keccak.keccak256
        # may itself be bound to this module (circular).
        from khipu_tpu.base.crypto.keccak import keccak256_py

        return [keccak256_py(m) for m in messages]
    blob = b"".join(messages)
    offsets = (ctypes.c_uint64 * (n + 1))()
    pos = 0
    for i, m in enumerate(messages):
        offsets[i] = pos
        pos += len(m)
    offsets[n] = pos
    out = ctypes.create_string_buffer(32 * n)
    lib.khipu_keccak_batch(_RATE_256, blob, offsets, n, out, 32)
    raw = out.raw
    return [raw[i * 32 : (i + 1) * 32] for i in range(n)]


class RunningKeccak256:
    """A Keccak-256 stream that can be read without being ended:
    ``update`` absorbs, ``digest`` is what the stream would hash to if
    it ended here, and the stream goes on (RLPx's frame MAC,
    network/rlpx.py). The 25 lanes live here; the permutation runs in
    the native library. Build one only where ``available()``."""

    __slots__ = ("_lib", "_lanes", "_tail")

    def __init__(self):
        self._lib = _get_lib()
        self._lanes = (ctypes.c_uint64 * 25)()
        self._tail = b""  # under one block, not absorbed yet

    def update(self, data: bytes) -> None:
        data = self._tail + data
        whole = len(data) // _RATE_256
        if whole:
            self._lib.khipu_keccak_absorb(_RATE_256, self._lanes, data, whole)
        self._tail = data[whole * _RATE_256:]

    def digest(self) -> bytes:
        out = ctypes.create_string_buffer(32)
        self._lib.khipu_keccak_peek(
            _RATE_256, self._lanes, self._tail, len(self._tail), out, 32)
        return out.raw
