"""On-demand g++ build of the native shared libraries, keyed by a
stamp of what went into them.

The binaries are git-ignored and built with ``-march=native``, and a
checkout can be copied to a machine with a different CPU, so "newer
than its sources" says nothing about whether a binary on disk belongs
here. Each binary instead carries, in its file name, a hash of its
sources, its compile command and this host's CPU flags; a binary built
elsewhere or from other sources has another name and is simply not
found — it is rebuilt from the committed sources, never loaded.
"""

from __future__ import annotations

import ctypes
import functools
import glob
import hashlib
import os
import platform
import subprocess
import sys
import sysconfig
import threading
from typing import List, Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_CSRC = os.path.join(_DIR, "csrc")
_CSRC_EXT = os.path.join(_DIR, "csrc_ext")
_LIB_STEM = "_libkhipu_native"
_EXT_STEM = "_khipu_rlp_ext"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_failed = False
_ext_lock = threading.Lock()
_ext_mod = None
_ext_failed = False


def _sources() -> List[str]:
    return sorted(
        os.path.join(_CSRC, f)
        for f in os.listdir(_CSRC)
        if f.endswith(".cc")
    )


def _ext_sources() -> List[str]:
    # the sponge is compiled in: the trie walk hashes its keys with no
    # ctypes call between
    return [os.path.join(_CSRC_EXT, "rlp_ext.c"),
            os.path.join(_CSRC, "keccak.cc")]


def _lib_cmd() -> List[str]:
    return ["g++", "-O3", "-march=native", "-shared", "-fPIC",
            "-std=c++17"]


def _ext_cmd() -> List[str]:
    # hidden: the extension's khipu_keccak is its own, whatever else
    # the process has loaded (PyMODINIT_FUNC exports the init)
    return ["gcc", "-O3", "-shared", "-fPIC", "-fvisibility=hidden",
            f"-I{sysconfig.get_paths()['include']}"]


@functools.lru_cache(maxsize=1)
def _cpu_flags() -> str:
    """What ``-march=native`` resolves against on this host."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return f"{platform.machine()} {platform.processor()}"


def _stamp(sources: List[str], cmd: List[str]) -> str:
    h = hashlib.sha256()
    for path in sources:
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
    h.update(" ".join(cmd).encode() + b"\0")
    h.update(_cpu_flags().encode())
    return h.hexdigest()[:16]


def lib_path() -> str:
    """Where THIS host's build of the current sources lives."""
    return os.path.join(
        _DIR, f"{_LIB_STEM}.{_stamp(_sources(), _lib_cmd())}.so"
    )


def rlp_ext_path() -> str:
    return os.path.join(
        _DIR, f"{_EXT_STEM}.{_stamp(_ext_sources(), _ext_cmd())}.so"
    )


def _build(cmd: List[str], sources: List[str], out: str,
           stem: str) -> None:
    """Compile to a process-unique temp path and os.replace() into
    place (concurrent builders must never dlopen a half-written .so),
    then drop binaries of this stem carrying any other stamp."""
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [*cmd, "-o", tmp, *sources],
            check=True, capture_output=True, timeout=300,
        )
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for old in glob.glob(os.path.join(_DIR, f"{stem}*.so")):
        if old != out:
            try:
                os.unlink(old)
            except OSError:
                pass


def _warn(what: str, err: Exception) -> None:
    detail = getattr(err, "stderr", b"") or b""
    print(
        f"WARNING: native {what} unavailable ({type(err).__name__}: "
        f"{err}); falling back to pure Python (~30x slower)"
        + (f"\n{detail.decode(errors='replace')[-2000:]}" if detail else ""),
        file=sys.stderr,
    )


def load_library() -> Optional[ctypes.CDLL]:
    """Build (unless this host's build of these sources is already on
    disk) and dlopen the native library.

    Returns None — with a warning on stderr — when no working toolchain
    is available; callers fall back to pure Python.
    """
    global _lib, _failed
    if _lib is not None or _failed:
        return _lib
    with _lock:
        if _lib is not None or _failed:
            return _lib
        try:
            out = lib_path()
            if not os.path.exists(out):
                _build(_lib_cmd(), _sources(), out, _LIB_STEM)
            _lib = ctypes.CDLL(out)
        except Exception as e:
            _failed = True
            _lib = None
            _warn("library (keccak/secp256k1/EVM/store)", e)
        return _lib


def rlp_ext_is_fresh() -> bool:
    """True when this host's build of the current RLP extension source
    is on disk — THE staleness rule, shared by load_rlp_ext and the
    import-time binding decision in base/rlp.py."""
    return os.path.exists(rlp_ext_path())


def load_rlp_ext():
    """Build (if missing) and import the CPython RLP extension module
    (csrc_ext/rlp_ext.c). Returns the module, or None with a warning —
    callers fall back to the pure-Python codec."""
    global _ext_mod, _ext_failed
    if _ext_mod is not None or _ext_failed:
        return _ext_mod
    with _ext_lock:
        if _ext_mod is not None or _ext_failed:
            return _ext_mod
        try:
            import importlib.util

            out = rlp_ext_path()
            if not os.path.exists(out):
                _build(_ext_cmd(), _ext_sources(), out, _EXT_STEM)
            spec = importlib.util.spec_from_file_location(
                "khipu_rlp_ext", out
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _ext_mod = mod
        except Exception as e:
            _ext_failed = True
            _ext_mod = None
            _warn("RLP extension", e)
        return _ext_mod
