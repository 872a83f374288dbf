"""Native C++ components vs pure-Python oracles."""

import os
import random

import pytest

from khipu_tpu.base.crypto.keccak import keccak256_py, keccak512_py
from khipu_tpu.native import keccak as native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no native toolchain"
)


def test_native_keccak256_vs_oracle():
    rng = random.Random(0)
    for n in [0, 1, 55, 56, 135, 136, 137, 271, 272, 273, 576, 4096]:
        data = rng.randbytes(n)
        assert native.keccak256(data) == keccak256_py(data), n


def test_native_keccak512_vs_oracle():
    rng = random.Random(1)
    for n in [0, 1, 71, 72, 73, 143, 144, 145, 576]:
        data = rng.randbytes(n)
        assert native.keccak512(data) == keccak512_py(data), n


def test_native_keccak_known_vectors():
    assert (
        native.keccak256(b"").hex()
        == "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
    )
    assert (
        native.keccak256(b"abc").hex()
        == "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45"
    )


def test_native_batch_matches_singles():
    rng = random.Random(2)
    msgs = [rng.randbytes(rng.randint(0, 600)) for _ in range(257)]
    assert native.keccak256_batch(msgs) == [
        native.keccak256(m) for m in msgs
    ]
    assert native.keccak256_batch([]) == []


# ------------------------------------------------- rlp resize guard

def _rlp_ext():
    from khipu_tpu.base.rlp import RLPError
    from khipu_tpu.native.build import load_rlp_ext

    ext = load_rlp_ext()
    if ext is None:
        pytest.skip("rlp extension unavailable")
    ext._set_error(RLPError)
    return ext


class TestRlpEncodeResizeGuard:
    """rlp_ext.c two-pass encode: a bytearray resized between the
    size pass and the write pass (GC finalizer / rogue thread) must
    raise RLPError — never scribble past the output buffer."""

    def test_grow_between_passes_raises(self):
        from khipu_tpu.base.rlp import RLPError

        ext = _rlp_ext()
        ba = bytearray(b"x" * 10)
        ext._set_encode_hook(lambda: ba.extend(b"y" * 90))
        try:
            with pytest.raises(RLPError):
                ext.encode([ba, b"tail"])
        finally:
            ext._set_encode_hook(None)

    def test_shrink_between_passes_raises(self):
        from khipu_tpu.base.rlp import RLPError

        ext = _rlp_ext()
        ba = bytearray(b"x" * 100)
        ext._set_encode_hook(lambda: ba.__init__(b"x" * 3))
        try:
            with pytest.raises(RLPError):
                ext.encode([ba, b"tail"])
        finally:
            ext._set_encode_hook(None)

    def test_hook_without_resize_is_benign(self):
        ext = _rlp_ext()
        ba = bytearray(b"hello rlp")
        ext._set_encode_hook(lambda: None)
        try:
            out = ext.encode([ba, b"tail"])
        finally:
            ext._set_encode_hook(None)
        assert out == ext.encode([ba, b"tail"])  # hook cleared, same bytes

    def test_nested_list_growth_raises(self):
        from khipu_tpu.base.rlp import RLPError

        ext = _rlp_ext()
        inner = bytearray(b"ab")
        ext._set_encode_hook(lambda: inner.extend(b"c" * 60))
        try:
            with pytest.raises(RLPError):
                ext.encode([[inner], [b"x", [inner]]])
        finally:
            ext._set_encode_hook(None)


# --------------------------------------------------- build stamps


class TestBuildStamp:
    """native/build.py keys a binary on (sources, compile command, host
    CPU flags) in its file name: the checkout is copied between
    machines with ``-march=native`` binaries in it, and "newer than the
    source" says nothing about where a binary was built."""

    @pytest.fixture
    def fresh_build(self, tmp_path, monkeypatch):
        """native/build.py pointed at a private output directory (the
        real sources, no loaded state)."""
        from khipu_tpu.native import build

        monkeypatch.setattr(build, "_DIR", str(tmp_path))
        monkeypatch.setattr(build, "_lib", None)
        monkeypatch.setattr(build, "_failed", False)
        monkeypatch.setattr(build, "_ext_mod", None)
        monkeypatch.setattr(build, "_ext_failed", False)
        return build

    def test_foreign_stamp_is_rebuilt_not_loaded(self, fresh_build):
        build = fresh_build
        # a binary "from another machine": right stem, foreign stamp,
        # and not even a valid ELF — loading it would fail loudly
        foreign = os.path.join(
            build._DIR, "_khipu_rlp_ext.0123456789abcdef.so"
        )
        with open(foreign, "wb") as f:
            f.write(b"built elsewhere")
        assert not build.rlp_ext_is_fresh()
        ext = build.load_rlp_ext()
        assert ext is not None and ext.__file__ == build.rlp_ext_path()
        assert ext.encode([b"cat", b"dog"]) == b"\xc8\x83cat\x83dog"
        assert build.rlp_ext_is_fresh()
        assert not os.path.exists(foreign)  # the stale binary is gone

    def test_stamp_moves_with_cpu_flags_command_and_sources(
            self, fresh_build, monkeypatch, tmp_path):
        build = fresh_build
        here = build.lib_path()
        # another host's CPU
        monkeypatch.setattr(build, "_cpu_flags", lambda: "fpu vme avx9000")
        other_cpu = build.lib_path()
        assert other_cpu != here
        monkeypatch.undo()
        monkeypatch.setattr(build, "_DIR", str(tmp_path))
        # another compile command
        monkeypatch.setattr(
            build, "_lib_cmd", lambda: ["g++", "-O0", "-shared", "-fPIC"]
        )
        assert build.lib_path() not in (here, other_cpu)
        monkeypatch.undo()
        monkeypatch.setattr(build, "_DIR", str(tmp_path))
        # edited sources
        src = tmp_path / "edited.cc"
        src.write_text("// changed\n")
        monkeypatch.setattr(build, "_sources", lambda: [str(src)])
        assert build.lib_path() not in (here, other_cpu)

    def test_toolchain_failure_warns_instead_of_hiding(
            self, fresh_build, monkeypatch, capsys):
        build = fresh_build
        monkeypatch.setattr(
            build, "_ext_cmd", lambda: ["gcc-that-does-not-exist"]
        )
        assert build.load_rlp_ext() is None  # the pure-Python codec serves
        err = capsys.readouterr().err
        assert "native RLP extension unavailable" in err
        assert "pure Python" in err
