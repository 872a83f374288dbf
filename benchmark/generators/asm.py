"""A small EVM assembler for the benchmark's hand-written contracts
(no compiler and no network here). After the ``asm``/``push`` helpers
of ``tests/test_native_evm.py`` (copied in spirit; that file stays as
it is), with mnemonics and labels so that a listing reads like the
annotated bytecode in ``docs/deployments.md``.

A program is a sequence of items:

    "SLOAD"            one opcode by mnemonic
    42  /  ("push", v[, width])   PUSHn of the shortest (or given) width
    b"..."             raw bytes
    ":name"            a JUMPDEST, and the label's address
    "@name"            PUSH2 <address of :name>
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

OPCODES: Dict[str, int] = {
    "STOP": 0x00, "ADD": 0x01, "MUL": 0x02, "SUB": 0x03, "DIV": 0x04,
    "LT": 0x10, "GT": 0x11, "EQ": 0x14, "ISZERO": 0x15, "AND": 0x16,
    "OR": 0x17, "SHL": 0x1B, "SHR": 0x1C, "SHA3": 0x20,
    "ADDRESS": 0x30, "CALLER": 0x33, "CALLVALUE": 0x34,
    "CALLDATALOAD": 0x35, "CALLDATASIZE": 0x36, "RETURNDATASIZE": 0x3D,
    "POP": 0x50, "MLOAD": 0x51, "MSTORE": 0x52, "SLOAD": 0x54,
    "SSTORE": 0x55, "JUMP": 0x56, "JUMPI": 0x57, "GAS": 0x5A,
    "JUMPDEST": 0x5B,
    "LOG0": 0xA0, "LOG1": 0xA1, "LOG2": 0xA2, "LOG3": 0xA3, "LOG4": 0xA4,
    "CALL": 0xF1, "RETURN": 0xF3, "REVERT": 0xFD,
}
OPCODES.update({f"DUP{n}": 0x7F + n for n in range(1, 17)})
OPCODES.update({f"SWAP{n}": 0x8F + n for n in range(1, 17)})
MNEMONICS = {v: k for k, v in OPCODES.items()}


def push(v: int, width: int = None) -> bytes:
    b = v.to_bytes(width or max(1, (v.bit_length() + 7) // 8), "big")
    return bytes([0x60 + len(b) - 1]) + b


def assemble(program: Sequence) -> bytes:
    """Two passes: sizes are fixed (a label reference is always PUSH2),
    so the first pass places the labels and the second emits."""
    labels: Dict[str, int] = {}
    pieces: List = []
    pos = 0
    for item in program:
        if isinstance(item, str) and item.startswith("@"):
            pieces.append(item)  # PUSH2 <label>, resolved below
            pos += 3
            continue
        if isinstance(item, str) and item.startswith(":"):
            labels[item[1:]] = pos
            piece = bytes([OPCODES["JUMPDEST"]])
        elif isinstance(item, str):
            piece = bytes([OPCODES[item]])
        elif isinstance(item, int):
            piece = push(item)
        elif isinstance(item, tuple):
            piece = push(*item[1:])
        else:
            piece = bytes(item)
        pos += len(piece)
        pieces.append(piece)
    return b"".join(
        push(labels[p[1:]], 2) if isinstance(p, str) else p for p in pieces)


def listing(code: bytes) -> List[Tuple[int, str]]:
    """(offset, text) per instruction, for the docs and for a test that
    the listing printed there is the code the node runs. Bytes after
    the last known opcode (a metadata trailer) are listed as data."""
    out, pc = [], 0
    while pc < len(code):
        op = code[pc]
        if 0x60 <= op <= 0x7F:
            n = op - 0x5F
            out.append((pc, f"PUSH{n} 0x{code[pc + 1: pc + 1 + n].hex()}"))
            pc += 1 + n
        else:
            out.append((pc, MNEMONICS.get(op, f"DATA 0x{op:02x}")))
            pc += 1
    return out
