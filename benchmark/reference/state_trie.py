"""Ethereum's state as a plain reference: the secure hexary
Merkle-Patricia trie of a ``{key: value}`` map (root and node set), the
account record's RLP, a contract's storage trie and the whole state of a
genesis alloc, in straightforward Python over ``reference/keccak.py``.

The benchmark's own: it imports nothing of the program, so a root or a
node set computed here is an independent statement of what a fast sync
has to leave in the store. A trie is built as a tree of plain records
first and hashed level by level, deepest first, one ``keccak256_batch``
a level; a node whose RLP is shorter than 32 bytes is embedded in its
parent and is no node of the set (the Yellow Paper's rule), the root is
always hashed.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Mapping, Optional, Tuple

from benchmark.reference.keccak import keccak256_batch

EMPTY_ROOT = keccak256_batch([b"\x80"])[0]    # the trie of no keys
EMPTY_CODE_HASH = keccak256_batch([b""])[0]


# ---------------------------------------------------------------- RLP


def _length(n: int, offset: int) -> bytes:
    if n < 56:
        return bytes([offset + n])
    raw = n.to_bytes((n.bit_length() + 7) // 8, "big")
    return bytes([offset + 55 + len(raw)]) + raw


def rlp_bytes(b: bytes) -> bytes:
    if len(b) == 1 and b[0] < 0x80:
        return b
    return _length(len(b), 0x80) + b


def rlp_list(encoded_items: Iterable[bytes]) -> bytes:
    """A list whose items are already encoded."""
    payload = b"".join(encoded_items)
    return _length(len(payload), 0xC0) + payload


def int_bytes(n: int) -> bytes:
    """Big-endian with no leading zero; 0 is the empty string."""
    return n.to_bytes((n.bit_length() + 7) // 8, "big")


def account_rlp(nonce: int, balance: int, storage_root: bytes = EMPTY_ROOT,
                code_hash: bytes = EMPTY_CODE_HASH) -> bytes:
    return rlp_list([rlp_bytes(int_bytes(nonce)), rlp_bytes(int_bytes(balance)),
                     rlp_bytes(storage_root), rlp_bytes(code_hash)])


# --------------------------------------------------------------- trie


def hex_prefix(nibbles: bytes, leaf: bool) -> bytes:
    flag = 2 if leaf else 0
    if len(nibbles) % 2:
        head, rest = bytes([16 * (flag + 1) + nibbles[0]]), nibbles[1:]
    else:
        head, rest = bytes([16 * flag]), nibbles
    return head + bytes(16 * rest[i] + rest[i + 1]
                        for i in range(0, len(rest), 2))


class _Node:
    """``kind`` leaf: ``path``, ``value``; ext: ``path``, ``child``;
    branch: ``children`` (16, None where empty). ``ref`` is how the
    parent names it once encoded: RLP of its hash, or its own RLP."""

    __slots__ = ("kind", "path", "value", "child", "children", "enc", "ref")

    def __init__(self, kind, path=b"", value=b"", child=None, children=None):
        self.kind, self.path, self.value = kind, path, value
        self.child, self.children = child, children
        self.enc = self.ref = None

    def encode(self) -> bytes:
        if self.kind == "leaf":
            return rlp_list([rlp_bytes(hex_prefix(self.path, True)),
                             rlp_bytes(self.value)])
        if self.kind == "ext":
            return rlp_list([rlp_bytes(hex_prefix(self.path, False)),
                             self.child.ref])
        return rlp_list([c.ref if c is not None else b"\x80"
                         for c in self.children] + [b"\x80"])


def _build(keys: List[bytes], values: List[bytes], lo: int, hi: int,
           depth: int, levels: Dict[int, List[_Node]]) -> _Node:
    """The node over sorted ``keys[lo:hi]`` (nibble strings of one
    length, distinct), which agree on their first ``depth`` nibbles."""
    first = keys[lo]
    if hi - lo == 1:
        node = _Node("leaf", path=first[depth:], value=values[lo])
    else:
        last, d = keys[hi - 1], depth
        while first[d] == last[d]:
            d += 1
        if d > depth:
            node = _Node("ext", path=first[depth:d],
                         child=_build(keys, values, lo, hi, d, levels))
        else:
            children: List[Optional[_Node]] = [None] * 16
            i = lo
            while i < hi:
                nib, j = keys[i][depth], i + 1
                while j < hi and keys[j][depth] == nib:
                    j += 1
                children[nib] = _build(keys, values, i, j, depth + 1, levels)
                i = j
            node = _Node("branch", children=children)
    levels.setdefault(depth, []).append(node)
    return node


def plain_trie(pairs: Mapping[bytes, bytes]) -> Tuple[bytes, Dict[bytes, bytes]]:
    """(root, {hash: RLP} of every node stored by hash) of the trie of
    ``pairs`` under the keys as given: distinct, of one length."""
    if not pairs:
        return EMPTY_ROOT, {}
    ordered = sorted(pairs)
    keys = [bytes(n for b in k for n in (b >> 4, b & 15)) for k in ordered]
    values = [pairs[k] for k in ordered]
    levels: Dict[int, List[_Node]] = {}
    root = _build(keys, values, 0, len(keys), 0, levels)
    nodes: Dict[bytes, bytes] = {}
    for depth in sorted(levels, reverse=True):
        stored = []
        for node in levels[depth]:
            node.enc = node.encode()
            if len(node.enc) < 32 and node is not root:
                node.ref = node.enc     # embedded in its parent
            else:
                stored.append(node)
        for node, digest in zip(stored, keccak256_batch(
                [n.enc for n in stored])):
            node.ref = rlp_bytes(digest)
            nodes[digest] = node.enc
    return root.ref[1:], nodes


def trie(pairs: Mapping[bytes, bytes]) -> Tuple[bytes, Dict[bytes, bytes]]:
    """The SECURE trie of ``pairs``, as the state and every contract's
    storage are kept: each key is replaced by its Keccak-256."""
    raw_keys = list(pairs)
    return plain_trie(dict(zip(keccak256_batch(raw_keys),
                               (pairs[k] for k in raw_keys))))


# -------------------------------------------------------------- state


def storage_trie(slots: Mapping[int, int]) -> Tuple[bytes, Dict[bytes, bytes]]:
    """A contract's storage: key the slot as 32 bytes, value the RLP of
    the integer; a zero value is no entry."""
    return trie({slot.to_bytes(32, "big"): rlp_bytes(int_bytes(value))
                 for slot, value in slots.items() if value})


def state(alloc: Mapping[bytes, object], start_nonce: int = 0):
    """The state of a genesis alloc ``{address: balance, or an object
    with .balance, .nonce (None: the start nonce), .code, .storage}``:
    (state root, {"state": nodes, "storage": nodes, "code": {hash:
    code}}, {address: (nonce, balance, storage root, code hash)})."""
    storage_nodes: Dict[bytes, bytes] = {}
    codes: Dict[bytes, bytes] = {}
    records: Dict[bytes, Tuple[int, int, bytes, bytes]] = {}
    contracts = [(a, e) for a, e in alloc.items() if not isinstance(e, int)]
    code_hashes = keccak256_batch([bytes(e.code or b"") for _a, e in contracts])
    for (addr, entry), code_hash in zip(contracts, code_hashes):
        storage_root, nodes = storage_trie(entry.storage or {})
        storage_nodes.update(nodes)
        if entry.code:
            codes[code_hash] = bytes(entry.code)
        nonce = start_nonce if entry.nonce is None else entry.nonce
        records[addr] = (nonce, entry.balance, storage_root, code_hash)
    for addr, entry in alloc.items():
        if isinstance(entry, int):
            records[addr] = (start_nonce, entry, EMPTY_ROOT, EMPTY_CODE_HASH)
    root, state_nodes = trie(
        {addr: account_rlp(*rec) for addr, rec in records.items()})
    return root, {"state": state_nodes, "storage": storage_nodes,
                  "code": codes}, records
