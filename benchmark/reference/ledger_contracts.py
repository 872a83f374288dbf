"""The plain ledger of ``fullsync-postmerge-contracts``: the
specification of its two contracts (``docs/deployments.md``) folded in
Python ints over the picks, transaction by transaction in block order.
It imports nothing of the program and nothing of an EVM: what the node
serves over HTTP after the window (balances, storage words, receipts,
logs, blooms) is compared with this, not with the builder's own world.

It is sequential by nature: a call may revert on the state the
transactions before it left, and a pair's price moves with every swap.

Token (EIP-20 as Solidity lays it out): ``balances[a]`` at
keccak(pad32(a) ++ pad32(0)), ``allowances[o][s]`` at keccak(pad32(s) ++
keccak(pad32(o) ++ pad32(1))). Pair p trades token 0 (the hub) against
token p + 1; reserves at slots 0 and 1; ``swap(in, zeroForOne)`` pays
``in * 997 * rOut // (rIn * 1000 + in * 997)``.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark.reference.keccak import keccak256_batch

(KIND_PLAIN, KIND_TRANSFER, KIND_APPROVE, KIND_TRANSFER_FROM, KIND_SWAP,
 KIND_REVERT) = range(2, 8)
KIND_NAMES = {KIND_PLAIN: "plain", KIND_TRANSFER: "transfer",
              KIND_APPROVE: "approve", KIND_TRANSFER_FROM: "transferFrom",
              KIND_SWAP: "swap", KIND_REVERT: "revert"}

SENDER_TOKENS = 1 << 96
ALLOWANCE = 1 << 128
REVERT_AMOUNT = 1 << 200


def _keccak(data: bytes) -> bytes:
    return keccak256_batch([data])[0]


TOPIC_TRANSFER = _keccak(b"Transfer(address,address,uint256)")
TOPIC_APPROVAL = _keccak(b"Approval(address,address,uint256)")
TOPIC_SWAP = _keccak(b"Swap(address,uint256,uint256,bool)")

Log = Tuple[bytes, Tuple[bytes, ...], bytes]  # address, topics, data


def pad32(a: bytes) -> bytes:
    return a.rjust(32, b"\x00")


def word(v: int) -> bytes:
    return v.to_bytes(32, "big")


def balance_slots(holders: Sequence[bytes]) -> List[int]:
    keys = keccak256_batch([pad32(h) + bytes(32) for h in holders])
    return [int.from_bytes(k, "big") for k in keys]


def allowance_slots(pairs: Sequence[Tuple[bytes, bytes]]) -> List[int]:
    """Slot of ``allowances[owner][spender]`` per (owner, spender)."""
    inner = keccak256_batch([pad32(o) + word(1) for o, _ in pairs])
    keys = keccak256_batch([pad32(s) + h for (_, s), h in zip(pairs, inner)])
    return [int.from_bytes(k, "big") for k in keys]


def bloom(logs: Sequence[Log]) -> bytes:
    """The 2048-bit filter over each log's address and topics (Yellow
    Paper 4.4.1): three bits an item, from the low 11 bits of the first
    three byte pairs of its Keccak."""
    items = [x for address, topics, _ in logs for x in (address, *topics)]
    acc = 0
    for h in keccak256_batch(items) if items else []:
        for i in (0, 2, 4):
            acc |= 1 << (((h[i] << 8) | h[i + 1]) & 2047)
    return acc.to_bytes(256, "big")


class Ledger:
    """The state the specification speaks of, with what genesis put
    there answered lazily: a token balance or allowance nobody touched
    is looked up in the seed's arrays, never copied."""

    def __init__(self, state: Dict):
        self.senders: List[bytes] = state["senders"]
        self.others: List[bytes] = state["others"]
        self.tokens: List[bytes] = state["tokens"]
        self.pairs: List[bytes] = state["pairs"]
        self.reserves = [list(r) for r in state["reserves"]]
        self.reserves_at_genesis = [list(r) for r in state["reserves"]]
        self._holders = state["holders"]
        self._holdings = state["holdings"]
        self._sorted: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._sender_of = {a: j for j, a in enumerate(self.senders)}
        self._pair_of = {a: p for p, a in enumerate(self.pairs)}
        self.plain_index = {a: i for i, a in enumerate(self.others)}
        self.balances: List[Dict[bytes, int]] = [{} for _ in self.tokens]
        self.allowances: List[Dict[Tuple[bytes, bytes], int]] = [
            {} for _ in self.tokens]
        self.plain_gained: Dict[int, int] = {}

    # ------------------------------------------------ what genesis held

    def _genesis_balance(self, token: int, who: bytes) -> int:
        if who in self._sender_of:
            return SENDER_TOKENS
        p = self._pair_of.get(who)
        if p is not None:
            sides = (0, p + 1)
            return self.reserves_at_genesis[p][sides.index(token)] \
                if token in sides else 0
        i = self.plain_index[who]
        if token not in self._sorted:
            order = np.argsort(self._holders[token])
            self._sorted[token] = (self._holders[token][order],
                                   self._holdings[token][order])
        keys, held = self._sorted[token]
        at = int(np.searchsorted(keys, i))
        return int(held[at]) if at < len(keys) and keys[at] == i else 0

    def _genesis_allowance(self, token: int, owner: bytes,
                           spender: bytes) -> int:
        j = self._sender_of.get(owner)
        if j is None:
            return 0
        p = self._pair_of.get(spender)
        if p is not None:
            return ALLOWANCE if token in (0, p + 1) else 0
        k = self._sender_of.get(spender)
        n = len(self.senders)
        return ALLOWANCE if k is not None and (k + 1) % n == j else 0

    def balance_of(self, token: int, who: bytes) -> int:
        book = self.balances[token]
        if who not in book:
            book[who] = self._genesis_balance(token, who)
        return book[who]

    def allowance(self, token: int, owner: bytes, spender: bytes) -> int:
        book = self.allowances[token]
        if (owner, spender) not in book:
            book[owner, spender] = self._genesis_allowance(
                token, owner, spender)
        return book[owner, spender]

    # ------------------------------------------------- the specification

    def transfer(self, token: int, caller: bytes, to: bytes, v: int):
        """-> (ok, logs). ``require(balances[caller] >= v)``."""
        if self.balance_of(token, caller) < v:
            return False, []
        self.balances[token][caller] -= v
        self.balances[token][to] = self.balance_of(token, to) + v
        return True, [(self.tokens[token],
                       (TOPIC_TRANSFER, pad32(caller), pad32(to)), word(v))]

    def approve(self, token: int, caller: bytes, spender: bytes, v: int):
        self.allowances[token][caller, spender] = v
        return True, [(self.tokens[token],
                       (TOPIC_APPROVAL, pad32(caller), pad32(spender)),
                       word(v))]

    def transfer_from(self, token: int, caller: bytes, f: bytes, to: bytes,
                      v: int):
        if self.allowance(token, f, caller) < v or \
                self.balance_of(token, f) < v:
            return False, []
        self.balances[token][f] -= v
        self.allowances[token][f, caller] -= v
        self.balances[token][to] = self.balance_of(token, to) + v
        return True, [(self.tokens[token],
                       (TOPIC_TRANSFER, pad32(f), pad32(to)), word(v))]

    def swap(self, pair: int, caller: bytes, amount_in: int, zero_for_one):
        """A revert of either nested call, or of the pair, undoes the
        whole transaction: nothing is kept unless all of it passes."""
        if amount_in == 0:
            return False, []
        here = self.pairs[pair]
        sides = (0, pair + 1)
        i = 0 if zero_for_one else 1
        o = 1 - i
        r_in, r_out = self.reserves[pair][i], self.reserves[pair][o]
        ok, logs_in = self.transfer_from(
            sides[i], here, caller, here, amount_in)
        if not ok:
            return False, []
        fee_in = amount_in * 997
        out = fee_in * r_out // (r_in * 1000 + fee_in)
        # the pair holds its reserve of tokenOut at least, and out < rOut
        # by the formula: this transfer cannot fail, so nothing above
        # ever has to be undone
        ok, logs_out = self.transfer(sides[o], here, caller, out)
        assert ok
        self.reserves[pair][i] = r_in + amount_in
        self.reserves[pair][o] = r_out - out
        return True, logs_in + logs_out + [(
            here, (TOPIC_SWAP, pad32(caller)),
            word(amount_in) + word(out) + word(int(bool(zero_for_one))))]


def fold(state: Dict, picks: Dict, head: int):
    """The ledger after blocks 1..head, and per transaction (block
    index, position) its kind, status and logs."""
    led = Ledger(state)
    n = len(led.senders)
    kind, sender, receiver, amount, token, pair, flag = (
        picks[k][:head].tolist() for k in (
            "kind", "sender", "receiver", "amount", "token", "pair", "flag"))
    receipts: Dict[Tuple[int, int], Tuple[int, int, List[Log]]] = {}
    for b in range(len(kind)):
        for j in range(len(kind[b])):
            k, s, r, v = kind[b][j], sender[b][j], receiver[b][j], amount[b][j]
            caller, to = led.senders[s], led.others[r]
            if k == KIND_PLAIN:
                led.plain_gained[r] = led.plain_gained.get(r, 0) + v
                ok, logs = True, []
            elif k == KIND_TRANSFER:
                ok, logs = led.transfer(token[b][j], caller, to, v)
            elif k == KIND_REVERT:
                ok, logs = led.transfer(
                    token[b][j], caller, to, REVERT_AMOUNT)
            elif k == KIND_APPROVE:
                ok, logs = led.approve(token[b][j], caller, to, v)
            elif k == KIND_TRANSFER_FROM:
                ok, logs = led.transfer_from(
                    token[b][j], caller, led.senders[(s + 1) % n], to, v)
            elif k == KIND_SWAP:
                ok, logs = led.swap(pair[b][j], caller, v, flag[b][j])
            else:
                raise ValueError(f"unknown kind {k}")
            receipts[b, j] = (k, int(ok), logs)
    return led, receipts
