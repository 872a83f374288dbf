"""The plain ledger: balances and token slots after N blocks, folded in
Python from the record of who paid whom. It imports nothing of the
program: what the node serves over HTTP after the window is compared
with this, not with the builder's own world.

Plain account i starts at ``base + extra[i]`` and gains every plain
transfer addressed to it. The fixture token keeps ``balance[holder]`` at
storage slot keccak(pad32(holder) ++ pad32(0)) with unchecked arithmetic
mod 2**256: a ``transfer(to, amount)`` from s subtracts from s's slot
and adds to ``to``'s.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark.reference.keccak import keccak256_batch

KIND_TOKEN, KIND_PLAIN = 1, 2
M256 = (1 << 256) - 1


def plain_balances(base: int, extra: np.ndarray, picks: Dict,
                   head: int) -> List[int]:
    """Balance of every plain account after blocks 1..head."""
    gained = np.zeros(len(extra), dtype=np.int64)
    kind = picks["kind"][:head]
    mask = kind == KIND_PLAIN
    np.add.at(gained, picks["receiver"][:head][mask],
              picks["amount"][:head][mask])
    return [base + int(x) + int(g) for x, g in zip(extra, gained)]


def token_balances(n_plain: int, n_senders: int, picks: Dict, head: int):
    """(plain holders' token balances, senders' token balances) after
    blocks 1..head, the latter mod 2**256."""
    got = np.zeros(n_plain, dtype=np.int64)
    sent = np.zeros(n_senders, dtype=np.int64)
    mask = picks["kind"][:head] == KIND_TOKEN
    np.add.at(got, picks["receiver"][:head][mask],
              picks["amount"][:head][mask])
    np.add.at(sent, picks["sender"][:head][mask],
              picks["amount"][:head][mask])
    return [int(x) for x in got], [(-int(x)) & M256 for x in sent]


def token_slots(holders: List[bytes]) -> List[int]:
    keys = keccak256_batch([h.rjust(32, b"\x00") + bytes(32) for h in holders])
    return [int.from_bytes(k, "big") for k in keys]
