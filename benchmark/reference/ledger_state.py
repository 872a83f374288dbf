"""The plain ledger of ``fullsync-postmerge-deep``: balances of plain
accounts and, per token contract, holders' token balances after N
blocks, folded in Python/NumPy from the record of who paid whom and the
seeded initial holdings. It imports nothing of the program: what the
node serves over HTTP after the window is compared with this, not with
the builder's own world.

Plain account i starts at ``base + extra[i]`` and gains every plain
transfer addressed to it. Every token contract keeps ``balance[holder]``
at storage slot keccak(pad32(holder) ++ pad32(0)) with unchecked
arithmetic mod 2**256: a ``transfer(to, amount)`` from s subtracts from
s's slot and adds to ``to``'s. Pre-populated holders start at their
seeded holding; everyone else (the senders too) starts at 0.
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
    mask = picks["kind"][:head] == KIND_PLAIN
    np.add.at(gained, picks["receiver"][:head][mask],
              picks["amount"][:head][mask])
    return [base + int(x) + int(g) for x, g in zip(extra, gained)]


def _sums(index: np.ndarray, amount: np.ndarray) -> Dict[int, int]:
    who, inverse = np.unique(index, return_inverse=True)
    total = np.zeros(len(who), dtype=np.int64)
    np.add.at(total, inverse, amount)
    return dict(zip(who.tolist(), total.tolist()))


def contract_ledger(contract: int, holders: np.ndarray, holdings: np.ndarray,
                    picks: Dict, head: int) -> Dict[str, Dict[int, int]]:
    """One contract's token balances after blocks 1..head, by what
    happened to the slot. Keys are indexes into the plain accounts,
    except ``senders``' (indexes into the funded key-holders):

        untouched  pre-populated, never credited: the seeded holding
        updated    pre-populated and credited
        created    credited, not pre-populated: a slot made by a block
        senders    debited (from 0, so the balance wraps mod 2**256)
    """
    mask = picks["token"][:head] == contract
    amount = picks["amount"][:head][mask]
    credited = _sums(picks["receiver"][:head][mask], amount)
    debited = _sums(picks["sender"][:head][mask], amount)
    initial = dict(zip(holders.tolist(), holdings.tolist()))
    return {
        "untouched": {i: v for i, v in initial.items() if i not in credited},
        "updated": {i: (initial[i] + g) & M256
                    for i, g in credited.items() if i in initial},
        "created": {i: g for i, g in credited.items() if i not in initial},
        "senders": {s: (-d) & M256 for s, d in debited.items()},
    }


def token_slots(holders: List[bytes]) -> List[int]:
    keys = keccak256_batch([h.rjust(32, b"\x00") + bytes(32) for h in holders])
    return [int.from_bytes(k, "big") for k in keys]
