"""A mainnet-shaped genesis from a seed: the plain accounts and funded
key-holders of ``accounts.make_alloc``, plus ``token_contracts`` ERC-20
contracts whose storage is already populated.

The contract of popularity rank r (1-based) holds ``round(token_slots /
(r * H_n))`` holders, H_n the n-th harmonic number: Zipf with exponent
1 over holder counts, ``token_slots`` in all (to rounding). Its holders
are drawn without replacement from the plain accounts; holder h's
balance lives at storage slot keccak(pad32(h) ++ pad32(0)) and is a
seeded non-zero u64. Every contract runs ``chain.ERC20_RUNTIME`` followed
by its rank as two dead bytes after the final STOP (as a compiler's
metadata trailer is): as many code hashes as contracts, one behaviour.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark.generators import accounts as gen_accounts
from benchmark.generators.chain import ERC20_RUNTIME


def holder_counts(contracts: int, slots: int) -> List[int]:
    """Holders of the contract of each rank, 1-based rank r at r - 1."""
    harmonic = sum(1.0 / r for r in range(1, contracts + 1))
    return [max(1, round(slots / (r * harmonic)))
            for r in range(1, contracts + 1)]


def token_code(rank: int) -> bytes:
    return ERC20_RUNTIME + rank.to_bytes(2, "big")


def balance_slots(holders: List[bytes]) -> List[int]:
    """Storage slot of each holder's token balance."""
    from khipu_tpu.native.keccak import keccak256_batch

    keys = keccak256_batch([h.rjust(32, b"\x00") + bytes(32)
                            for h in holders])
    return [int.from_bytes(k, "big") for k in keys]


def make_state(sizes: Dict, seed: int) -> Dict:
    """The seed's state: ``keys``, ``senders``, ``others``, ``extra`` as
    ``accounts.make_alloc`` gives them; ``tokens`` (contract addresses by
    rank), ``holders`` (per contract, indexes into ``others``),
    ``holdings`` (per contract, the holders' u64 balances) and ``alloc``
    (address -> balance or ``GenesisAccount``)."""
    from khipu_tpu.domain.blockchain import GenesisAccount

    keys, senders, others, extra, alloc = gen_accounts.make_alloc(
        int(sizes["accounts"]), int(sizes["funded_senders"]), seed)
    contracts = int(sizes["token_contracts"])
    rng = np.random.default_rng([seed, 0x746F6B656E])
    raw = rng.integers(0, 256, (contracts, 20), dtype=np.uint8)
    # distinct by construction: the rank, and low bytes that no plain
    # account's index (make_alloc) reaches
    raw[:, 14:16] = np.arange(1, contracts + 1, dtype=">u2").view(
        np.uint8).reshape(contracts, 2)
    raw[:, 16:] = 0xFF
    tokens = [r.tobytes() for r in raw]
    holders, holdings = [], []
    for rank, count in enumerate(
            holder_counts(contracts, int(sizes["token_slots"])), 1):
        who = rng.choice(len(others), min(count, len(others)), replace=False)
        held = rng.integers(1, 1 << 64, len(who), dtype=np.uint64)
        slots = balance_slots([others[i] for i in who.tolist()])
        alloc[tokens[rank - 1]] = GenesisAccount(
            code=token_code(rank), storage=dict(zip(slots, held.tolist())))
        holders.append(who)
        holdings.append(held)
    return {"keys": keys, "senders": senders, "others": others,
            "extra": extra, "alloc": alloc, "tokens": tokens,
            "holders": holders, "holdings": holdings}
