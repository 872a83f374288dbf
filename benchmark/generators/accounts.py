"""Genesis alloc from a seed: funded key-holders plus plain accounts.

After ``chip_smoke.make_alloc`` / ``bench._replay_keys`` (copied; the
originals stay where tests import them), with keys, addresses and
balances drawn from the seed instead of fixed.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

SENDER_BALANCE = 10**24
PLAIN_BALANCE_BASE = 10**18


def make_keys(n: int, seed: int) -> Tuple[List[bytes], List[bytes]]:
    """``n`` private keys and their addresses."""
    from khipu_tpu.base.crypto.secp256k1 import (
        privkey_to_pubkey,
        pubkey_to_address,
    )

    rng = np.random.default_rng([seed, 0x6B657973])
    raw = rng.integers(1, 256, (n, 32), dtype=np.uint8)
    raw[:, 0] = 0  # well under the curve order
    keys = [r.tobytes() for r in raw]
    addrs = [pubkey_to_address(privkey_to_pubkey(k)) for k in keys]
    return keys, addrs


def make_alloc(accounts: int, senders: int, seed: int):
    """(keys, sender addresses, plain addresses, plain balances u64[n],
    alloc dict). Plain balances are PLAIN_BALANCE_BASE + the u64."""
    keys, addrs = make_keys(senders, seed)
    rng = np.random.default_rng([seed, 0x616C6C6F])
    n = accounts - senders
    raw = rng.integers(0, 256, (n, 20), dtype=np.uint8)
    # distinct by construction: the low 4 bytes carry the index
    raw[:, 16:] = np.arange(n, dtype=">u4").view(np.uint8).reshape(n, 4)
    others = [r.tobytes() for r in raw]
    extra = rng.integers(0, 1 << 40, n, dtype=np.int64)
    alloc: Dict[bytes, int] = {a: SENDER_BALANCE for a in addrs}
    for a, x in zip(others, extra.tolist()):
        alloc[a] = PLAIN_BALANCE_BASE + x
    return keys, addrs, others, extra, alloc
