"""Post-Merge-shaped blocks whose contract calls run the bytecode of
``generators/contracts.py``: the picks, and the blocks through
``ChainBuilder`` as ``generators/chain_state.py`` builds them (wire RLP,
the header roots the replay must hit, the plain record the reference
folds).

    senders_pool      a block's txs come one from each sender, in an
                      order shuffled by the seed, so kinds interleave
    mix               share of a block's txs of each kind: ``plain``,
                      ``transfer``, ``approve``, ``transferFrom``,
                      ``swap``, ``revert`` (a ``transfer`` of 2**200)
    token_zipf        a token call picks its contract by Zipf with this
                      exponent over the popularity ranks
    pair_zipf         a swap picks its pair likewise
    new_holder_share  a ``transfer``/``transferFrom`` receiver is, with
                      this probability, uniform over all plain accounts
                      (mostly a new slot); else one of that contract's
                      pre-populated holders (an update)

Every block holds the same count of each kind (largest remainders of
share x txs). Roots come from ``ChainBuilder`` with the host hasher.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.generators.chain import COINBASE, load, save  # noqa: E402

__all__ = ["KINDS", "draw", "build", "load", "save", "payload"]

# the numbers are reference/ledger_contracts.py's (2 is chain.KIND_PLAIN)
KINDS = {"plain": 2, "transfer": 3, "approve": 4, "transferFrom": 5,
         "swap": 6, "revert": 7}
REVERT_AMOUNT = 1 << 200
GAS = {2: 21_000, 3: 150_000, 4: 150_000, 5: 150_000, 6: 400_000,
       7: 150_000}


def kind_counts(mix: Dict[str, float], txs: int) -> Dict[str, int]:
    """Whole transactions of each kind in a block of ``txs``: floors of
    share x txs, the rest by largest remainder (ties by KINDS' order)."""
    exact = {k: float(mix.get(k, 0.0)) * txs for k in KINDS}
    counts = {k: int(v) for k, v in exact.items()}
    rest = sorted(KINDS, key=lambda k: counts[k] - exact[k])
    for k in rest[: txs - sum(counts.values())]:
        counts[k] += 1
    return counts


def _zipf(rng, n: int, exponent: float, shape):
    weight = 1.0 / np.arange(1, n + 1) ** float(exponent)
    return rng.choice(n, shape, p=weight / weight.sum())


def draw(params: Dict, blocks: int, txs: int, n_plain: int,
         holders: List[np.ndarray], n_pairs: int, seed: int) -> Dict:
    """The seeded picks, as arrays [blocks, txs]: kind, sender index,
    receiver index (into the plain accounts: the payee, or an approve's
    spender), amount, token and pair (0-based rank; -1 where the kind
    has none) and flag (a swap's zeroForOne)."""
    rng = np.random.default_rng([seed, 0x636F6E7472])
    shape = (blocks, txs)
    pool = int(params["senders_pool"])
    sender = rng.permuted(np.tile(np.arange(txs) % pool, (blocks, 1)), axis=1)
    counts = kind_counts(params["mix"], txs)
    row = np.concatenate([np.full(n, KINDS[k]) for k, n in counts.items()])
    kind = rng.permuted(np.tile(row, (blocks, 1)), axis=1)
    on_token = np.isin(kind, [KINDS[k] for k in (
        "transfer", "approve", "transferFrom", "revert")])
    pays = np.isin(kind, [KINDS["transfer"], KINDS["transferFrom"]])
    token = _zipf(rng, len(holders), params["token_zipf"], shape)
    pair = _zipf(rng, n_pairs, params["pair_zipf"], shape)
    counts_h = np.array([len(h) for h in holders])
    starts = np.concatenate([[0], np.cumsum(counts_h)[:-1]])
    within = (rng.random(shape) * counts_h[token]).astype(np.int64)
    existing = np.concatenate(holders)[starts[token] + within]
    anyone = rng.integers(0, n_plain, shape)
    fresh = rng.random(shape) < float(params["new_holder_share"])
    receiver = np.where(pays & ~fresh, existing, anyone)
    amount = rng.integers(1_000, 1 << 20, shape)
    amount = np.where(kind == KINDS["swap"],
                      rng.integers(1 << 10, 1 << 20, shape), amount)
    amount = np.where(kind == KINDS["approve"],
                      rng.integers(1 << 20, 1 << 40, shape), amount)
    return {"kind": kind, "sender": sender, "receiver": receiver,
            "amount": amount, "token": np.where(on_token, token, -1),
            "pair": np.where(kind == KINDS["swap"], pair, -1),
            "flag": rng.integers(0, 2, shape)}


def _word(v: int) -> bytes:
    return v.to_bytes(32, "big")


def payload(kind: int, to: bytes, amount: int, spent_from: bytes,
            flag: int) -> bytes:
    """ABI calldata of one contract call: 4-byte selector, 32-byte words."""
    from benchmark.generators import contracts as C

    pad = C.pad32
    if kind == KINDS["transfer"]:
        return C.SEL_TRANSFER.to_bytes(4, "big") + pad(to) + _word(amount)
    if kind == KINDS["revert"]:
        return C.SEL_TRANSFER.to_bytes(4, "big") + pad(to) + \
            _word(REVERT_AMOUNT)
    if kind == KINDS["approve"]:
        return C.SEL_APPROVE.to_bytes(4, "big") + pad(to) + _word(amount)
    if kind == KINDS["transferFrom"]:
        return C.SEL_TRANSFER_FROM.to_bytes(4, "big") + pad(spent_from) + \
            pad(to) + _word(amount)
    if kind == KINDS["swap"]:
        return C.SEL_SWAP.to_bytes(4, "big") + _word(amount) + _word(flag)
    raise ValueError(f"kind {kind} carries no calldata")


def transactions(state: Dict, picks: Dict, n: int, nonces: List[int]):
    """Block ``n``'s unsigned transactions with their senders' indexes,
    advancing ``nonces``."""
    from khipu_tpu.domain.transaction import Transaction

    senders, others = state["senders"], state["others"]
    out = []
    for j in range(picks["kind"].shape[1]):
        k, s, amt = (int(picks[f][n, j]) for f in ("kind", "sender", "amount"))
        to = others[int(picks["receiver"][n, j])]
        if k == KINDS["plain"]:
            tx = Transaction(nonces[s], 10**9, GAS[k], to, amt)
        else:
            target = (state["pairs"][int(picks["pair"][n, j])]
                      if k == KINDS["swap"]
                      else state["tokens"][int(picks["token"][n, j])])
            tx = Transaction(
                nonces[s], 10**9, GAS[k], target, 0, payload=payload(
                    k, to, amt, senders[(s + 1) % len(senders)],
                    int(picks["flag"][n, j])))
        nonces[s] += 1
        out.append((s, tx))
    return out


def build(spec, state: Dict, picks: Dict, head_blocks: int = 0,
          on_head=None):
    """Blocks through ``ChainBuilder``; returns (wire RLP per block,
    header state roots). ``on_head(wire, roots)`` is called once
    ``head_blocks`` blocks exist."""
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import Blockchain
    from khipu_tpu.domain.transaction import sign_transaction
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.chain_builder import ChainBuilder

    cfg = fixture_config(chain_id=1)
    builder = ChainBuilder(Blockchain(Storages(), cfg), cfg, spec)
    keys = state["keys"]
    nonces = [0] * len(keys)
    wire, roots = [], []
    for n in range(len(picks["kind"])):
        block = builder.add_block(
            [sign_transaction(tx, keys[s], chain_id=1)
             for s, tx in transactions(state, picks, n, nonces)],
            coinbase=COINBASE)
        wire.append(block.encode())
        roots.append(block.header.state_root)
        if on_head and n + 1 == head_blocks:
            on_head(wire, roots)
    return wire, roots


def main(argv) -> int:
    """``python chain_contracts.py '<json>'``: build the chain of one
    (sizes, traffic, seed) into ``out``, writing ``head_out`` as soon as
    the first ``head_blocks`` exist (``chain_state.main``'s contract)."""
    from benchmark.generators import contracts as gen_contracts
    from khipu_tpu.domain.blockchain import GenesisSpec

    a = json.loads(argv[1])
    state = gen_contracts.make_state(a["sizes"], a["seed"])
    spec = GenesisSpec(alloc=state["alloc"], gas_limit=a["gas_limit"])
    picks = draw(a["params"], a["blocks"], int(a["sizes"]["txs_per_block"]),
                 len(state["others"]), state["holders"],
                 len(state["pairs"]), a["seed"])
    wire, roots = build(
        spec, state, picks, head_blocks=a["head_blocks"],
        on_head=lambda w, r: save(a["head_out"], w, r, b""))
    save(a["out"], wire, roots, b"")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
