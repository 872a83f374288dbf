"""Post-Merge-shaped blocks over the pre-populated state of
``generators/state.py``: the picks, and the blocks through
``ChainBuilder`` as ``generators/chain.py`` builds them (wire RLP, the
header roots the replay must hit, the plain record of who paid whom).

    senders_pool      tx j of a block comes from sender j % senders_pool
    token_share       fraction of a block's txs that are ERC-20
                      ``transfer`` calls; the rest are plain transfers to
                      receivers uniform over the plain accounts
    token_zipf        each token tx picks its contract by Zipf with this
                      exponent over the popularity ranks
    new_holder_share  a token tx's receiver is, with this probability,
                      uniform over all plain accounts (mostly an insert
                      of a new slot); else uniform over that contract's
                      pre-populated holders (an update of an existing one)

No deploy transaction: the contracts are in genesis. Roots come from
``ChainBuilder`` with the host hasher: the host's Python MPT, which
shares no code with the fused device commit.
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

from benchmark.generators.chain import (  # noqa: E402
    COINBASE,
    KIND_PLAIN,
    KIND_TOKEN,
    load,
    save,
)

__all__ = ["KIND_PLAIN", "KIND_TOKEN", "draw", "build", "load", "save"]


def draw(params: Dict, blocks: int, txs: int, n_plain: int,
         holders: List[np.ndarray], seed: int) -> Dict:
    """The seeded picks, as arrays [blocks, txs]: kind, sender index,
    receiver index (into the plain accounts), amount, and token (the
    contract's 0-based rank index; -1 on a plain transfer)."""
    rng = np.random.default_rng([seed, 0x64656570])
    shape = (blocks, txs)
    pool = int(params["senders_pool"])
    sender = np.tile(np.arange(txs) % pool, (blocks, 1))
    n_token = int(round(txs * float(params["token_share"])))
    kind = np.full(shape, KIND_PLAIN)
    kind[:, :n_token] = KIND_TOKEN
    weight = 1.0 / np.arange(1, len(holders) + 1) ** float(
        params["token_zipf"])
    token = rng.choice(len(holders), shape, p=weight / weight.sum())
    # a pre-populated holder of the picked contract, through one flat
    # array of all contracts' holders
    counts = np.array([len(h) for h in holders])
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    within = (rng.random(shape) * counts[token]).astype(np.int64)
    existing = np.concatenate(holders)[starts[token] + within]
    anyone = rng.integers(0, n_plain, shape)
    fresh = rng.random(shape) < float(params["new_holder_share"])
    receiver = np.where((kind == KIND_PLAIN) | fresh, anyone, existing)
    amount = rng.integers(1_000, 1 << 20, shape)
    return {"kind": kind, "sender": sender, "receiver": receiver,
            "amount": amount, "token": np.where(kind == KIND_TOKEN, token, -1)}


def build(spec, state: Dict, picks: Dict, head_blocks: int = 0,
          on_head=None):
    """Blocks through ``ChainBuilder``; returns (wire RLP per block,
    header state roots). ``on_head(wire, roots)`` is called once
    ``head_blocks`` blocks exist."""
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import Blockchain
    from khipu_tpu.domain.transaction import Transaction, sign_transaction
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.chain_builder import ChainBuilder

    cfg = fixture_config(chain_id=1)
    builder = ChainBuilder(Blockchain(Storages(), cfg), cfg, spec)
    keys, others, tokens = state["keys"], state["others"], state["tokens"]
    nonces = [0] * len(keys)
    wire, roots = [], []
    kind, sender, receiver, amount, token = (
        picks[k].tolist()
        for k in ("kind", "sender", "receiver", "amount", "token"))
    for n in range(len(kind)):
        txs = []
        for j in range(len(kind[n])):
            s, rcpt, amt = sender[n][j], others[receiver[n][j]], amount[n][j]
            if kind[n][j] == KIND_TOKEN:
                tx = Transaction(
                    nonces[s], 10**9, 100_000, tokens[token[n][j]], 0,
                    payload=rcpt.rjust(32, b"\x00") + amt.to_bytes(32, "big"))
            else:
                tx = Transaction(nonces[s], 10**9, 21_000, rcpt, amt)
            txs.append(sign_transaction(tx, keys[s], chain_id=1))
            nonces[s] += 1
        block = builder.add_block(txs, coinbase=COINBASE)
        wire.append(block.encode())
        roots.append(block.header.state_root)
        if on_head and n + 1 == head_blocks:
            on_head(wire, roots)
    return wire, roots


def main(argv) -> int:
    """``python chain_state.py '<json>'``: build the chain of one (sizes,
    traffic, seed) into ``out``, writing ``head_out`` as soon as the
    first ``head_blocks`` exist. The driver runs this as a child process
    (JAX held to the CPU there) beside its own genesis build and the
    node's warm-up on the head."""
    from benchmark.generators import state as gen_state
    from khipu_tpu.domain.blockchain import GenesisSpec

    a = json.loads(argv[1])
    state = gen_state.make_state(a["sizes"], a["seed"])
    spec = GenesisSpec(alloc=state["alloc"], gas_limit=a["gas_limit"])
    picks = draw(a["params"], a["blocks"], int(a["sizes"]["txs_per_block"]),
                 len(state["others"]), state["holders"], a["seed"])
    wire, roots = build(
        spec, state, picks, head_blocks=a["head_blocks"],
        on_head=lambda w, r: save(a["head_out"], w, r, b""))
    save(a["out"], wire, roots, b"")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
