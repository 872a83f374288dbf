"""A post-Merge-shaped chain from a seed, as wire RLP with the header
roots the replay must hit, and the plain record of who paid whom that
the reference ledger folds.

After ``chip_smoke.build_chain`` (BASELINE config #4's shape; copied),
with the sender and receiver distributions as parameters:

    senders_pool     how many of the funded key-holders send; a block's
                     tx j comes from sender j % senders_pool
    receivers        {"dist": "uniform"} over the plain accounts, or
                     {"dist": "hotspot", "hot_set": n, "hot_share": p}
                     (YCSB's hotspot: p of the picks fall in the first
                     n plain accounts of a seeded permutation)
    token_share      fraction of a block's txs that are ERC-20
                     ``transfer`` calls (block 1 deploys the token)

Roots come from ``ChainBuilder`` with the host hasher: the host's
Python MPT, which shares no code with the fused device commit.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict

import numpy as np

# the transfer-only ERC-20 fixture of bench.py (_ERC20_RUNTIME/_INIT),
# copied: slot keccak(pad32(holder) ++ pad32(0)) is the holder's
# balance, arithmetic is unchecked mod 2**256
ERC20_RUNTIME = bytes([
    0x33, 0x60, 0x00, 0x52, 0x60, 0x00, 0x60, 0x20, 0x52, 0x60, 0x40,
    0x60, 0x00, 0x20, 0x80, 0x54, 0x60, 0x20, 0x35, 0x90, 0x03, 0x90,
    0x55, 0x60, 0x00, 0x35, 0x60, 0x00, 0x52, 0x60, 0x40, 0x60, 0x00,
    0x20, 0x80, 0x54, 0x60, 0x20, 0x35, 0x01, 0x90, 0x55, 0x00,
])
ERC20_INIT = bytes([
    0x60, len(ERC20_RUNTIME), 0x60, 0x0C, 0x60, 0x00, 0x39,
    0x60, len(ERC20_RUNTIME), 0x60, 0x00, 0xF3,
]) + ERC20_RUNTIME

KIND_DEPLOY, KIND_TOKEN, KIND_PLAIN = 0, 1, 2
COINBASE = b"\xaa" * 20


def draw(params: Dict, blocks: int, txs: int, n_plain: int, seed: int):
    """The seeded picks, as arrays [blocks, txs]: kind, sender index,
    receiver index (into the plain accounts), amount."""
    rng = np.random.default_rng([seed, 0x636861696E])
    pool = int(params["senders_pool"])
    sender = np.tile(np.arange(txs) % pool, (blocks, 1))
    rcv = params["receivers"]
    if rcv["dist"] == "uniform":
        receiver = rng.integers(0, n_plain, (blocks, txs))
    elif rcv["dist"] == "hotspot":
        hot = rng.permutation(n_plain)[: int(rcv["hot_set"])]
        in_hot = rng.random((blocks, txs)) < float(rcv["hot_share"])
        receiver = np.where(
            in_hot, hot[rng.integers(0, len(hot), (blocks, txs))],
            rng.integers(0, n_plain, (blocks, txs)))
    else:
        raise ValueError(f"unknown receiver distribution {rcv['dist']!r}")
    amount = rng.integers(1_000, 1 << 20, (blocks, txs))
    n_token = int(round(txs * float(params["token_share"])))
    kind = np.full((blocks, txs), KIND_PLAIN)
    kind[1:, :n_token] = KIND_TOKEN
    kind[0, 0] = KIND_DEPLOY
    return {"kind": kind, "sender": sender, "receiver": receiver,
            "amount": amount}


def build(spec, keys, senders, others, picks: Dict, log=None,
          head_blocks: int = 0, on_head=None):
    """Blocks through ``ChainBuilder``; returns (wire RLP per block,
    header state roots, token address). ``on_head(wire, roots, token)``
    is called once ``head_blocks`` blocks exist."""
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import Blockchain
    from khipu_tpu.domain.transaction import (
        Transaction,
        contract_address,
        sign_transaction,
    )
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.chain_builder import ChainBuilder

    cfg = fixture_config(chain_id=1)
    chain = Blockchain(Storages(), cfg)
    builder = ChainBuilder(chain, cfg, spec)
    token = contract_address(senders[0], 0)
    nonces = [0] * len(keys)
    wire, roots = [], []
    kind, sender, receiver, amount = (
        picks[k].tolist() for k in ("kind", "sender", "receiver", "amount"))
    for n in range(len(kind)):
        txs = []
        for j in range(len(kind[n])):
            s, rcpt, amt = sender[n][j], others[receiver[n][j]], amount[n][j]
            if kind[n][j] == KIND_DEPLOY:
                tx = Transaction(nonces[s], 10**9, 500_000, None, 0,
                                 payload=ERC20_INIT)
            elif kind[n][j] == KIND_TOKEN:
                tx = Transaction(
                    nonces[s], 10**9, 100_000, token, 0,
                    payload=rcpt.rjust(32, b"\x00") + amt.to_bytes(32, "big"))
            else:
                tx = Transaction(nonces[s], 10**9, 21_000, rcpt, amt)
            txs.append(sign_transaction(tx, keys[s], chain_id=1))
            nonces[s] += 1
        block = builder.add_block(txs, coinbase=COINBASE)
        wire.append(block.encode())
        roots.append(block.header.state_root)
        if on_head and n + 1 == head_blocks:
            on_head(wire, roots, token)
        if log and (n + 1) % 64 == 0:
            log(f"chain: built {n + 1} blocks")
    return wire, roots, token


def save(path: str, wire, roots, token) -> None:
    tmp = path + ".tmp.npz"
    np.savez(
        tmp, blob=np.frombuffer(b"".join(wire), dtype=np.uint8),
        lens=np.array([len(w) for w in wire]),
        roots=np.frombuffer(b"".join(roots), dtype=np.uint8),
        token=np.frombuffer(token, dtype=np.uint8))
    os.replace(tmp, path)


def load(path: str):
    """(wire RLP per block, header state roots, token address)."""
    with np.load(path) as z:
        blob, lens = z["blob"].tobytes(), z["lens"].tolist()
        roots_raw, token = z["roots"].tobytes(), z["token"].tobytes()
    wire, pos = [], 0
    for n in lens:
        wire.append(blob[pos: pos + n])
        pos += n
    roots = [roots_raw[i: i + 32] for i in range(0, len(roots_raw), 32)]
    return wire, roots, token


def main(argv) -> int:
    """``python chain.py '<json>'``: build the chain of one (sizes,
    traffic, seed) into ``out``, writing ``head_out`` as soon as the
    first ``head_blocks`` exist. The driver runs this as a child process
    (JAX held to the CPU there) so that a new seed's chain is built
    while the parent warms the node up on the head."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.generators import accounts as gen_accounts
    from khipu_tpu.domain.blockchain import GenesisSpec

    a = json.loads(argv[1])
    keys, senders, others, _extra, alloc = gen_accounts.make_alloc(
        a["accounts"], a["funded"], a["seed"])
    spec = GenesisSpec(alloc=alloc, gas_limit=a["gas_limit"])
    picks = draw(a["params"], a["blocks"], a["txs"], len(others), a["seed"])
    wire, roots, token = build(
        spec, keys, senders, others, picks, head_blocks=a["head_blocks"],
        on_head=lambda w, r, t: save(a["head_out"], w, r, t))
    save(a["out"], wire, roots, token)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
