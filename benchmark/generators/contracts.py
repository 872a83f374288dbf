"""The two contracts of ``fullsync-postmerge-contracts`` and the state
they stand in: an EIP-20 token as Solidity lays it out (selector
dispatch, ``balances`` at slot 0, ``allowances`` at slot 1, ``require``,
events) and a constant-product pair after Uniswap V2's ``swap`` cut to
one hop (two reserve slots, two nested calls into tokens, a ``Swap``
event). ``docs/deployments.md`` has the specification and the annotated
listing; ``benchmark/reference/ledger_contracts.py`` implements the
specification, this file is what the node runs.

``make_state`` is ``state.make_state`` (the deep deployment's accounts,
256 tokens and 2^20 pre-populated holder slots, seed for seed) with the
tokens' code replaced and, on top:

    every sender holds SENDER_TOKENS of every token
    every sender allows every pair ALLOWANCE on both of its tokens
    sender j is allowed ALLOWANCE by sender (j + 1) % n on every token
    every pair holds seeded reserves of both its tokens, in its own
    slots 0/1 and in the tokens' ``balances``
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from benchmark.generators import state as gen_state
from benchmark.generators.asm import assemble

SEL_TRANSFER = 0xA9059CBB        # transfer(address,uint256)
SEL_APPROVE = 0x095EA7B3         # approve(address,uint256)
SEL_TRANSFER_FROM = 0x23B872DD   # transferFrom(address,address,uint256)
SEL_BALANCE_OF = 0x70A08231      # balanceOf(address)
SEL_SWAP = 0x2AEA6605            # swap(uint256,bool)
# keccak("Transfer(address,address,uint256)"), ("Approval(address,address,
# uint256)"), ("Swap(address,uint256,uint256,bool)"); a test re-derives
# all seven constants with the reference's own Keccak
TOPIC_TRANSFER = int(
    "ddf252ad1be2c89b69c2b068fc378daa952ba7f163c4a11628f55a4df523b3ef", 16)
TOPIC_APPROVAL = int(
    "8c5be1e5ebec7d5bd14f71427d1e84f3dd0314c0f7b2291e5b200ac8c7c3b925", 16)
TOPIC_SWAP = int(
    "cc65e4d9060ece2ecf63011ac580550b04c8daeba63fac4dfe8669353cd88859", 16)

SENDER_TOKENS = 1 << 96
ALLOWANCE = 1 << 128
RESERVE_LO, RESERVE_HI = 1 << 50, 1 << 60
ADDR_MASK = ("push", (1 << 160) - 1, 20)

_SELECTOR = [0, "CALLDATALOAD", 0xE0, "SHR"]
_RETURN_TRUE = [1, 0, "MSTORE", 0x20, 0, "RETURN"]


def _map_slot(base: int) -> List:
    """[.., key] -> [.., keccak(pad32(key) ++ pad32(base))]."""
    return [0, "MSTORE", base, 0x20, "MSTORE", 0x40, 0, "SHA3"]


def _arg_address(offset: int) -> List:
    return [offset, "CALLDATALOAD", ADDR_MASK, "AND"]


TOKEN_PROGRAM: List = [
    # ---- dispatcher: no value, then the selector
    "CALLVALUE", "@revert", "JUMPI",
    *_SELECTOR,
    "DUP1", ("push", SEL_TRANSFER, 4), "EQ", "@transfer", "JUMPI",
    "DUP1", ("push", SEL_TRANSFER_FROM, 4), "EQ", "@transferFrom", "JUMPI",
    "DUP1", ("push", SEL_APPROVE, 4), "EQ", "@approve", "JUMPI",
    "DUP1", ("push", SEL_BALANCE_OF, 4), "EQ", "@balanceOf", "JUMPI",
    ":revert", 0, 0, "REVERT",

    # ---- transfer(to, v)
    ":transfer", "POP",
    "CALLER", *_map_slot(0),               # [slotFrom]
    "DUP1", "SLOAD",                       # [slotFrom, balFrom]
    0x24, "CALLDATALOAD",                  # [slotFrom, balFrom, v]
    "DUP1", "DUP3", "LT", "@revert", "JUMPI",   # require(balFrom >= v)
    "DUP1", "SWAP2", "SUB",                # [slotFrom, v, balFrom - v]
    "SWAP1", "SWAP2", "SSTORE",            # balances[caller] -= v; [v]
    *_arg_address(4),                      # [v, to]
    "DUP1", *_map_slot(0),                 # [v, to, slotTo]
    "DUP1", "SLOAD", "DUP4", "ADD",        # [v, to, slotTo, balTo + v]
    "SWAP1", "SSTORE",                     # balances[to] += v; [v, to]
    "SWAP1", 0, "MSTORE",                  # data = v; [to]
    "CALLER", ("push", TOPIC_TRANSFER, 32), 0x20, 0, "LOG3",
    *_RETURN_TRUE,

    # ---- transferFrom(f, to, v)
    ":transferFrom", "POP",
    *_arg_address(4),                      # [f]
    "DUP1", *_map_slot(1),                 # [f, keccak(f, 1)]
    0x20, "MSTORE", "CALLER", 0, "MSTORE", 0x40, 0, "SHA3",  # [f, aslot]
    "DUP1", "SLOAD",                       # [f, aslot, allow]
    0x44, "CALLDATALOAD",                  # [f, aslot, allow, v]
    "DUP1", "DUP3", "LT", "@revert", "JUMPI",   # require(allow >= v)
    "DUP4", *_map_slot(0),                 # [f, aslot, allow, v, bslot]
    "DUP1", "SLOAD",                       # [.., v, bslot, bal]
    "DUP3", "DUP2", "LT", "@revert", "JUMPI",   # require(bal >= v)
    "DUP3", "SWAP1", "SUB",                # [.., v, bslot, bal - v]
    "SWAP1", "SSTORE",                     # balances[f] -= v
    "DUP1", "SWAP2", "SUB",                # [f, aslot, v, allow - v]
    "DUP3", "SSTORE",                      # allowances[f][caller] -= v
    *_arg_address(0x24),                   # [f, aslot, v, to]
    "DUP1", *_map_slot(0),                 # [f, aslot, v, to, tslot]
    "DUP1", "SLOAD", "DUP4", "ADD", "SWAP1", "SSTORE",  # balances[to] += v
    "DUP2", 0, "MSTORE",                   # data = v; [f, aslot, v, to]
    "DUP4", ("push", TOPIC_TRANSFER, 32), 0x20, 0, "LOG3",
    *_RETURN_TRUE,

    # ---- approve(s, v)
    ":approve", "POP",
    "CALLER", *_map_slot(1),               # [keccak(caller, 1)]
    0x20, "MSTORE",
    *_arg_address(4),                      # [s]
    "DUP1", 0, "MSTORE", 0x40, 0, "SHA3",  # [s, aslot]
    0x24, "CALLDATALOAD",                  # [s, aslot, v]
    "DUP1", "SWAP2", "SSTORE",             # allowances[caller][s] = v; [s, v]
    0, "MSTORE",                           # data = v; [s]
    "CALLER", ("push", TOPIC_APPROVAL, 32), 0x20, 0, "LOG3",
    *_RETURN_TRUE,

    # ---- balanceOf(a)
    ":balanceOf", "POP",
    *_arg_address(4), *_map_slot(0), "SLOAD",
    0, "MSTORE", 0x20, 0, "RETURN",
]


def _call_token(in_size: int, out_off: int, token_index_depth: int) -> List:
    """CALL the token whose index (0/1) sits ``token_index_depth`` deep
    once the five constant arguments are pushed; calldata is in memory
    from 0. Leaves the success flag, then requires it and a returned
    ``true`` at ``out_off``."""
    return [
        0x20, out_off, in_size, 0, 0,      # outSize outOff inSize inOff value
        f"DUP{token_index_depth + 5}", 2, "ADD", "SLOAD", "GAS", "CALL",
        "ISZERO", "@revert", "JUMPI",
        out_off, "MLOAD", 1, "EQ", "ISZERO", "@revert", "JUMPI",
    ]


PAIR_PROGRAM: List = [
    "CALLVALUE", "@revert", "JUMPI",
    *_SELECTOR,
    ("push", SEL_SWAP, 4), "EQ", "@swap", "JUMPI",
    ":revert", 0, 0, "REVERT",

    # ---- swap(amountIn, zeroForOne)
    ":swap",
    4, "CALLDATALOAD",                     # [in]
    "DUP1", "ISZERO", "@revert", "JUMPI",  # require(in != 0)
    0x24, "CALLDATALOAD", "ISZERO",        # [in, i]  i: index of tokenIn
    "DUP1", "ISZERO",                      # [in, i, o]  o = zeroForOne
    # tokenIn.transferFrom(caller, this, in)
    ("push", SEL_TRANSFER_FROM << 224, 32), 0, "MSTORE",
    "CALLER", 4, "MSTORE", "ADDRESS", 0x24, "MSTORE",
    "DUP3", 0x44, "MSTORE",
    *_call_token(0x64, 0x80, 2),             # token[i]
    # amountOut = in * 997 * rOut / (rIn * 1000 + in * 997)
    "DUP2", "SLOAD", "DUP2", "SLOAD",      # [in, i, o, rIn, rOut]
    "DUP5", 997, "MUL",                    # [.., rIn, rOut, inFee]
    "DUP1", "DUP3", "MUL",                 # [.., rIn, rOut, inFee, num]
    "DUP4", 1000, "MUL", "DUP3", "ADD",    # [.., inFee, num, den]
    "SWAP1", "DIV", "SWAP1", "POP",        # [in, i, o, rIn, rOut, out]
    # tokenOut.transfer(caller, out)
    ("push", SEL_TRANSFER << 224, 32), 0, "MSTORE",
    "CALLER", 4, "MSTORE", "DUP1", 0x24, "MSTORE",
    *_call_token(0x44, 0xA0, 4),        # token[o]
    "DUP1", "SWAP2", "SUB", "DUP4", "SSTORE",   # reserve[o] = rOut - out
    "SWAP1", "DUP5", "ADD", "DUP4", "SSTORE",   # reserve[i] = rIn + in
    # [in, i, o, out]; Swap(caller; in, out, zeroForOne)
    "DUP4", 0, "MSTORE", "DUP1", 0x20, "MSTORE", "DUP2", 0x40, "MSTORE",
    "CALLER", ("push", TOPIC_SWAP, 32), 0x60, 0, "LOG2",
    0, "MSTORE", 0x20, 0, "RETURN",
]

TOKEN_RUNTIME = assemble(TOKEN_PROGRAM)
PAIR_RUNTIME = assemble(PAIR_PROGRAM)


def token_code(rank: int) -> bytes:
    """Rank as two dead bytes after the code, as a compiler's metadata
    trailer is: as many code hashes as contracts (deep's rule)."""
    return TOKEN_RUNTIME + rank.to_bytes(2, "big")


def pair_code(index: int) -> bytes:
    return PAIR_RUNTIME + (index + 1).to_bytes(2, "big")


def pad32(a: bytes) -> bytes:
    return a.rjust(32, b"\x00")


def allowance_slots(owners: Sequence[bytes],
                    spenders: Sequence[bytes]) -> List[int]:
    """``allowances[o][s]`` at keccak(pad32(s) ++ keccak(pad32(o) ++
    pad32(1))), for each (owner, spender) pair of the two lists."""
    from khipu_tpu.native.keccak import keccak256_batch

    one = (1).to_bytes(32, "big")
    inner = keccak256_batch([pad32(o) + one for o in owners])
    keys = keccak256_batch([pad32(s) + h for s, h in zip(spenders, inner)])
    return [int.from_bytes(k, "big") for k in keys]


def pair_tokens(pair: int) -> tuple:
    """Token indexes (0-based rank) of pair ``pair`` (0-based): the hub,
    rank 1, against rank pair + 2."""
    return 0, pair + 1


def make_state(sizes: Dict, seed: int) -> Dict:
    """``state.make_state``'s dict with the tokens re-coded, ``pairs``
    (addresses), ``reserves`` (per pair, the two seeded reserves) and
    the genesis additions in ``alloc``."""
    from khipu_tpu.domain.blockchain import GenesisAccount

    data = gen_state.make_state(sizes, seed)
    alloc, tokens, senders = data["alloc"], data["tokens"], data["senders"]
    n_pairs, n = int(sizes["pairs"]), len(senders)
    if n_pairs >= len(tokens):
        raise ValueError("a pair needs a token of its own beside the hub")
    rng = np.random.default_rng([seed, 0x70616972])
    raw = rng.integers(0, 256, (n_pairs, 20), dtype=np.uint8)
    # distinct from accounts and tokens: the pair's number, and low
    # bytes that neither make_alloc nor the tokens reach
    raw[:, 14:16] = np.arange(1, n_pairs + 1, dtype=">u2").view(
        np.uint8).reshape(n_pairs, 2)
    raw[:, 16:] = 0xFE
    pairs = [r.tobytes() for r in raw]
    reserves = rng.integers(RESERVE_LO, RESERVE_HI, (n_pairs, 2)).tolist()

    extra: List[Dict[int, int]] = [{} for _ in tokens]
    held = gen_state.balance_slots(senders)
    lent = allowance_slots([senders[(j + 1) % n] for j in range(n)], senders)
    for storage in extra:
        storage.update(dict.fromkeys(held, SENDER_TOKENS))
        storage.update(dict.fromkeys(lent, ALLOWANCE))
    at_pair = gen_state.balance_slots(pairs)
    for p, pair in enumerate(pairs):
        to_pair = allowance_slots(senders, [pair] * n)
        for side, t in enumerate(pair_tokens(p)):
            extra[t].update(dict.fromkeys(to_pair, ALLOWANCE))
            extra[t][at_pair[p]] = reserves[p][side]
        alloc[pair] = GenesisAccount(code=pair_code(p), storage={
            0: reserves[p][0], 1: reserves[p][1],
            2: int.from_bytes(tokens[0], "big"),
            3: int.from_bytes(tokens[p + 1], "big")})
    for rank, (token, more) in enumerate(zip(tokens, extra), 1):
        alloc[token] = GenesisAccount(
            code=token_code(rank), storage={**alloc[token].storage, **more})
    data.update(pairs=pairs, reserves=reserves)
    return data
