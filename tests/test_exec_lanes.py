"""The execute lanes, counted and timed: a code hash whose learnt
``transfer`` template meets its first ``approve`` costs one whole-block
fallback and goes opaque for good, the root stays right, and
``khipu_exec_lane_txs_total`` sums to the transactions executed
whichever lanes ran them (ledger/ledger.py, schedule.py)."""

import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.generators import contracts as C  # noqa: E402
from khipu_tpu.base.crypto.keccak import keccak256  # noqa: E402
from khipu_tpu.config import SyncConfig, fixture_config  # noqa: E402
from khipu_tpu.domain.blockchain import (  # noqa: E402
    RECEIPT_LOGS,
    Blockchain,
    GenesisAccount,
    GenesisSpec,
)
from khipu_tpu.domain.transaction import (  # noqa: E402
    Transaction,
    sign_transaction,
)
from khipu_tpu.ledger import ledger, schedule  # noqa: E402
from khipu_tpu.storage.storages import Storages  # noqa: E402
from khipu_tpu.sync.chain_builder import ChainBuilder  # noqa: E402

KEYS = [bytes([0] * 31 + [i + 1]) for i in range(6)]
TOKEN = b"\x70" * 20
PLAIN = [bytes([0x50 + i]) * 20 for i in range(4)]
COINBASE = b"\xaa" * 20


def pad(a):
    return a.rjust(32, b"\x00")


def call(selector, *words):
    return selector.to_bytes(4, "big") + b"".join(
        w if isinstance(w, bytes) else w.to_bytes(32, "big") for w in words)


@pytest.fixture()
def chain_of_four():
    """Four blocks over one token: transfers alone (observed in the
    residue), transfers again (checked against the template), the first
    approve among transfers (escapes the template), then the mix again
    (opaque: residue, with plain transfers between the barriers)."""
    from khipu_tpu.base.crypto.secp256k1 import (
        privkey_to_pubkey,
        pubkey_to_address,
    )

    senders = [pubkey_to_address(privkey_to_pubkey(k)) for k in KEYS]
    slot = lambda a: int.from_bytes(keccak256(pad(a) + bytes(32)), "big")  # noqa
    alloc = {a: 10**24 for a in senders}
    alloc.update({a: 1 for a in PLAIN})
    alloc[TOKEN] = GenesisAccount(
        code=C.token_code(1), storage={slot(a): 1 << 96 for a in senders})
    spec = GenesisSpec(alloc=alloc, gas_limit=30_000_000)
    transfer = lambda to, v: call(C.SEL_TRANSFER, pad(to), v)  # noqa: E731
    approve = lambda s, v: call(C.SEL_APPROVE, pad(s), v)  # noqa: E731
    plans = [
        [(0, TOKEN, transfer(PLAIN[0], 5)), (1, TOKEN, transfer(PLAIN[1], 6)),
         (2, PLAIN[2], 9)],
        [(0, TOKEN, transfer(PLAIN[1], 7)), (1, TOKEN, transfer(PLAIN[0], 8)),
         (2, PLAIN[2], 9), (3, PLAIN[3], 9)],
        [(0, TOKEN, transfer(PLAIN[0], 1)), (1, PLAIN[2], 3),
         (2, TOKEN, approve(PLAIN[3], 77)), (3, TOKEN, transfer(PLAIN[1], 2)),
         (4, PLAIN[3], 4)],
        [(0, PLAIN[2], 1), (1, PLAIN[3], 2), (2, TOKEN, transfer(PLAIN[0], 3)),
         (3, PLAIN[2], 4), (4, PLAIN[3], 5), (5, TOKEN, approve(PLAIN[0], 6))],
    ]
    cfg = dataclasses.replace(fixture_config(chain_id=1),
                              sync=SyncConfig(parallel_tx=False))
    builder = ChainBuilder(Blockchain(Storages(), cfg), cfg, spec)
    nonces = [0] * len(KEYS)
    blocks = []
    for plan in plans:
        txs = []
        for s, to, what in plan:
            tx = (Transaction(nonces[s], 10**9, 21_000, to, what)
                  if isinstance(what, int) else
                  Transaction(nonces[s], 10**9, 150_000, to, 0, payload=what))
            txs.append(sign_transaction(tx, KEYS[s], chain_id=1))
            nonces[s] += 1
        blocks.append(builder.add_block(txs, coinbase=COINBASE))
    return spec, blocks


def lane_txs():
    return {lane: c.value for lane, c in ledger.LANE_TXS.items()}


def test_a_templates_first_approve_costs_one_fallback_and_the_lanes_add_up(
        chain_of_four):
    spec, blocks = chain_of_four
    schedule.reset_learner()
    cfg = fixture_config(chain_id=1)  # the defaults: scheduled, 8 workers
    chain = Blockchain(Storages(), cfg)
    parent = chain.load_genesis(spec).header
    code_hash = keccak256(C.token_code(1))
    before = lane_txs()
    seconds = {lane: c.value for lane, c in ledger.LANE_SECONDS.items()}
    fallbacks = schedule.EXEC_GAUGES["fallbacks"]
    logs = RECEIPT_LOGS.value
    stats = []
    for block in blocks:
        # validate=True: gas, receipts root, bloom and state root are all
        # held to the header the sequential executor produced
        result = ledger.execute_block(
            block, parent.state_root, chain.get_world_state, cfg)
        chain.save_block(block, result.receipts, block.header.difficulty,
                         result.world)
        stats.append(result.stats)
        parent = block.header
    observed, checked, escaped, opaque = stats

    # block 1: both calls unknown at plan time: residue, one observed
    assert observed.lane_txs == {**dict.fromkeys(ledger.EXEC_LANES, 0),
                                 "residue": 2, "vector": 1}
    assert not observed.fallback and observed.batches == 1
    # block 2: a template, never trusted (the dispatcher's JUMPI fails
    # the purity scan): checked lane, in one batch with the transfers
    assert checked.lane_txs["checked"] == 2 and \
        checked.lane_txs["vector"] == 2
    # block 3: the approve escapes the transfer's footprint
    assert escaped.fallback and escaped.mispredicted_txs == 1
    assert escaped.lane_txs == {**dict.fromkeys(ledger.EXEC_LANES, 0),
                                "optimistic": 5}
    assert escaped.batches == 0
    assert escaped.lane_seconds["optimistic"] > 0
    assert escaped.lane_seconds["checked"] > 0  # the attempt's time stays
    assert schedule.EXEC_GAUGES["fallbacks"] == fallbacks + 1
    assert schedule.LEARNER.lookup(code_hash) == "opaque"
    # block 4: opaque for good: two residue barriers, the plain
    # transfers between them vectorised in slivers of two
    assert not opaque.fallback
    assert opaque.lane_txs["residue"] == 2 and opaque.lane_txs["vector"] == 4
    assert opaque.batches == 2
    for st in stats:
        assert sum(st.lane_txs.values()) == st.tx_count
    gained = {k: v - before[k] for k, v in lane_txs().items()}
    assert gained == {"vector": 7, "checked": 2, "residue": 4,
                      "optimistic": 5, "sequential": 0}
    assert sum(gained.values()) == sum(
        len(b.body.transactions) for b in blocks)
    for lane, c in ledger.LANE_SECONDS.items():
        assert (c.value > seconds[lane]) == (lane != "sequential")
    # seven transfers and two approves logged one event each
    assert RECEIPT_LOGS.value - logs == 9
    assert sum(len(r.logs) for b in blocks
               for r in chain.get_receipts(b.header.number)) == 9


def test_sequential_blocks_book_their_own_lane(chain_of_four):
    spec, blocks = chain_of_four
    cfg = dataclasses.replace(fixture_config(chain_id=1),
                              sync=SyncConfig(parallel_tx=False))
    chain = Blockchain(Storages(), cfg)
    parent = chain.load_genesis(spec).header
    before = lane_txs()
    result = ledger.execute_block(
        blocks[0], parent.state_root, chain.get_world_state, cfg)
    assert result.stats.lane_txs["sequential"] == 3
    assert {k: v - before[k] for k, v in lane_txs().items()} == {
        **dict.fromkeys(ledger.EXEC_LANES, 0), "sequential": 3}
    from khipu_tpu.observability.registry import REGISTRY

    snap = REGISTRY.snapshot()
    for family in ("khipu_exec_lane_txs_total",
                   "khipu_exec_lane_seconds_total"):
        assert set(snap[family]) == {
            f'lane="{lane}"' for lane in ledger.EXEC_LANES}
    assert "khipu_receipt_logs_total" in snap
