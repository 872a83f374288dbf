"""The execute lanes, counted and timed: a code hash whose learnt
``transfer`` template meets its first ``approve`` costs the segment of
the plan that held the call (rolled back to the last residue barrier
and re-run serially), not the block, and goes opaque for good; the root
stays right, and ``khipu_exec_lane_txs_total`` sums to the transactions
executed whichever lanes ran them (ledger/ledger.py, schedule.py).
Every block here is built by the sequential executor and executed with
``validate=True``: gas, receipts root (status, logs), bloom and state
root are held to what the serial fold produced."""

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


def build(tokens, plans):
    """A genesis with ``tokens`` (address -> code), every sender funded
    and holding 1 << 96 of each, and the blocks of ``plans`` as the
    SEQUENTIAL executor builds them. A plan is one block's transactions
    as (sender index, to, what): ``what`` an int is a plain transfer of
    that value (0: a zero-value transfer, which the planner routes to
    the residue: a barrier), else a contract call's payload."""
    from khipu_tpu.base.crypto.secp256k1 import (
        privkey_to_pubkey,
        pubkey_to_address,
    )

    senders = [pubkey_to_address(privkey_to_pubkey(k)) for k in KEYS]
    slot = lambda a: int.from_bytes(keccak256(pad(a) + bytes(32)), "big")  # noqa
    alloc = {a: 10**24 for a in senders}
    alloc.update({a: 1 for a in PLAIN})
    for token, code in tokens.items():
        alloc[token] = GenesisAccount(
            code=code, storage={slot(a): 1 << 96 for a in senders})
    spec = GenesisSpec(alloc=alloc, gas_limit=30_000_000)
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


def execute(spec, blocks, cfg=None):
    """The blocks through ``execute_block`` on a fresh node with a fresh
    learner (the defaults: scheduled, 8 workers), each held to its
    header; per block its Stats, and the chain."""
    schedule.reset_learner()
    cfg = cfg or fixture_config(chain_id=1)
    chain = Blockchain(Storages(), cfg)
    parent = chain.load_genesis(spec).header
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
    return stats, chain


def transfer(to, v):
    return call(C.SEL_TRANSFER, pad(to), v)


def approve(spender, v):
    return call(C.SEL_APPROVE, pad(spender), v)


def lanes(**kw):
    return {**dict.fromkeys(ledger.EXEC_LANES, 0), **kw}


@pytest.fixture()
def chain_of_four():
    """Four blocks over one token: transfers alone (observed in the
    residue), transfers again (checked against the template), the first
    approve among transfers (escapes the template), then the mix again
    (opaque: residue, with plain transfers between the barriers)."""
    return build({TOKEN: C.token_code(1)}, [
        [(0, TOKEN, transfer(PLAIN[0], 5)), (1, TOKEN, transfer(PLAIN[1], 6)),
         (2, PLAIN[2], 9)],
        [(0, TOKEN, transfer(PLAIN[1], 7)), (1, TOKEN, transfer(PLAIN[0], 8)),
         (2, PLAIN[2], 9), (3, PLAIN[3], 9)],
        [(0, TOKEN, transfer(PLAIN[0], 1)), (1, PLAIN[2], 3),
         (2, TOKEN, approve(PLAIN[3], 77)), (3, TOKEN, transfer(PLAIN[1], 2)),
         (4, PLAIN[3], 4)],
        [(0, PLAIN[2], 1), (1, PLAIN[3], 2), (2, TOKEN, transfer(PLAIN[0], 3)),
         (3, PLAIN[2], 4), (4, PLAIN[3], 5), (5, TOKEN, approve(PLAIN[0], 6))],
    ])


def lane_txs():
    return {lane: c.value for lane, c in ledger.LANE_TXS.items()}


def test_a_templates_first_approve_costs_one_segment_and_the_lanes_add_up(
        chain_of_four):
    spec, blocks = chain_of_four
    code_hash = keccak256(C.token_code(1))
    before = lane_txs()
    seconds = {lane: c.value for lane, c in ledger.LANE_SECONDS.items()}
    fallbacks = schedule.EXEC_GAUGES["fallbacks"]
    reruns = schedule.EXEC_GAUGES["segment_reruns"]
    logs = RECEIPT_LOGS.value
    stats, chain = execute(spec, blocks)
    observed, checked, escaped, opaque = stats

    # block 1: both calls unknown at plan time: residue, one observed
    assert observed.lane_txs == lanes(residue=2, vector=1)
    assert not observed.fallback and observed.batches == 1
    # block 2: a template, never trusted (the dispatcher's JUMPI fails
    # the purity scan): checked lane, in one batch with the transfers
    assert checked.lane_txs["checked"] == 2 and \
        checked.lane_txs["vector"] == 2
    # block 3: the approve escapes the transfer's footprint: its segment
    # (the block: no barrier) goes back to the checkpoint and runs in
    # index order, booked under residue; nothing falls back
    assert not escaped.fallback and escaped.mispredicted_txs == 1
    assert escaped.reruns == 1 and escaped.rerun_txs == 5
    assert escaped.lane_txs == lanes(residue=5)
    assert escaped.batches == 0
    assert escaped.lane_seconds["optimistic"] == 0
    assert escaped.lane_seconds["residue"] > 0
    assert escaped.lane_seconds["checked"] > 0  # the attempt's time stays
    assert schedule.EXEC_GAUGES["fallbacks"] == fallbacks
    assert schedule.EXEC_GAUGES["segment_reruns"] == reruns + 1
    assert schedule.LEARNER.lookup(code_hash) == "opaque"
    # block 4: opaque for good: two residue barriers, the plain
    # transfers between them vectorised in slivers of two
    assert not opaque.fallback and opaque.reruns == 0
    assert opaque.lane_txs["residue"] == 2 and opaque.lane_txs["vector"] == 4
    assert opaque.batches == 2
    for st in stats:
        assert sum(st.lane_txs.values()) == st.tx_count
    gained = {k: v - before[k] for k, v in lane_txs().items()}
    assert gained == {"vector": 7, "checked": 2, "residue": 9,
                      "optimistic": 0, "sequential": 0}
    assert sum(gained.values()) == sum(
        len(b.body.transactions) for b in blocks)
    for lane, c in ledger.LANE_SECONDS.items():
        assert (c.value > seconds[lane]) == (
            lane not in ("sequential", "optimistic"))
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


# ------------------------------------- segment-local recovery (PR 35)

TOKEN2 = b"\x71" * 20
TWO = {TOKEN: C.token_code(1), TOKEN2: C.token_code(2)}
LEARN_BOTH = [(0, TOKEN, transfer(PLAIN[0], 5)),
              (1, TOKEN2, transfer(PLAIN[1], 6)), (2, PLAIN[2], 9)]


def gauges():
    return dict(schedule.EXEC_GAUGES.items())


def rose(before):
    return {k: v - before[k] for k, v in gauges().items() if v != before[k]}


@pytest.mark.parametrize("barrier", [False, True],
                         ids=["one-segment", "two-segments"])
def test_two_tokens_meet_their_first_approve_in_one_block(barrier):
    """The parent un-learnt one code hash a block, whatever arrived. Both
    go opaque in the block that meets them: in two segments each escape
    re-runs its own; in one segment the first escape re-runs it and the
    serial re-run still holds the second call to its prediction."""
    between = [(3, PLAIN[3], 0)] if barrier else []
    spec, blocks = build(TWO, [LEARN_BOTH, [
        (0, TOKEN, approve(PLAIN[3], 77)), (1, PLAIN[2], 3), *between,
        (2, TOKEN2, approve(PLAIN[2], 78)), (5, PLAIN[3], 4)]])
    before = gauges()
    (_, escaped), _ = execute(spec, blocks)
    n = len(blocks[1].body.transactions)
    assert not escaped.fallback and escaped.mispredicted_txs == 2
    assert escaped.lane_txs == lanes(residue=n)
    assert (escaped.reruns, escaped.rerun_txs) == (
        (2, n - 1) if barrier else (1, n))
    for code in TWO.values():
        assert schedule.LEARNER.lookup(keccak256(code)) == "opaque"
    gained = rose(before)
    assert "fallbacks" not in gained
    assert gained["mispredictions"] == 2 and gained["opaque_codes"] == 2
    assert gained["segment_reruns"] == escaped.reruns
    assert gained["rerun_txs"] == escaped.rerun_txs


def test_an_escape_after_a_barrier_leaves_what_came_before_it_alone():
    """The checked call and the plain transfer before the barrier run
    once, in their own lanes; only the segment after it runs again."""
    spec, blocks = build({TOKEN: C.token_code(1)}, [LEARN_BOTH[::2], [
        (0, TOKEN, transfer(PLAIN[0], 1)), (1, PLAIN[2], 3),
        (2, PLAIN[3], 0),  # a zero-value transfer: residue, the barrier
        (3, TOKEN, approve(PLAIN[3], 77)), (4, PLAIN[3], 4)]])
    before, txs_before = gauges(), lane_txs()
    (_, escaped), _ = execute(spec, blocks)
    assert escaped.lane_txs == lanes(checked=1, vector=1, residue=3)
    assert (escaped.reruns, escaped.rerun_txs) == (1, 2)
    assert escaped.batches == 1 and not escaped.fallback
    gained = rose(before)
    # interpreter runs of block 2: the transfer once (checked, stood),
    # the approve twice (the attempt that escaped, then serially)
    assert gained["checked_call_txs"] == 1
    assert gained["mispredictions"] == 1 and "fallbacks" not in gained
    assert {k: v - txs_before[k] for k, v in lane_txs().items()} == lanes(
        checked=1, vector=2, residue=4)  # block 1: residue 1, vector 1


def test_a_later_segment_that_calls_the_demoted_hash_is_not_attempted():
    spec, blocks = build({TOKEN: C.token_code(1)}, [LEARN_BOTH[::2], [
        (0, TOKEN, approve(PLAIN[3], 77)), (1, PLAIN[2], 3),
        (2, PLAIN[3], 0),  # the barrier
        (3, TOKEN, approve(PLAIN[2], 78)), (4, PLAIN[3], 4),
        (5, TOKEN, transfer(PLAIN[0], 9))]])
    before = gauges()
    (_, escaped), _ = execute(spec, blocks)
    assert escaped.mispredicted_txs == 1  # no second escape
    assert (escaped.reruns, escaped.rerun_txs) == (1, 2)
    assert escaped.lane_txs == lanes(residue=6) and escaped.batches == 0
    gained = rose(before)
    assert gained["mispredictions"] == 1 and gained["opaque_codes"] == 1
    # the second segment's calls never ran checked: nothing stood in
    # that lane and nothing was attempted there but the one escape
    assert "checked_call_txs" not in gained and "fallbacks" not in gained


def test_an_invalid_transaction_in_the_attempt_still_falls_back_whole(
        chain_of_four, monkeypatch):
    """TxValidationError names no segment to roll back to: the block
    goes to ``_execute_optimistic``, the oracle, as before."""
    spec, blocks = chain_of_four
    real, raised = ledger._validate_stx, []

    def once(stx, sender, config, world, accumulated, limit, index):
        if index == 1 and len(blocks[1].body.transactions) == 4 \
                and stx is blocks[1].body.transactions[1] and not raised:
            raised.append(index)
            raise ledger.TxValidationError(index, "injected")
        return real(stx, sender, config, world, accumulated, limit, index)

    monkeypatch.setattr(ledger, "_validate_stx", once)
    before = gauges()
    (_, fell, *_), _ = execute(spec, blocks)
    assert raised == [1]
    assert fell.fallback and fell.reruns == 0 and fell.mispredicted_txs == 0
    assert fell.lane_txs == lanes(optimistic=4) and fell.batches == 0
    assert rose(before)["fallbacks"] == 1


def test_the_execute_span_says_what_was_re_run(chain_of_four):
    """``reruns`` / ``rerun_txs`` on the ``execute`` span, ``fallback`` 0
    on a block that re-ran a segment, and the two gauges beside
    ``mispredictions``: what ``exec_rerun_txs_share.sync`` will read."""
    from khipu_tpu.observability.trace import tracer
    from khipu_tpu.sync.replay import ReplayDriver

    spec, blocks = chain_of_four
    schedule.reset_learner()
    cfg = dataclasses.replace(fixture_config(chain_id=1), sync=SyncConfig(
        commit_window_blocks=2, pipeline_depth=2))
    chain = Blockchain(Storages(), cfg)
    chain.load_genesis(spec)
    before = gauges()
    tracer.enable()
    tracer.reset()
    try:
        stats = ReplayDriver(chain, cfg, device_commit=False).replay(blocks)
        spans = [s for s in tracer.snapshot() if s.name == "execute"]
    finally:
        tracer.disable()
        tracer.reset()
    assert chain.get_header_by_number(4).hash == blocks[-1].hash
    assert stats.mispredictions == 1
    tags = {s.tags["block"]: s.tags for s in spans}
    assert sorted(tags) == [1, 2, 3, 4]
    assert [(tags[n]["reruns"], tags[n]["rerun_txs"], tags[n]["fallback"])
            for n in (1, 2, 3, 4)] == [(0, 0, 0), (0, 0, 0), (1, 5, 0),
                                       (0, 0, 0)]
    assert tags[3]["residue"] == 5 and tags[3]["optimistic"] == 0
    for t in tags.values():
        assert sum(t[lane] for lane in ledger.EXEC_LANES) == t["txs"]
    gained = rose(before)
    assert gained["segment_reruns"] == 1 and gained["rerun_txs"] == 5
    assert gained["mispredictions"] == 1 and "fallbacks" not in gained
    from khipu_tpu.observability.registry import REGISTRY

    snap = REGISTRY.snapshot()
    assert snap["khipu_exec_batch_segment_reruns"] >= 1
    assert snap["khipu_exec_batch_rerun_txs"] >= 5


def test_the_backlog_of_templates_drains_without_a_fallback():
    """PR 34's count of the mechanism, at its small state: 256 tokens, 200
    senders, ``sync.contracts``' mix, 100 blocks. On the parent 96 of them
    fell back whole, each to un-learn one code hash (96 demoted); now no
    block does, every escape un-learns its hash in the block that meets
    it, and what is re-run is a few transactions in a hundred."""
    import json

    from benchmark.generators import chain_contracts as gen_chain

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark/traffic/contracts.json")) as f:
        params = json.load(f)["params"]
    sizes = {"accounts": 2048, "funded_senders": 200, "txs_per_block": 200,
             "token_contracts": 256, "token_slots": 2048, "pairs": 32}
    n_blocks, seed = 100, 77
    state = C.make_state(sizes, seed)
    picks = gen_chain.draw(params, n_blocks, 200, len(state["others"]),
                           state["holders"], len(state["pairs"]), seed)
    spec = GenesisSpec(alloc=state["alloc"], gas_limit=30_000_000)
    seq = dataclasses.replace(fixture_config(chain_id=1),
                              sync=SyncConfig(parallel_tx=False))
    builder = ChainBuilder(Blockchain(Storages(), seq), seq, spec)
    nonces = [0] * len(state["keys"])
    blocks = [builder.add_block(
        [sign_transaction(tx, state["keys"][s], chain_id=1)
         for s, tx in gen_chain.transactions(state, picks, n, nonces)],
        coinbase=gen_chain.COINBASE) for n in range(n_blocks)]
    before = gauges()
    stats, _ = execute(spec, blocks)  # each block held to its header
    assert sum(st.fallback for st in stats) <= 2
    for st in stats:
        assert sum(st.lane_txs.values()) == st.tx_count == 200
    gained = rose(before)
    assert gained.get("fallbacks", 0) <= 2
    assert gained["mispredictions"] >= 96
    opaque = sum(schedule.LEARNER.lookup(keccak256(C.token_code(rank)))
                 == "opaque" for rank in range(1, 257))
    assert opaque >= 96
    rerun = sum(st.rerun_txs for st in stats)
    assert rerun == gained["rerun_txs"]
    assert 0 < rerun < 0.05 * 200 * n_blocks
    # behind the backlog the window's blocks re-run next to nothing
    assert sum(st.rerun_txs for st in stats[45:]) < 0.03 * 200 * 55
