"""The cell ``sync.contracts`` on the CPU: the token and pair bytecode
against the plain reference under all three executors, the generators,
the manifest, the new readers, and ``run.py --rehearse`` end to end at
the configuration's rehearsal sizes (2,400 accounts, 8 tokens, 4 pairs,
50 transactions a block). Says nothing of the chip."""

import dataclasses
import io
import json
import os
import sys
from contextlib import redirect_stdout
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import run as bench_run  # noqa: E402
from benchmark.generators import asm  # noqa: E402
from benchmark.generators import chain_contracts as gen_chain  # noqa: E402
from benchmark.generators import contracts as gen_contracts  # noqa: E402
from benchmark.lib import manifest  # noqa: E402
from benchmark.reference import ledger_contracts as ref  # noqa: E402
from benchmark.reference.keccak import keccak256_batch  # noqa: E402

SEED = 2_147_483_789
NEW = {"exec_residue_share.sync", "exec_fallback_blocks_share.sync",
       "exec_batch_width.sync", "exec_interpreter_ms_per_block.sync",
       "exec_vector_ms_per_block.sync", "exec_rerun_txs_share.sync"}
CELL = manifest.cell("sync.contracts")
CONF, TRAFFIC = CELL["config_file"], CELL["traffic_file"]
SIZES = bench_run.merged(CONF, True)["sizes"]
PARAMS = bench_run.merged(TRAFFIC, True)["params"]
MIX = {"plain": 0.40, "transfer": 0.30, "approve": 0.06,
       "transferFrom": 0.06, "swap": 0.16, "revert": 0.02}


@pytest.fixture(scope="module")
def small_state():
    return gen_contracts.make_state(SIZES, SEED)


def draw(state, blocks, seed=SEED, params=PARAMS, txs=None):
    return gen_chain.draw(params, blocks, txs or SIZES["txs_per_block"],
                          len(state["others"]), state["holders"],
                          len(state["pairs"]), seed)


# ------------------------------------------- bytecode against reference

EXECUTORS = {"sequential": {"parallel_tx": False},
             "scheduled": {},
             "optimistic": {"scheduled_tx": False}}


def build_under(executor, state, picks):
    """The picks' blocks through ``ChainBuilder`` with ``execute_block``
    held to one executor; per block (root, gas used, bloom, receipts)
    and the chain to read state from."""
    from khipu_tpu.config import SyncConfig, fixture_config
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.domain.transaction import sign_transaction
    from khipu_tpu.ledger.schedule import reset_learner
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.chain_builder import ChainBuilder

    reset_learner()
    cfg = dataclasses.replace(fixture_config(chain_id=1),
                              sync=SyncConfig(**EXECUTORS[executor]))
    chain = Blockchain(Storages(), cfg)
    builder = ChainBuilder(chain, cfg, GenesisSpec(
        alloc=state["alloc"], gas_limit=30_000_000))
    nonces = [0] * len(state["keys"])
    out = []
    for n in range(len(picks["kind"])):
        block = builder.add_block(
            [sign_transaction(tx, state["keys"][s], chain_id=1)
             for s, tx in gen_chain.transactions(state, picks, n, nonces)],
            coinbase=gen_chain.COINBASE)
        out.append((block.header.state_root, block.header.gas_used,
                    block.header.logs_bloom,
                    [(r.post_tx_state, r.cumulative_gas_used, r.logs_bloom,
                      [(l.address, tuple(l.topics), l.data) for l in r.logs])
                     for r in chain.get_receipts(block.header.number)]))
    return out, chain


def hard_picks(state):
    """Seeded blocks of the mix, then one block by hand: a run of swaps
    on one pair in both directions, a swap of nothing, a transferFrom
    beyond its allowance, an approve that lowers an allowance to 5 and
    the transferFrom of 6 that then fails, a transfer to oneself's
    spender and a transfer of more than anyone holds."""
    picks = {k: v.astype(object) for k, v in draw(state, 6).items()}
    K = gen_chain.KINDS
    n = len(state["senders"])
    hand = [  # kind, sender, receiver, amount, token, pair, flag
        (K["swap"], 0, 0, 5000, -1, 1, 1), (K["swap"], 1, 0, 7000, -1, 1, 1),
        (K["swap"], 2, 0, 9000, -1, 1, 0), (K["swap"], 3, 0, 1 << 19, -1, 1, 1),
        (K["swap"], 4, 0, 0, -1, 1, 1),
        (K["transferFrom"], 5, 7, (1 << 128) + 1, 2, -1, 0),
        (K["transferFrom"], 6, 7, 1 << 40, 2, -1, 0),
        (K["approve"], 8, 9, 5, 3, -1, 0),
        (K["transfer"], 9, 11, 1234, 3, -1, 0),
        (K["revert"], 10, 11, 1, 0, -1, 0),
        (K["plain"], 11, 12, 77, -1, -1, 0),
        (K["swap"], 12, 0, 4096, -1, 0, 0), (K["swap"], 13, 0, 4096, -1, 0, 1),
    ]
    assert n > 13
    row = {k: [] for k in picks}
    for kind, s, r, amt, tok, pair, flag in hand:
        for k, v in zip(("kind", "sender", "receiver", "amount", "token",
                         "pair", "flag"), (kind, s, r, amt, tok, pair, flag)):
            row[k].append(v)
    width = picks["kind"].shape[1]
    for k in picks:  # pad the hand-made block with plain transfers
        pad = {"kind": K["plain"], "sender": None, "receiver": 3,
               "amount": 1, "token": -1, "pair": -1, "flag": 0}[k]
        free = [7] + list(range(14, n))  # senders the hand left unused
        fill = [pad if pad is not None else free[i]
                for i in range(width - len(hand))]
        picks[k] = np.concatenate(
            [picks[k], np.array([row[k] + fill], dtype=object)])
    return picks


@pytest.fixture(scope="module")
def three_ways(small_state):
    picks = hard_picks(small_state)
    return picks, {name: build_under(name, small_state, picks)
                   for name in EXECUTORS}


def test_the_three_executors_agree_on_roots_statuses_logs_blooms_and_gas(
        three_ways):
    _, built = three_ways
    seq, sched, opt = (built[k][0] for k in EXECUTORS)
    assert seq == sched == opt
    assert len({root for root, *_ in seq}) == len(seq)


def test_the_bytecode_does_what_the_reference_says(small_state, three_ways):
    picks, built = three_ways
    blocks, chain = built["scheduled"]
    head = len(blocks)
    led, receipts = ref.fold(small_state, picks, head)
    kinds = {}
    for (b, j), (kind, status, logs) in receipts.items():
        got_status, _, got_bloom, got_logs = blocks[b][3][j]
        assert (got_status, got_logs) == (status, logs), (b, j, kind)
        assert got_bloom == ref.bloom(logs)
        kinds.setdefault((ref.KIND_NAMES[kind], status), []).append((b, j))
    # every kind of the mix ran, and the hand-made block's failures failed
    assert {k for k, _ in kinds} == set(MIX)
    assert len(kinds["swap", 0]) == 1 and len(kinds["transferFrom", 0]) == 1
    assert not kinds.get(("revert", 1)) and len(kinds["revert", 0]) >= 6
    last = head - 1
    assert [receipts[last, j][1] for j in range(13)] == [
        1, 1, 1, 1, 0, 0, 1, 1, 1, 0, 1, 1, 1]
    # the same-pair run: each swap priced on the reserves the one
    # before it left (three in one direction, one in the other)
    r0, r1 = ref.fold(small_state, picks, last)[0].reserves[1]
    for j, (amount, zero_for_one) in zip(
            (0, 1, 2, 3), ((5000, 1), (7000, 1), (9000, 0), (1 << 19, 1))):
        r_in, r_out = (r0, r1) if zero_for_one else (r1, r0)
        out = amount * 997 * r_out // (r_in * 1000 + amount * 997)
        assert receipts[last, j][2][-1][2] == b"".join(
            v.to_bytes(32, "big") for v in (amount, out, zero_for_one))
        r_in, r_out = r_in + amount, r_out - out
        r0, r1 = (r_in, r_out) if zero_for_one else (r_out, r_in)
    assert led.reserves[1] == [r0, r1] != small_state["reserves"][1]
    # state: every balance, allowance and reserve the reference touched
    world = chain.get_world_state(blocks[-1][0])
    tokens, pairs = small_state["tokens"], small_state["pairs"]
    read = 0
    for c, book in enumerate(led.balances):
        for a, slot in zip(book, ref.balance_slots(list(book))):
            assert world.get_storage(tokens[c], slot) == book[a]
            read += 1
    for c, book in enumerate(led.allowances):
        for (o, s), slot in zip(book, ref.allowance_slots(list(book))):
            assert world.get_storage(tokens[c], slot) == book[o, s]
            read += 1
    for p, (r0, r1) in enumerate(led.reserves):
        assert (world.get_storage(pairs[p], 0),
                world.get_storage(pairs[p], 1)) == (r0, r1)
    assert read > 300
    assert led.allowances[3][small_state["senders"][8],
                             small_state["others"][9]] == 5
    for i, gained in list(led.plain_gained.items())[:20]:
        assert world.get_balance(small_state["others"][i]) == \
            10**18 + int(small_state["extra"][i]) + gained


def test_the_reference_imports_nothing_of_the_program():
    for name in ("ledger_contracts", "keccak"):
        text = open(os.path.join(
            REPO, "benchmark", "reference", name + ".py")).read()
        assert "khipu_tpu" not in text.split('"""', 2)[2]
        assert "generators" not in text.split('"""', 2)[2]


def test_selectors_and_topics_are_the_signatures_keccaks():
    k = lambda s: keccak256_batch([s])[0]  # noqa: E731
    C = gen_contracts
    for sel, sig in ((C.SEL_TRANSFER, b"transfer(address,uint256)"),
                     (C.SEL_APPROVE, b"approve(address,uint256)"),
                     (C.SEL_TRANSFER_FROM,
                      b"transferFrom(address,address,uint256)"),
                     (C.SEL_BALANCE_OF, b"balanceOf(address)"),
                     (C.SEL_SWAP, b"swap(uint256,bool)")):
        assert sel.to_bytes(4, "big") == k(sig)[:4]
    assert C.TOPIC_TRANSFER.to_bytes(32, "big") == ref.TOPIC_TRANSFER == k(
        b"Transfer(address,address,uint256)")
    assert C.TOPIC_APPROVAL.to_bytes(32, "big") == ref.TOPIC_APPROVAL
    assert C.TOPIC_SWAP.to_bytes(32, "big") == ref.TOPIC_SWAP
    assert (ref.SENDER_TOKENS, ref.ALLOWANCE, ref.REVERT_AMOUNT) == (
        C.SENDER_TOKENS, C.ALLOWANCE, gen_chain.REVERT_AMOUNT)
    assert {v: k for k, v in gen_chain.KINDS.items()} == ref.KIND_NAMES


def test_the_listing_in_the_docs_is_the_code_the_node_runs():
    text = open(os.path.join(REPO, "docs", "deployments.md")).read()
    for title, code in (("token", gen_contracts.TOKEN_RUNTIME),
                        ("pair", gen_contracts.PAIR_RUNTIME)):
        block = text.split(f"<!-- listing: {title} -->")[1].split("```")[1]
        listed = [tuple(l.split(None, 1)) for l in block.strip().splitlines()]
        assert [(f"{pc:04x}", op) for pc, op in asm.listing(code)] == [
            (pc, op.split("  ;")[0].strip()) for pc, op in listed]
    assert asm.assemble(["@x", "STOP", ":x", 1, ("push", 2, 4)]) == bytes(
        [0x61, 0, 4, 0x00, 0x5B, 0x60, 1, 0x63, 0, 0, 0, 2])


# ------------------------------------------------------- the generators


def test_the_state_is_deeps_with_the_genesis_additions(small_state):
    from benchmark.generators import state as gen_state

    deep = gen_state.make_state(SIZES, SEED)
    s = small_state
    for k in ("senders", "others", "tokens"):
        assert s[k] == deep[k]
    n, t, p = len(s["senders"]), len(s["tokens"]), len(s["pairs"])
    assert (n, t, p) == (50, 8, 4)
    added = sum(len(s["alloc"][a].storage) - len(deep["alloc"][a].storage)
                for a in s["tokens"])
    assert added == n * t + n * t + 2 * p * n + 2 * p
    # at full size: 51,200 + 51,200 + 12,800 + 64, and 128 pair slots
    assert 200 * 256 * 2 + 2 * 32 * 200 + 2 * 32 + 4 * 32 == 115_392
    for rank, a in enumerate(s["tokens"], 1):
        assert s["alloc"][a].code == gen_contracts.token_code(rank)
        assert s["alloc"][a].code[:-2] == gen_contracts.TOKEN_RUNTIME
        assert deep["alloc"][a].storage.items() <= \
            s["alloc"][a].storage.items()
    codes = [gen_contracts.token_code(r) for r in range(1, 257)] + \
        [gen_contracts.pair_code(i) for i in range(32)]
    assert len(set(keccak256_batch(codes))) == 288
    led = ref.Ledger(s)
    for i, pair in enumerate(s["pairs"]):
        record = s["alloc"][pair]
        assert record.code == gen_contracts.pair_code(i)
        assert [record.storage[k] for k in (0, 1)] == s["reserves"][i]
        assert all(1 << 50 <= r < 1 << 60 for r in s["reserves"][i])
        assert (record.storage[2], record.storage[3]) == tuple(
            int.from_bytes(s["tokens"][c], "big") for c in (0, i + 1))
        for side, c in enumerate((0, i + 1)):
            (slot,) = ref.balance_slots([pair])
            assert s["alloc"][s["tokens"][c]].storage[slot] == \
                s["reserves"][i][side] == led.balance_of(c, pair)
    # the reference's lazy genesis against the alloc, slot for slot
    j = 7
    owner, spender = s["senders"][(j + 1) % n], s["senders"][j]
    slots = ref.allowance_slots([(owner, spender), (spender, owner),
                                 (s["senders"][j], s["pairs"][2])])
    for c in (0, 3, 5):
        storage = s["alloc"][s["tokens"][c]].storage
        assert storage[slots[0]] == led.allowance(c, owner, spender) \
            == 1 << 128
        assert slots[1] not in storage and \
            led.allowance(c, spender, owner) == 0
        assert storage.get(slots[2], 0) == led.allowance(
            c, s["senders"][j], s["pairs"][2]) == (
            1 << 128 if c in (0, 3) else 0)
        (slot,) = ref.balance_slots([spender])
        assert storage[slot] == led.balance_of(c, spender) == 1 << 96
        holder = int(s["holders"][c][0])
        assert led.balance_of(c, s["others"][holder]) == \
            int(s["holdings"][c][0])


def test_the_picks_are_the_mix_and_one_seed_gives_them_twice(small_state):
    assert TRAFFIC["params"]["mix"] == MIX == PARAMS["mix"]
    assert gen_chain.kind_counts(MIX, 200) == {
        "plain": 80, "transfer": 60, "approve": 12, "transferFrom": 12,
        "swap": 32, "revert": 4}
    assert sum(gen_chain.kind_counts(MIX, 50).values()) == 50
    holders = small_state["holders"]
    params = dict(TRAFFIC["params"])
    picks = draw(small_state, 300, params=params, txs=200)
    again = draw(small_state, 300, params=params, txs=200)
    other = draw(small_state, 300, seed=SEED + 1, params=params, txs=200)
    assert all(picks[k].tobytes() == again[k].tobytes() for k in picks)
    assert picks["kind"].tobytes() != other["kind"].tobytes()
    for name, n in gen_chain.kind_counts(MIX, 200).items():
        assert ((picks["kind"] == gen_chain.KINDS[name]).sum(axis=1)
                == n).all()
    assert (np.sort(picks["sender"], axis=1) == np.arange(200)).all()
    assert (picks["sender"][0] != picks["sender"][1]).any()  # shuffled
    first = picks["kind"][:, 0]  # kinds interleave: no fixed layout
    assert len(np.unique(first)) >= 4
    swap = picks["kind"] == gen_chain.KINDS["swap"]
    on_token = picks["token"] >= 0
    assert (picks["pair"][~swap] == -1).all() and not (swap & on_token).any()
    assert on_token.mean() == pytest.approx(0.44)
    harmonic = sum(1 / r for r in range(1, 9))
    share = np.bincount(picks["token"][on_token], minlength=8) / on_token.sum()
    assert share == pytest.approx(
        [1 / (r * harmonic) for r in range(1, 9)], abs=0.012)
    h4 = sum(1 / r for r in range(1, 5))
    assert np.bincount(picks["pair"][swap]) / swap.sum() == pytest.approx(
        [1 / (r * h4) for r in range(1, 5)], abs=0.015)
    amounts = picks["amount"][swap]
    assert amounts.min() >= 1 << 10 and amounts.max() < 1 << 20
    assert 0.45 < picks["flag"][swap].mean() < 0.55
    pays = np.isin(picks["kind"], [gen_chain.KINDS["transfer"],
                                   gen_chain.KINDS["transferFrom"]])
    held = np.array([r in set(holders[c].tolist()) for c, r in zip(
        picks["token"][pays][:4000], picks["receiver"][pays][:4000])])
    assert 0.78 < held.mean() < 0.92


# ---------------------------------------------------------- the manifest


def test_the_manifest_names_the_cell_its_files_and_its_metrics():
    deep = manifest.cell("sync.deep")["config_file"]
    sizes = dict(CONF["sizes"])
    assert sizes.pop("pairs") == 32 and sizes == deep["sizes"]
    assert CONF["program"] == deep["program"]
    assert CONF["guarantees"][:5] == deep["guarantees"][:5]
    assert len(CONF["guarantees"]) == 9
    assert CONF["driver"] == "sync_contracts" and CONF["architecture"] is None
    entry = CELL["config_entry"]
    assert entry["source"] == CONF["source"] and len(CONF["source"]) <= 200
    assert "README.md:8" in CONF["source"]
    assert len(CELL["why"]) <= 200 and len(entry["why"]) <= 200
    assert CELL["chips"] == 1 and CELL["traffic"] == "contracts"
    assert set(entry["reduced"]) == set(CONF["reduced"]) == {
        "accounts", "contracts", "storage_slots", "contract_kinds", "pair"}
    assert {"mix", "pair_zipf", "revert_share"} <= set(CONF["assumed"])
    assert "no upstream document" in CONF["assumed"]["mix"]
    for key in ("warmup_blocks", "warmup_max_blocks", "trace_seconds",
                "log_metrics_of"):
        assert TRAFFIC[key] == manifest.cell("sync.deep")["traffic_file"][key]
    assert TRAFFIC["chain_blocks"] % 15 == 0 and TRAFFIC["chain_blocks"] >= 345
    assert TRAFFIC["generator"] == "chain_contracts"
    assert TRAFFIC["params"]["senders_pool"] == 200
    bench = manifest.benchmark_json()
    assert [w["chips"] for w in bench["workloads"]] == [1] * len(
        bench["workloads"])
    reported = {m["name"] for m in manifest.metrics_for(
        "sync.contracts", "per_layer")}
    deeps = {m["name"] for m in manifest.metrics_for("sync.deep", "per_layer")}
    assert reported == deeps | NEW and not deeps & NEW
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["layer"] == "driver foreground (sync/replay.py)"
            assert m["moves"] == "sync_blocks_per_s"
            assert "sync.contracts" in m["workloads"]
            assert not {"sync.dense", "sync.deep"} & set(m["workloads"])
    (e2e,) = [m for m in bench["end_to_end"]
              if m["name"] == "sync_blocks_per_s"]
    assert e2e["workloads"][:2] == ["sync.dense", "sync.deep"]
    assert "sync.contracts" in e2e["workloads"]


# ----------------------------------------------------------- the readers


def test_span_tag_sum_and_a_program_that_lacks_the_tags():
    from benchmark.readers import span_tag_sum

    span = lambda **tags: SimpleNamespace(name="execute", tags=tags)  # noqa
    art = {"blocks": 2, "windows": 1, "spans": [
        span(txs=200, residue=50, vector=100, checked=10, batches=20,
             fallback=0, vector_s=0.02),
        span(txs=200, residue=0, vector=0, checked=0, batches=0,
             fallback=1, vector_s=0.01),
        SimpleNamespace(name="window.build", tags={})]}
    read = lambda name: span_tag_sum.read(  # noqa: E731
        art, **manifest.metric_file(name)["args"])
    assert read("exec_residue_share.sync") == 12.5
    assert read("exec_fallback_blocks_share.sync") == 50.0
    assert read("exec_batch_width.sync") == 5.5
    assert read("exec_vector_ms_per_block.sync") == pytest.approx(15.0)
    old = {"blocks": 2, "windows": 1, "replay_stats": [],
           "registry": ({"khipu_best_block_number": 1},) * 2,
           "spans": [span(txs=200, block=7)]}
    for name in NEW:  # every new metric's reader, on a parent's artefacts
        spec = manifest.metric_file(name)
        reader = manifest.load_module("readers", spec["reader"])
        assert reader.read(old, **spec["args"]) is None
        assert reader.read({"blocks": 2}, **spec["args"]) is None
    seconds = "khipu_exec_lane_seconds_total"
    art["registry"] = (
        {seconds: {'lane="checked"': 1.0, 'lane="residue"': 2.0,
                   'lane="optimistic"': 0.0, 'lane="vector"': 5.0}},
        {seconds: {'lane="checked"': 1.1, 'lane="residue"': 2.2,
                   'lane="optimistic"': 0.3, 'lane="vector"': 9.0}})
    spec = manifest.metric_file("exec_interpreter_ms_per_block.sync")
    assert manifest.load_module("readers", spec["reader"]).read(
        art, **spec["args"]) == pytest.approx(300.0)


# --------------------------------------------- rehearsals, end to end


@pytest.fixture(scope="module", autouse=True)
def own_run_dir(tmp_path_factory):
    """``run.py`` empties ``<BENCH_DIR>/cache/_run`` at the start of
    every run, and xdist runs the other files' rehearsals in other
    processes at the same time: these get a directory of their own (and
    share its seed cache among themselves)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(bench_run, "BENCH_DIR",
                      str(tmp_path_factory.mktemp("contracts")))
        yield


def rehearse(trace=0, control=None):
    argv = ["--workload", "sync.contracts", "--seed", str(SEED), "--seconds",
            "2", "--trace", str(trace), "--rehearse"]
    if control:
        argv += ["--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(argv)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), lines


@pytest.fixture(scope="module")
def traced_run():
    return rehearse(trace=1)


def test_contracts_cell_rehearsal_ends_in_the_contracts_line():
    rc, line, lines = rehearse()
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"sync_blocks_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"  # a rehearsal, and says so
    checks = [l for l in lines if l.startswith("check: ")]
    assert len(checks) >= 22 and all(l.endswith(" ok") for l in checks)


def test_every_check_of_the_configuration_is_counted_in_the_log(traced_run):
    _, line, lines = traced_run
    assert line["correct"] is True
    count = lambda prefix: int([  # noqa: E731
        l for l in lines if l.startswith("check: " + prefix)][0].split(
        "_of_")[1].split()[0].split("_")[0])
    assert count("balance_mismatches") == 64
    assert count("token_slot_mismatches") >= 40
    assert count("allowance_slot_mismatches") == 16
    assert count("reserve_mismatches") >= 2
    assert count("receipt_mismatches") == 96
    assert count("getlogs_differs_from_reference") >= 20
    (read,) = [l for l in lines if "receipts read: " in l]
    by_kind = json.loads(read.split("read: ")[1].rsplit(",", 1)[0].replace(
        "'", '"'))
    assert set(by_kind) == set(MIX) and min(by_kind.values()) >= 4
    assert int(read.rsplit(",", 1)[1].split()[0]) >= 40  # logs compared
    (slots,) = [l for l in lines if "token slots read on ranks" in l]
    ranks = json.loads(slots.split("ranks ")[1].split(":")[0])
    assert len(ranks) == 8 and ranks[0] == 1 and max(ranks) >= 5
    (lanes,) = [l for l in lines if "execute lanes in the window" in l]
    total, of = lanes.split("; sum ")[1].split()[0:3:2]
    assert int(total) == int(of) > 0


def test_traced_rehearsal_reports_the_new_metrics(traced_run):
    rc, line, lines = traced_run
    assert rc == 0
    got = line["metrics"]
    assert NEW <= set(got)
    assert {"fg_busy_ms_per_block.sync", "compiles_in_window.sync",
            "node_read_ms_per_block.sync",
            "exec_interpreted_share.sync"} <= set(got)  # deep's, kept
    assert any("also read, as sync.dense reads it: "
               "fused_row_amplification.sync = " in l for l in lines)
    assert 5 < got["exec_residue_share.sync"]["value"] < 80
    # (eight tokens are all opaque before this window opens: the
    # fallback itself is tests/test_exec_lanes.py's)
    assert 0 <= got["exec_fallback_blocks_share.sync"]["value"] <= 100
    assert 1 <= got["exec_batch_width.sync"]["value"] < 10
    assert got["exec_interpreter_ms_per_block.sync"]["value"] > 0
    assert got["exec_vector_ms_per_block.sync"]["value"] > 0
    assert got["exec_interpreter_ms_per_block.sync"]["value"] + \
        got["exec_vector_ms_per_block.sync"]["value"] < \
        got["fg_execute_ms_per_block.sync"]["value"]
    units = {m["name"]: m["unit"]
             for m in manifest.benchmark_json()["per_layer"]}
    assert all(got[name]["unit"] == units[name] for name in NEW)


@pytest.mark.parametrize("control,said,reason", [
    ("wrong-root", "state_root", "WindowMismatch"),
    ("wrong-log", "logs_bloom", "logsBloom mismatch")])
def test_a_chain_with_one_forged_header_field_is_not_correct(
        control, said, reason):
    rc, line, lines = rehearse(control=control)
    assert rc == 0 and line["correct"] is False
    assert line["failed"] > 0
    assert any(f"carries a forged {said}" in l for l in lines)
    assert any("blocks_failed_or_wrong_root" in l and "FAILED" in l
               for l in lines)
    # refused for what was forged, not by the way (a parent hash)
    assert any("failures: " in l for l in lines)
    assert any(reason in l for l in lines)


def test_a_served_receipt_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from benchmark.drivers import sync_contracts as driver

    real = driver.rpc

    def altered(port, method, *params):
        out = real(port, method, *params)
        if method == "eth_getTransactionReceipt" and out["logs"]:
            data = out["logs"][-1]["data"]
            out["logs"][-1]["data"] = data[:-1] + ("0" if data[-1] != "0"
                                                   else "1")
        return out

    monkeypatch.setattr(driver, "rpc", altered)
    rc, line, lines = rehearse()
    assert rc == 0 and line["correct"] is False
    assert any("receipt_mismatches" in l and "FAILED" in l for l in lines)
    assert any("token_slot_mismatches" in l and " = 0 " in l for l in lines)
    with pytest.raises(SystemExit):
        rehearse(control="no-such-control")
