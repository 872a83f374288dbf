"""The cell ``sync.deep`` on the CPU: its generators, its plain
reference, its readers, and ``run.py --rehearse`` end to end at the
configuration's rehearsal sizes (2,400 accounts, 8 token contracts with
1,600 pre-populated slots, a 256-entry node cache). Says nothing of the
chip."""

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
from benchmark.generators import chain_state, state  # noqa: E402
from benchmark.lib import manifest  # noqa: E402
from benchmark.readers import registry_delta, span_tag_mean  # noqa: E402
from benchmark.reference import ledger_state  # noqa: E402
from benchmark.reference.keccak import keccak256_batch  # noqa: E402

SEED = 2_147_483_777
NEW = {"node_cache_miss_reads_per_block.sync", "node_read_us_per_miss.sync",
       "node_read_ms_per_block.sync", "fused_rounds_per_dispatch.sync",
       "exec_interpreted_share.sync", "trie_read_ms_per_block.sync",
       "trie_reads_per_block.sync"}
CONF = manifest.cell("sync.deep")["config_file"]
TRAFFIC = manifest.cell("sync.deep")["traffic_file"]


# ------------------------------------------------------- the generators


def test_holder_counts_follow_the_stated_zipf():
    sizes = CONF["sizes"]
    counts = state.holder_counts(sizes["token_contracts"],
                                 sizes["token_slots"])
    assert len(counts) == 256
    assert counts[0] == 171_214 and counts[255] == 669
    assert abs(sum(counts) - sizes["token_slots"]) < 128  # rounding
    for rank in (2, 16, 128, 256):  # count * rank is flat
        assert counts[rank - 1] * rank == pytest.approx(counts[0], rel=0.002)


@pytest.fixture(scope="module")
def small_state():
    return state.make_state(bench_run.merged(CONF, True)["sizes"], SEED)


def test_every_contract_has_a_code_hash_of_its_own_and_one_behaviour():
    codes = [state.token_code(rank) for rank in range(1, 257)]
    assert len(set(keccak256_batch(codes))) == 256
    from benchmark.generators.chain import ERC20_RUNTIME as runtime

    assert runtime[-1] == 0x00  # STOP: what follows never runs
    assert all(c[:-2] == runtime and int.from_bytes(c[-2:], "big") == r
               for r, c in enumerate(codes, 1))


def test_one_seed_gives_the_same_state_and_picks_twice(small_state):
    sizes = bench_run.merged(CONF, True)["sizes"]
    again = state.make_state(sizes, SEED)
    assert again["tokens"] == small_state["tokens"]
    assert again["others"] == small_state["others"]
    assert list(again["alloc"]) == list(small_state["alloc"])
    for a, b in zip(again["holders"] + again["holdings"],
                    small_state["holders"] + small_state["holdings"]):
        assert a.tobytes() == b.tobytes()
    for addr in small_state["tokens"]:
        assert again["alloc"][addr] == small_state["alloc"][addr]
    other = state.make_state(sizes, SEED + 1)
    assert other["tokens"] != small_state["tokens"]
    params = TRAFFIC["params"]
    draw = lambda s: chain_state.draw(
        params, 40, 12, len(s["others"]), s["holders"], SEED)
    first, second = draw(small_state), draw(again)
    assert all(first[k].tobytes() == second[k].tobytes() for k in first)


def test_the_state_is_what_the_configuration_says(small_state):
    sizes = bench_run.merged(CONF, True)["sizes"]
    s = small_state
    assert len(s["others"]) == sizes["accounts"] - sizes["funded_senders"]
    assert len(s["alloc"]) == sizes["accounts"] + sizes["token_contracts"]
    assert len(set(s["tokens"]) | set(s["others"]) | set(s["senders"])) \
        == len(s["alloc"])
    for rank, (addr, who, held) in enumerate(
            zip(s["tokens"], s["holders"], s["holdings"]), 1):
        record = s["alloc"][addr]
        assert record.code == state.token_code(rank)
        assert len(set(who.tolist())) == len(who) == len(record.storage)
        assert held.dtype == np.uint64 and held.min() >= 1
        slots = ledger_state.token_slots([s["others"][i] for i in who[:3]])
        assert [record.storage[k] for k in slots] == held[:3].tolist()


def test_the_picks_follow_the_traffic_files_parameters(small_state):
    holders = small_state["holders"]
    picks = chain_state.draw(TRAFFIC["params"], 400, 200,
                             len(small_state["others"]), holders, SEED)
    token = picks["kind"] == chain_state.KIND_TOKEN
    assert token.mean() == 0.5 and (picks["token"][~token] == -1).all()
    assert (picks["sender"] == np.arange(200) % 200).all()
    # Zipf(1.0) over 8 ranks: rank 1 gets 1 / H_8 of the calls
    share = np.bincount(picks["token"][token], minlength=8) / token.sum()
    harmonic = sum(1 / r for r in range(1, 9))
    assert share == pytest.approx(
        [1 / (r * harmonic) for r in range(1, 9)], abs=0.01)
    # ~0.8 of token receivers hold that token already (a little more:
    # a receiver drawn from everyone may happen to hold it)
    held = np.array([r in set(holders[c].tolist()) for c, r in zip(
        picks["token"][token][:4000], picks["receiver"][token][:4000])])
    assert 0.78 < held.mean() < 0.92


# ---------------------------------------------------- the plain ledger


def test_the_plain_ledger_against_a_hand_folded_three_block_example():
    T, P = ledger_state.KIND_TOKEN, ledger_state.KIND_PLAIN
    picks = {
        "kind": np.array([[T, T, P], [T, P, P], [T, T, T]]),
        "sender": np.array([[0, 1, 2], [0, 1, 2], [0, 1, 2]]),
        "receiver": np.array([[5, 6, 7], [5, 7, 7], [9, 5, 6]]),
        "amount": np.array([[10, 20, 30], [1, 2, 3], [100, 200, 300]]),
        "token": np.array([[0, 1, -1], [0, -1, -1], [0, 0, 1]]),
    }
    holders = [np.array([5, 8]), np.array([6])]
    holdings = [np.array([1000, 7], dtype=np.uint64),
                np.array([(1 << 64) - 1], dtype=np.uint64)]
    extra = np.arange(10)
    assert ledger_state.plain_balances(50, extra, picks, 3)[7] == 50 + 7 + 35
    assert ledger_state.plain_balances(50, extra, picks, 1)[7] == 50 + 7 + 30
    book = ledger_state.contract_ledger(0, holders[0], holdings[0], picks, 3)
    assert book == {
        "untouched": {8: 7},
        "updated": {5: 1000 + 10 + 1 + 200},
        "created": {9: 100},
        "senders": {0: (1 << 256) - 111, 1: (1 << 256) - 200},
    }
    # contract 1 after two blocks: block 3's transfer has not happened
    book = ledger_state.contract_ledger(1, holders[1], holdings[1], picks, 2)
    assert book == {"untouched": {}, "updated": {6: (1 << 64) - 1 + 20},
                    "created": {}, "senders": {1: (1 << 256) - 20}}
    # a holder's slot is keccak(pad32(holder) ++ pad32(0))
    (slot,) = ledger_state.token_slots([b"\x01" * 20])
    assert slot == int.from_bytes(keccak256_batch(
        [bytes(12) + b"\x01" * 20 + bytes(32)])[0], "big")


# ----------------------------------------------------------- the readers


def snaps(**close):
    zero = {k: ({lk: 0 for lk in v} if isinstance(v, dict) else 0)
            for k, v in close.items()}
    return zero, close


def test_registry_delta_takes_close_minus_open_over_matching_labels():
    reads = "khipu_nodestore_reads_total"
    secs = "khipu_nodestore_source_seconds_total"
    opened = {reads: {'from="cache",store="account"': 100,
                      'from="source",store="account"': 10,
                      'from="source",store="storage"': 5,
                      'from="absent",store="storage"': 1},
              secs: {'store="account"': 1.0, 'store="storage"': 0.5},
              "khipu_exec_batch_residue_txs": 3}
    closed = {reads: {'from="cache",store="account"': 900,
                      'from="source",store="account"': 70,
                      'from="source",store="storage"': 45,
                      'from="absent",store="storage"': 11},
              secs: {'store="account"': 1.3, 'store="storage"': 0.7},
              "khipu_exec_batch_residue_txs": 13}
    art = {"registry": (opened, closed), "blocks": 20,
           "replay_stats": [SimpleNamespace(txs=40), SimpleNamespace(txs=60)]}
    source = [{"family": reads, "labels": {"from": "source"}}]
    assert registry_delta.read(art, num=source, den="block") == 5.0
    misses = [{"family": reads,
               "labels": {"from": ["source", "mirror", "absent"]}}]
    assert registry_delta.read(
        art, num=[{"family": secs}], den=misses,
        scale=1e6) == pytest.approx(0.5e6 / 110)
    assert registry_delta.read(
        art, num=[{"family": "khipu_exec_batch_residue_txs"}], den="tx",
        scale=100.0) == 10.0
    only = [{"family": reads,
             "labels": {"from": "source", "store": "storage"}}]
    assert registry_delta.read(art, num=only, den="block") == 2.0


def test_a_program_without_the_counters_or_the_tag_leaves_nothing_to_read():
    old = {"registry": ({"khipu_best_block_number": 1},) * 2, "blocks": 20}
    num = [{"family": "khipu_nodestore_reads_total"}]
    assert registry_delta.read(old, num=num, den="block") is None
    assert registry_delta.read({"blocks": 20}, num=num, den="block") is None
    there = {"registry": snaps(khipu_nodestore_reads_total={"_": 5})}
    assert registry_delta.read(there, num=num, den="block") is None  # 0 blocks
    span = lambda **tags: SimpleNamespace(name="fused.dispatch", tags=tags)
    args = {"name": "fused.dispatch", "tag": "rounds"}
    assert span_tag_mean.read(
        {"spans": [span(rounds=8), span(rounds=16)]}, **args) == 12.0
    assert span_tag_mean.read({"spans": [span(nodes=1)]}, **args) is None
    assert span_tag_mean.read({"spans": []}, **args) is None
    for name in NEW:  # every new metric's reader, on a parent's artefacts
        spec = manifest.metric_file(name)
        reader = manifest.load_module("readers", spec["reader"])
        art = {"registry": old["registry"], "blocks": 20, "windows": 10,
               "spans": [span(nodes=1, rows_padded=8)], "replay_stats": []}
        assert reader.read(art, **spec["args"]) is None


def test_the_manifest_names_the_cell_its_files_and_its_metrics():
    listing = manifest.listing()
    assert "sync.deep" in listing["cells"]
    assert "fullsync-postmerge-deep" in listing["configs_in_manifest"]
    assert "deep" in listing["traffic_in_manifest"]
    assert NEW <= set(listing["metrics_in_manifest"])
    reported = {m["name"] for m in manifest.metrics_for(
        "sync.deep", "per_layer")}
    dense = {m["name"] for m in manifest.metrics_for(
        "sync.dense", "per_layer")}
    # seven of dense's are pinned to one cell by test_program_metrics.py
    # (PERF.md section 7); the driver logs them instead
    pinned = {m["name"] for m in manifest.benchmark_json()["per_layer"]
              if m["workloads"] == ["sync.dense"]}
    assert len(pinned) == 7 and TRAFFIC["log_metrics_of"] == "sync.dense"
    assert reported == (dense - pinned) | NEW and not dense & NEW
    entry = manifest.cell("sync.deep")["config_entry"]
    assert set(entry["reduced"]) == set(CONF["reduced"]) == {
        "accounts", "contracts", "storage_slots", "behaviours"}
    # the shapes of the source are fullsync-postmerge's, unchanged, the
    # commit window and the batch too: the two sync cells differ in
    # state, contracts and the node cache's size, and in nothing else
    dense_conf = manifest.cell("sync.dense")["config_file"]
    for key in ("txs_per_block", "funded_senders", "batch_blocks"):
        assert CONF["sizes"][key] == dense_conf["sizes"][key]
    assert CONF["sizes"]["batch_blocks"] == \
        5 * CONF["program"]["sync"]["commit_window_blocks"] == 15
    assert CONF["program"]["sync"] == dense_conf["program"]["sync"]
    assert "six seeds" in CONF["assumed"]["commit_window_blocks"]
    assert (TRAFFIC["warmup_blocks"], TRAFFIC["warmup_max_blocks"]) == (
        45, 120)
    assert CONF["program"]["bridge"] == dense_conf["program"]["bridge"]
    assert CONF["program"]["db"] == {"engine": "kesque",
                                     "cache_size": 131072}
    assert CONF["guarantees"][:2] == dense_conf["guarantees"][:2]


# --------------------------------------------- rehearsals, end to end


@pytest.fixture(scope="module", autouse=True)
def own_run_dir(tmp_path_factory):
    """``run.py`` empties ``<BENCH_DIR>/cache/_run`` at the start of
    every run, and xdist runs the other files' rehearsals in other
    processes at the same time: these get a directory of their own (and
    share its seed cache among themselves)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(bench_run, "BENCH_DIR",
                      str(tmp_path_factory.mktemp("deep")))
        yield


def rehearse(trace=0, control=None):
    argv = ["--workload", "sync.deep", "--seed", str(SEED), "--seconds",
            "2", "--trace", str(trace), "--rehearse"]
    if control:
        argv += ["--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(argv)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), lines


@pytest.fixture(scope="module")
def deep_run():
    return rehearse()


def test_deep_cell_rehearsal_ends_in_the_contracts_line(deep_run):
    rc, line, lines = deep_run
    assert rc == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"sync_blocks_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # a rehearsal, and says so
    checks = [l for l in lines if l.startswith("check: ")]
    assert len(checks) >= 12 and all(l.endswith(" ok") for l in checks)


def test_the_served_node_agrees_with_the_plain_ledger_on_every_kind_of_slot(
        deep_run):
    _, _, lines = deep_run
    (read,) = [l for l in lines if "token slots read on ranks" in l]
    ranks = json.loads(read.split("ranks ")[1].split(":")[0])
    assert len(ranks) == 8 and ranks[0] == 1 and max(ranks) >= 5
    seen = json.loads(read.split(": ", 1)[1].replace("'", '"'))
    assert set(seen) == {"untouched", "updated", "created", "senders"}
    assert all(n >= 4 for n in seen.values())
    (slots,) = [l for l in lines if l.startswith("check: token_slot_mism")]
    assert f"_of_{sum(seen.values())} = 0 limit 0 ok" in slots
    assert any(l.startswith("check: balance_mismatches_of_64 = 0 ")
               for l in lines)


def test_the_rehearsal_misses_the_node_cache_and_dirties_many_tries(deep_run):
    _, _, lines = deep_run
    (acct,) = [l for l in lines if "node reads, account store" in l]
    (stor,) = [l for l in lines if "node reads, storage store" in l]
    for l in (acct, stor):
        counts = dict(kv.split("=") for kv in l.split("window: ")[1].split(
            " hit rate")[0].split())
        assert int(counts["source"]) > 0 and int(counts["absent"]) == 0


def test_traced_deep_rehearsal_reports_the_new_metrics():
    rc, line, lines = rehearse(trace=1)
    assert rc == 0 and line["correct"] is True
    got = line["metrics"]
    assert NEW <= set(got)
    assert {"fg_busy_ms_per_block.sync", "compiles_in_window.sync",
            "fused_wait_ms_per_window.sync"} <= set(got)  # dense's, kept
    assert any("also read, as sync.dense reads it: "
               "fused_row_amplification.sync = " in l for l in lines)
    assert got["node_cache_miss_reads_per_block.sync"]["value"] > 0
    assert got["node_read_us_per_miss.sync"]["value"] > 0
    assert got["node_read_ms_per_block.sync"]["value"] > 0
    assert 4 <= got["fused_rounds_per_dispatch.sync"]["value"] <= 32
    assert 0 <= got["exec_interpreted_share.sync"]["value"] <= 100
    units = {m["name"]: m["unit"]
             for m in manifest.benchmark_json()["per_layer"]}
    assert all(got[name]["unit"] == units[name] for name in NEW)


def test_a_deep_chain_with_one_wrong_header_root_is_not_correct():
    rc, line, lines = rehearse(control="wrong-root")
    assert rc == 0 and line["correct"] is False
    assert line["failed"] > 0
    assert any("blocks_failed_or_wrong_root" in l and "FAILED" in l
               for l in lines)


def test_a_served_token_slot_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from benchmark.drivers import sync_state as driver

    real = driver.rpc

    def altered(port, method, *params):
        out = real(port, method, *params)
        if method == "eth_getStorageAt":
            return hex(int(out, 16) ^ 1)
        return out

    monkeypatch.setattr(driver, "rpc", altered)
    rc, line, lines = rehearse()
    assert rc == 0 and line["correct"] is False
    assert any("token_slot_mismatches" in l and "FAILED" in l for l in lines)
    assert any("balance_mismatches_of_64 = 0" in l for l in lines)


def test_a_program_whose_genesis_takes_balances_only_fails_at_once(
        monkeypatch):
    """The parent of the PR that added the cell, under this benchmark's
    files: no result line, and nothing built before it says so."""
    from khipu_tpu.domain import blockchain

    monkeypatch.delattr(blockchain, "GenesisAccount")
    out = io.StringIO()
    with redirect_stdout(out), pytest.raises(SystemExit) as stop:
        bench_run.main(["--workload", "sync.deep", "--seed", "5",
                        "--seconds", "1", "--trace", "0", "--rehearse"])
    assert stop.value.code not in (0, None)
    assert '"correct"' not in out.getvalue()
    assert "seed:" not in out.getvalue()


def test_that_early_exit_waits_for_the_rlp_extensions_compiler(monkeypatch):
    """In a fresh checkout the program's import starts gcc in a daemon
    thread; a process that left before it ended would leave gcc running
    (the driver refuses that). The exit comes after the build's own
    entry point has returned."""
    from khipu_tpu.domain import blockchain
    from khipu_tpu.native import build

    order = []
    monkeypatch.delattr(blockchain, "GenesisAccount")
    monkeypatch.setattr(build, "load_rlp_ext",
                        lambda: order.append("build waited for"))
    with redirect_stdout(io.StringIO()), pytest.raises(SystemExit):
        bench_run.main(["--workload", "sync.deep", "--seed", "5",
                        "--seconds", "1", "--trace", "0", "--rehearse"])
    assert order == ["build waited for"]


def test_the_signature_census_counts_signatures_and_edges_per_window():
    """``tools/signature_census.py``, the tool that chose this cell's
    commit window: per window value the signatures the chain meets, and
    the least and most of every count the signature buckets."""
    from benchmark.tools import signature_census

    out = io.StringIO()
    with redirect_stdout(out):
        rc = signature_census.main([
            "--workload", "sync.deep", "--seed", str(SEED), "--windows",
            "2,3", "--blocks", "8", "--rehearse"])
    lines = out.getvalue().splitlines()
    assert rc == 0
    for window, batch in ((2, 10), (3, 15)):
        head = [l for l in lines if l.startswith(
            f"census: window {window} batch {batch}, 8 blocks, ")]
        assert len(head) == 1 and int(head[0].split()[-2]) >= 1
    edges = [l for l in lines if "least..most before bucketing" in l]
    assert len(edges) == 2
    for dim in ("1.rows=", "4.subs=", "3.admit=", "rounds=", "ext="):
        assert all(dim in l for l in edges)
    chip = [l for l in lines if "on the chip, by block" in l]
    assert len(chip) >= 2  # each window's first signature, then changes
    assert "1.rows=1024" in chip[0] and "ext=8192" in chip[0]
    first = [l for l in lines if l.startswith("census:   first by block")]
    assert first and all("backend=jnp" in l for l in first)
