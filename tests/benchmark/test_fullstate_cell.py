"""``snap.fullstate`` (PR 44): the plain reference of the state
(``benchmark/reference/state_trie.py``) against the program's own trie;
a whole fast sync through ``FastSyncService.run`` from three peers in
child processes against that reference, killed part-way and resumed; the
cell's files and manifest entries; a traced ``--rehearse`` and both
controls on the CPU. Says nothing of the chip. No TPU topology call
anywhere."""

import io
import json
import os
import random
import re
import sys
import threading
from contextlib import redirect_stdout
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import fastsync  # noqa: E402
from benchmark.generators import fullstate as gen  # noqa: E402
from benchmark.generators import state as gen_state  # noqa: E402
from benchmark.lib import manifest  # noqa: E402
from benchmark.reference import state_trie as ref  # noqa: E402

BENCH = manifest.benchmark_json()
CELL = manifest.cell("snap.fullstate")
CONF, TRAFFIC = CELL["config_file"], CELL["traffic_file"]
DEEP = manifest.cell("sync.deep")["config_file"]
NEW = ["peer_fetch_us_per_node.snap", "peer_rtt_ms_per_request.snap",
       "peer_requests_in_flight.snap", "full_loop_us_per_node.snap",
       "full_store_us_per_node.snap", "full_admit_us_per_node.snap",
       "full_verify_ms.snap", "storage_node_share.snap"]
# The eight are not in BENCHMARK.json (the driver takes entries at a list's
# end only, and test_driver_metrics.py pins the last fourteen): entries and
# data files wait in scripts/pr44-metrics/ for a `benchmark` PR, and
# scripts/pr44-metrics-overlay.sh lays them over a checkout.
WAITING_DIR = os.path.join(REPO, "scripts", "pr44-metrics")
with open(os.path.join(WAITING_DIR, "per_layer.json")) as _f:
    WAITING = json.load(_f)


def waiting_file(name):
    with open(os.path.join(WAITING_DIR, name + ".json")) as f:
        return json.load(f)


def read_waiting(art):
    """The eight through their readers, as ``run.per_layer`` would."""
    out = {}
    for m in WAITING:
        spec = waiting_file(m["name"])
        reader = manifest.load_module("readers", spec["reader"])
        value = reader.read(art, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
# deep's state on seed 77, by the reference on the CPU (PERF.md section
# 4): trie nodes in size classes 1-4, and by kind
CLASS_NODES_SEED_77 = {1: 2_658_634, 2: 95_291, 3: 74_665, 4: 28_542}
STATE_NODES_SEED_77 = 1_428_842 + 1_428_290 + 256
# The rate the window's length is reckoned with (as
# test_statesync_cell.py's): a program this fast pulls the window's
# nodes in 20 s, and one faster is the prompt to re-size the cell.
ASSUMED_NODES_PER_S = 40_000
SMALL = {"accounts": 2400, "funded_senders": 12, "token_contracts": 8,
         "token_slots": 1600}


# -------------------------------------------------- the plain reference


def test_hex_prefix_and_the_empty_trie():
    # the Yellow Paper's appendix C examples
    assert ref.hex_prefix(bytes([1, 2, 3, 4, 5]), False).hex() == "112345"
    assert ref.hex_prefix(bytes([0, 1, 2, 3, 4, 5]), False).hex() == "00012345"
    assert ref.hex_prefix(bytes([0, 15, 1, 12, 11, 8]), True).hex() == "200f1cb8"
    assert ref.hex_prefix(bytes([15, 1, 12, 11, 8]), True).hex() == "3f1cb8"
    assert ref.trie({}) == (ref.EMPTY_ROOT, {})
    assert ref.EMPTY_ROOT.hex().startswith("56e81f17")
    assert ref.EMPTY_CODE_HASH.hex().startswith("c5d24601")
    assert ref.rlp_bytes(b"\x7f") == b"\x7f"
    assert ref.rlp_bytes(b"\x80") == b"\x81\x80"
    assert ref.rlp_bytes(b"a" * 56)[:2] == b"\xb8\x38"
    assert ref.int_bytes(0) == b"" and ref.int_bytes(256) == b"\x01\x00"


@pytest.mark.parametrize("n,key_len,value_len", [
    (1, 2, 3), (5, 2, 3), (300, 2, 3), (300, 2, 40), (2000, 3, 1),
    (50, 32, 70)])
def test_the_references_trie_is_the_programs(n, key_len, value_len):
    """Short keys and values make nodes under 32 bytes, which a parent
    embeds: the case a state's 32-byte keys never reach."""
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.trie.mpt import MerklePatriciaTrie

    rng = random.Random(n * 131 + value_len)
    pairs = {}
    while len(pairs) < n:
        pairs[rng.randbytes(key_len)] = rng.randbytes(
            rng.randint(1, value_len))
    store = Storages().account_node_storage
    trie = MerklePatriciaTrie(store)
    for k, v in pairs.items():
        trie = trie.put(k, v)
    root, nodes = ref.plain_trie(pairs)
    assert root == trie.root_hash
    assert all(len(enc) >= 32 or h == root for h, enc in nodes.items())
    assert ref.keccak256_batch(list(nodes.values())) == list(nodes)


def test_the_references_state_is_load_genesis():
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.storage.storages import Storages

    alloc = gen_state.make_state(SMALL, 19)["alloc"]
    root, nodes, records = ref.state(alloc)
    storages = Storages()
    genesis = Blockchain(storages, fixture_config(chain_id=1)).load_genesis(
        GenesisSpec(alloc=alloc))
    assert genesis.header.state_root == root
    for kind, name in zip(gen.KINDS, fastsync.STORES):
        assert set(getattr(storages, name).source.keys()) == set(nodes[kind])
    assert len(nodes["code"]) == 8 and len(records) == len(alloc) == 2408


# --------------------- the service against the reference, on the CPU


@pytest.fixture(scope="module")
def synced(tmp_path_factory):
    """A node fast-synced from three peers in child processes, its first
    run killed part-way (the peers die), its second resumed."""
    from khipu_tpu.domain.block_header import BlockHeader

    tmp = tmp_path_factory.mktemp("fullstate-sync")
    seed, bests = 23, [24, 20, 16]
    alloc = gen_state.make_state(SMALL, seed)["alloc"]
    genesis_dir = str(tmp / "genesis")
    gen.build_genesis({"sizes": SMALL, "seed": seed, "gas_limit": 30_000_000,
                       "dir": genesis_dir, "ok": str(tmp / "genesis.ok")})
    sizes = bench_run.merged(CONF, True)["sizes"]
    rows = {int(k): v for k, v in sizes["mirror_rows"].items()}
    env = SimpleNamespace(log=lambda msg: None)
    peers = gen.Peers.start(genesis_dir, str(tmp / "a"), seed, bests, 16)
    later = board = None
    try:
        genesis = BlockHeader.decode(
            bytes.fromhex(peers.hellos[0]["genesis"]))
        cfg = fastsync.node_config(str(tmp / "node"), sizes, rows)
        board = fastsync.boot_node(env, cfg.db.data_dir, cfg, genesis, peers)
        total = sum(peers.hellos[0]["nodes"].values())
        svc = board.start_fast_sync()
        over = threading.Event()

        def kill():
            while not over.wait(0.001):
                if sum(svc.syncer.stats.nodes.values()) >= total // 2:
                    peers.stop()
                    return

        killer = threading.Thread(target=kill, daemon=True)
        killer.start()
        try:
            svc.run()
            died = None
        except Exception as e:  # whatever a node sees when its peers die
            died = e
        finally:
            over.set()
            killer.join()
        first = dict(svc.syncer.stats.nodes)
        forged = {}
        later = gen.Peers.start(genesis_dir, str(tmp / "b"), seed, bests, 16)
        # the peers that died were blacklisted by key, and these have
        # the same keys; a restarted node's blacklist is empty
        board.peer_manager.blacklist.entries.clear()
        for hello in later.hellos:
            board.peer_manager.connect("127.0.0.1", hello["port"],
                                       bytes.fromhex(hello["pub"]))
        again = board.start_fast_sync()
        state = again.run()
        for row in later.report():
            forged.update(row["forged"])
        yield SimpleNamespace(
            board=board, alloc=alloc, died=died, first=first, total=total,
            state=state, second=dict(again.syncer.stats.nodes),
            forged=forged, genesis=genesis, bests=bests,
            pivot=gen.pivot_number(bests, sizes["pivot_block_offset"]),
            counted=peers.hellos[0]["nodes"])
    finally:
        if board is not None:
            board.shutdown()
        for p in (peers, later):
            if p is not None:
                p.stop()


def test_a_killed_run_resumes_to_the_references_node_sets(synced):
    s = synced
    assert s.died is not None and 0 < sum(s.first.values()) < s.total
    root, nodes, _records = ref.state(s.alloc)
    storages = s.board.storages
    # the pivot is the median of the bests less the offset: 20 - 8
    assert s.pivot == 12 == s.board.blockchain.best_block_number
    assert s.board.blockchain.get_header_by_number(12).state_root == root
    assert s.genesis.state_root == root
    for kind, name in zip(gen.KINDS, fastsync.STORES):
        assert set(getattr(storages, name).source.keys()) == set(nodes[kind])
        assert len(nodes[kind]) == s.counted[kind]
    # every node once, however much the kill made the node fetch again
    assert s.state.downloaded_nodes == s.total == sum(map(len, nodes.values()))
    assert sum(s.first.values()) + sum(s.second.values()) >= s.total
    assert storages.app_state.fast_sync_done
    mirror = s.board.fast_sync_mirror
    assert mirror.resident_count == len(nodes["state"]) + len(nodes["storage"])
    assert mirror.verify() == 0
    assert all(mirror.contains(h) for h in list(nodes["storage"])[:64])
    assert fastsync.backfill_checks(
        s.board.blockchain, gen.empty_chain(s.genesis, max(s.bests)),
        s.pivot, s.genesis) == [
            fastsync.Check("backfilled_blocks_missing", 0, 0),
            fastsync.Check("backfilled_blocks_not_chain_linked", 0, 0)]


def test_every_account_slot_and_code_reads_back_as_seeded(synced):
    s = synced
    root, _nodes, records = ref.state(s.alloc)
    world = s.board.blockchain.get_world_state(root)
    slots = 0
    for addr, (nonce, balance, storage_root, code_hash) in records.items():
        acc = world.get_account(addr)
        assert (acc.nonce, acc.balance, acc.storage_root, acc.code_hash) == (
            nonce, balance, storage_root, code_hash)
        entry = s.alloc[addr]
        if not isinstance(entry, int):
            assert world.get_code(addr) == entry.code
            for slot, value in entry.storage.items():
                assert world.get_storage(addr, slot) == value
                slots += 1
    assert slots >= 1500 and len(records) == 2408


def test_no_forged_answer_was_stored(synced):
    s = synced
    assert len(s.forged) >= 10  # 1 in 16 of the second run's answers
    stores = [getattr(s.board.storages, name) for name in fastsync.STORES]
    for h, blob in s.forged.items():
        assert all(store.get(h) != blob for store in stores)
        assert s.board.fast_sync_mirror.get(h) != blob
        assert gen.forge(blob) == next(
            v for v in (store.get(h) for store in stores) if v is not None)


def test_forgery_is_dealt_to_one_peer_and_is_its_own_inverse():
    rng = random.Random(7)
    hashes = [rng.randbytes(32) for _ in range(40_000)]
    dealt = [gen.dealt_to(h, 11, 8, 4096) for h in hashes]
    assert set(dealt) == {-1, 0, 1, 2, 3, 4, 5, 6, 7}
    # 1 hash in 512 is dealt to a peer; asked of one peer in eight, that
    # is 1 answer in 4,096
    assert 40 <= sum(d >= 0 for d in dealt) <= 120
    assert gen.dealt_to(hashes[0], 11, 8, 0) == -1
    blob = rng.randbytes(113)
    assert gen.forge(blob) != blob and gen.forge(gen.forge(blob)) == blob
    assert gen.heights(660, 8) == [660, 656, 652, 648, 644, 640, 636, 632]
    assert gen.pivot_number(gen.heights(660, 8), 500) == 148


# --------------------------------------------- the files and the entries


def test_the_state_is_deeps_letter_for_letter():
    assert CONF["state_of"] == "fullsync-postmerge-deep" == DEEP["name"]
    for key in gen.STATE_KEYS:
        assert CONF["sizes"][key] == DEEP["sizes"][key]
        assert CONF["rehearse"]["sizes"][key] == DEEP["rehearse"]["sizes"][key]
    assert CONF["rehearse"]["sizes"] == dict(
        CONF["rehearse"]["sizes"], **SMALL)


def test_the_configuration_is_the_programs_defaults_and_eight_peers():
    from khipu_tpu.config import SyncConfig

    sizes, defaults = CONF["sizes"], SyncConfig()
    assert sizes["peers"] == 8
    for key in ("nodes_per_request", "min_peers_to_choose_pivot",
                "pivot_block_offset", "peer_request_timeout"):
        assert sizes[key] == getattr(defaults, key)
    from khipu_tpu.sync.fast_sync_service import MAX_CONCURRENT_REQUESTS

    assert MAX_CONCURRENT_REQUESTS == 50 >= sizes["peers"]
    assert defaults.fast_sync_mirror_rows == ()
    # the chain is long enough for the default offset, whichever peer's
    # best is the median
    bests = gen.heights(sizes["chain_blocks"], sizes["peers"])
    assert gen.pivot_number(bests, sizes["pivot_block_offset"]) == 148 > 1
    assert min(bests) > sizes["pivot_block_offset"]
    assert set(CONF["reduced"]) == set(CELL["config_entry"]["reduced"]) == {
        "accounts", "contracts", "storage_slots", "behaviours", "chain"}
    assert len(CONF["guarantees"]) >= 7 and len(CONF["source"]) <= 200
    assert CONF["source"] == CELL["config_entry"]["source"]


def test_the_mirror_holds_every_class_with_headroom():
    rows = {int(k): v for k, v in CONF["sizes"]["mirror_rows"].items()}
    assert set(rows) == set(CLASS_NODES_SEED_77)
    for nb, nodes in CLASS_NODES_SEED_77.items():
        assert rows[nb] % 1024 == 0
        assert 1.03 <= rows[nb] / nodes <= 1.30
        # room for the partial tile set-up's flush leaves behind
        assert rows[nb] - nodes >= 2 * 1024
    small = CONF["rehearse"]["sizes"]["mirror_rows"]
    assert all(v % 1024 == 0 and v >= 2048 for v in small.values())
    # reckoned device bytes: rows and claims, ~0.55 GB of the chip's 16
    reckoned = sum(rows[nb] * (136 * nb + 32) for nb in rows)
    assert 0.5e9 < reckoned < 0.65e9


def test_the_traffic_keeps_the_cell_resumed_and_half_a_minute_long():
    remaining = TRAFFIC["resume_remaining_nodes"]
    assert 0 < remaining < STATE_NODES_SEED_77
    # at the rate assumed the pull alone is 20 s or more ...
    assert remaining / ASSUMED_NODES_PER_S >= 20
    # ... and at PR 37's 23,354 nodes/s the window still ends by
    # completion, with room for pivot, verify and backfill
    run_seconds = BENCH["run_seconds"]
    assert remaining / 23_354 <= run_seconds - 8
    assert TRAFFIC["forge_one_in"] == 4096 and TRAFFIC["sample"] == 4096
    assert TRAFFIC["sample_contracts"] == 16
    assert TRAFFIC["rehearse"]["resume_remaining_nodes"] == 1500


def test_the_entries_are_additions_at_the_ends_of_their_lists():
    # per_layer is the parent's, entry for entry: the driver takes new
    # entries at a list's end only, and test_driver_metrics.py pins the
    # last fourteen, so the eight wait in scripts/pr44-metrics/
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == 56 and not set(NEW) & set(names)
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in ("keccak_verify_hbm_share.snap", "device_idle_share.snap"):
        assert entries[name]["workloads"] == [
            "snap.statesync", "snap.fullstate"]
    (e2e,) = [m for m in BENCH["end_to_end"]
              if m["name"] == "snap_nodes_per_s"]
    assert e2e["workloads"] == ["snap.statesync", "snap.fullstate"]
    assert e2e["bound"] == 0.2
    assert BENCH["workloads"][-1] == {
        "name": "snap.fullstate", "config": "fastsync-fullstate",
        "traffic": "fullstate", "chips": 1, "why": CELL["why"]}
    assert BENCH["configs"][-1]["name"] == "fastsync-fullstate"
    assert [m["name"] for m in manifest.metrics_for(
        "snap.fullstate", "per_layer")] == [
            "keccak_verify_hbm_share.snap", "device_idle_share.snap"]


def test_the_eight_that_wait_are_entries_with_files_a_benchmark_pr_can_take():
    assert [m["name"] for m in WAITING] == NEW
    # a layer the benchmark names, or the one PERF.md section 3 adds
    layers = {m["layer"] for m in BENCH["per_layer"]} | {
        "peer pool (sync/fast_sync_service.py)"}
    with open(os.path.join(REPO, "PERF.md")) as f:
        assert "| peer pool (sync/fast_sync_service.py) |" in f.read()
    for m in WAITING:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] == "snap_nodes_per_s"
        assert m["workloads"] == ["snap.fullstate"]
        assert m["layer"] in layers
        spec = waiting_file(m["name"])
        assert set(spec) == {"reader", "args", "what"}
        # read by readers the benchmark had, from the program's own
        # spans and counters
        assert spec["reader"] in ("registry_delta", "span_ms")
        assert not os.path.exists(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".json"))


def test_the_driver_stands_between_the_service_and_nothing():
    """The window is ``FastSyncService.run()`` on a node assembled
    through ``service_board.py``: the driver builds no syncer and no
    mirror, wraps no storage, and sets no capacity."""
    with open(fastsync.__file__) as f:
        source = f.read()
    code = re.sub(r'"""(.|\n)*?"""', "", source)
    for name in ("StateSyncer", "DeviceNodeMirror", "Timed", ".capacity",
                 "capacity_rows", "make_mirror", "admit(", "admit_packed",
                 "put_sync_state", "sync_once", "prefill"):
        assert name not in code, name
    assert "ServiceBoard(cfg)" in code and "board.start_network(" in code
    assert code.count("board.start_fast_sync(") == 2  # set-up's, the window's
    assert "final = svc.run()" in code
    # the peers are the benchmark's, and never import JAX
    with open(gen.__file__) as f:
        assert "jax" not in re.sub(r'"""(.|\n)*?"""', "", f.read()).replace(
            "JAX_PLATFORMS", "")


def test_a_program_that_cannot_be_stopped_and_resumed_fails_at_once(
        monkeypatch):
    """What the driver's run of the parent commit under this benchmark
    needs: another exit code than 0, soon, and no seed data touched."""
    from khipu_tpu.sync import fast_sync

    monkeypatch.delattr(fast_sync, "SyncStopped")
    with pytest.raises(SystemExit, match="cannot run fastsync-fullstate"):
        fastsync.run(SimpleNamespace())


# --------------------------------------------- the rehearsals, end to end


def rehearse(trace, control=None, seed="3000000019"):
    """(rc, result line, printed lines, the driver's artefacts) of one
    rehearsal."""
    kept = {}
    inner = bench_run.result_line

    def result_line(outcome, *args, **kwargs):
        kept["art"] = outcome.artefacts
        return inner(outcome, *args, **kwargs)

    bench_run.result_line = result_line
    out = io.StringIO()
    argv = ["--workload", "snap.fullstate", "--seed", seed, "--seconds", "2",
            "--trace", str(trace), "--rehearse"]
    try:
        with redirect_stdout(out):
            rc = bench_run.main(argv + (["--control", control]
                                        if control else []))
    finally:
        bench_run.result_line = inner
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), lines, kept["art"]


@pytest.fixture(scope="module", autouse=True)
def own_run_dir(tmp_path_factory):
    """``run.py`` empties ``<BENCH_DIR>/cache/_run`` at the start of
    every run, and xdist runs the other files' rehearsals in other
    processes at the same time: these get a directory of their own."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(bench_run, "BENCH_DIR",
                      str(tmp_path_factory.mktemp("fullstate")))
        yield


@pytest.fixture(scope="module")
def traced():
    return rehearse(trace=1)


def test_a_traced_rehearsal_reports_the_cells_metrics(traced):
    rc, line, lines, art = traced
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= art["nodes"] >= 1400
    # the HBM share needs the chip's program names: not on the CPU
    assert set(line["metrics"]) - {"keccak_verify_hbm_share.snap"} == {
        "device_idle_share.snap"}
    # the eight that wait, read from this run's artefacts
    got = read_waiting(art)
    assert set(got) == set(NEW)
    assert all(got[name]["value"] > 0 for name in NEW)
    # three peers, asked at once
    assert 1.0 < got["peer_requests_in_flight.snap"]["value"] <= 3.0
    assert all(n > 0 for n in art["requests_by_peer"])
    assert len(art["requests_by_peer"]) == 3
    # the resumed part of the syncer's order is the storage tries
    assert got["storage_node_share.snap"]["value"] == pytest.approx(
        100.0 * art["by_kind"]["storage"] / art["nodes"])
    assert got["storage_node_share.snap"]["value"] > 40
    # the per-node phases are the window's syncer's own counters
    (phases,) = [l for l in lines if "window: phases " in l]
    booked = dict(p.split("=") for p in phases.split("phases ")[1].split())
    assert got["peer_fetch_us_per_node.snap"]["value"] == pytest.approx(
        1e6 * float(booked["fetch"]) / art["nodes"], rel=0.01)
    assert got["full_verify_ms.snap"]["value"] == pytest.approx(
        1e3 * float(booked["verify"]), rel=0.05)
    checks = [l for l in lines if l.startswith("check: ")]
    assert len(checks) >= 24 and all(l.endswith(" ok") for l in checks)


def test_the_programs_new_spans_reach_the_breakdown(traced):
    rc, line, lines, art = traced
    names = {s.name for s in art["spans"]}
    assert {"fastsync.pivot", "fastsync.pool.request", "fastsync.backfill",
            "fastsync.batch", "fastsync.fetch", "fastsync.store",
            "mirror.admit", "mirror.flush", "mirror.verify"} <= names
    assert art["spans_dropped"] == 0
    requests = [s for s in art["spans"] if s.name == "fastsync.pool.request"]
    assert len(requests) == sum(art["requests_by_peer"])
    assert len({s.tags["peer"] for s in requests}) == 3
    assert all(s.tags["outcome"] == "ok" for s in requests)
    (pivot,) = [s for s in art["spans"] if s.name == "fastsync.pivot"]
    assert pivot.tags["number"] == 12
    gaps = dict(line["breakdown"]["idle_gaps"])
    assert "fastsync.pool.request" in gaps
    from khipu_tpu.observability.trace import tracer

    assert tracer.enabled is False


@pytest.mark.parametrize("control,fails", [
    ("no-batch-check", "forged_values_stored"),
    ("lost-code", "code_hash_mismatches_of_8")])
def test_a_control_comes_out_not_correct(control, fails):
    rc, line, lines, art = rehearse(trace=0, control=control, seed="41")
    assert rc == 0 and line["correct"] is False
    failed = [l.split()[1] for l in lines
              if l.startswith("check: ") and l.endswith("FAILED")]
    assert fails in failed
    if control == "lost-code":
        # the sync itself was sound: only the lost blob shows
        assert failed == [fails] and art["complete"]
    # an untraced run reports the end-to-end metrics and keeps no spans
    assert set(line["metrics"]) == {"setup_s", "snap_nodes_per_s"}
    assert art["spans"] == []
    # the control is gone with its run: the node checks again
    from khipu_tpu.base.crypto.keccak import keccak256
    from khipu_tpu.native.keccak import keccak256_batch
    from khipu_tpu.sync import fast_sync, fast_sync_service

    assert fast_sync.keccak256 is keccak256
    assert fast_sync_service.keccak256_batch is keccak256_batch
