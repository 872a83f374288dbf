"""The driver thread by name (PR 42): execute outside its lanes as tags
on the ``execute`` span, commit by part on a ``commit`` span, and a
store miss split into lock wait and read, counted in per-thread books
where it happens and written on ``window.build`` as the driver thread's
own (ledger/ledger.py, ledger/window.py, sync/replay.py,
storage/node_storage.py, unconfirmed.py, kesque.py). Times here say
that the books add up, never what anything costs."""

import dataclasses
import threading
import time

import pytest

from khipu_tpu.config import SyncConfig, fixture_config
from khipu_tpu.domain.block import Block
from khipu_tpu.domain.blockchain import Blockchain
from khipu_tpu.ledger import ledger, schedule
from khipu_tpu.observability.thread_books import ThreadBooks
from khipu_tpu.observability.trace import _NULL_SPAN, span, tracer
from khipu_tpu.storage.kesque import KesqueStore
from khipu_tpu.storage.storages import Storages
from khipu_tpu.sync.replay import ReplayDriver
from tests.test_exec_lanes import (
    PLAIN,
    TOKEN,
    C,
    approve,
    build,
    transfer,
)

CFG = dataclasses.replace(
    fixture_config(chain_id=1),
    sync=SyncConfig(parallel_tx=True, commit_window_blocks=2,
                    pipeline_depth=2))
LANE_TAGS = [lane + "_s" for lane in ledger.EXEC_LANES]
PART_TAGS = [part + "_s" for part in ledger.EXEC_PARTS]
# accounts and storage slots each block below writes, by hand: its
# senders, the plain receivers of value, the token (its storage root)
# and the coinbase; a transfer writes two balances, an approve one
# allowance
WRITTEN = [(6, 4), (8, 4), (9, 5), (10, 3)]


@pytest.fixture(scope="module")
def token_chain():
    """test_exec_lanes' four blocks over one token: residue, the checked
    lane (a segment checkpoint), an escape that rolls a segment back,
    then residue barriers with vector slivers between them."""
    spec, blocks = build({TOKEN: C.token_code(1)}, [
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
    return spec, [Block.decode(b.encode()) for b in blocks]


def replay(spec, blocks, storages=None, traced=True):
    """The blocks through the windowed replay on a fresh node whose node
    cache holds one entry (so reads miss it); the ring's spans."""
    schedule.reset_learner()
    chain = Blockchain(storages or Storages(cache_size=1), CFG)
    chain.load_genesis(spec)
    if traced:
        tracer.enable()
        tracer.reset()
    try:
        stats = ReplayDriver(chain, CFG, device_commit=False).replay(blocks)
        return stats, chain, tracer.snapshot()
    finally:
        tracer.disable()
        tracer.reset()


def named(spans, name):
    return [s for s in spans if s.name == name]


def test_execute_spans_name_the_parts_outside_the_lanes(token_chain):
    stats, _, spans = replay(*token_chain)
    executes = named(spans, "execute")
    assert [s.tags["block"] for s in executes] == [1, 2, 3, 4]
    for s in executes:
        assert {*PART_TAGS, "copies", "copy_s"} <= set(s.tags)
        told = sum(s.tags[t] for t in LANE_TAGS + PART_TAGS)
        assert 0 < told <= s.duration
        assert all(s.tags[t] >= 0 for t in PART_TAGS)
        assert s.tags["plan_s"] > 0 and s.tags["post_s"] > 0
        assert s.tags["validate_s"] > 0
        # the interpreter ran in every block, and a call frame is a copy
        assert s.tags["copies"] >= 1 and 0 < s.tags["copy_s"] <= s.duration
    # only a segment that holds a checked call pays for a checkpoint
    assert [s.tags["checkpoint_s"] > 0 for s in executes] == [
        False, True, True, False]
    assert sum(s.duration for s in executes) <= stats.phases["execute"]


def test_every_block_has_one_commit_span_split_in_three(token_chain):
    stats, _, spans = replay(*token_chain)
    commits = named(spans, "commit")
    builds = {s.sid: s for s in named(spans, "window.build")}
    assert [s.tags["block"] for s in commits] == [1, 2, 3, 4]
    for s, (accounts, slots) in zip(commits, WRITTEN):
        assert builds[s.parent].tags["block"] == s.tags["block"]
        parts = [s.tags[t] for t in ("storage_s", "account_s", "root_s")]
        assert all(p > 0 for p in parts) and sum(parts) <= s.duration
        assert (s.tags["accounts"], s.tags["slots"]) == (accounts, slots)
    assert sum(s.duration for s in commits) <= stats.phases["commit"]


def test_commit_counts_what_the_world_wrote(token_chain):
    """The same counts from the executed worlds' own write logs, block
    by block on a second node."""
    spec, blocks = token_chain
    schedule.reset_learner()
    chain = Blockchain(Storages(), CFG)
    parent = chain.load_genesis(spec).header
    for block, (accounts, slots) in zip(blocks, WRITTEN):
        result = ledger.execute_block(  # the root check would flush
            block, parent.state_root, chain.get_world_state, CFG,
            check_root=False)
        world = result.world
        dirty = {a for a, s in world.storages.items() if s.logs}
        changed = {a for a, d in world.deltas.items()
                   if d.nonce or d.balance}
        assert len(set(world.accounts) | changed | dirty) == accounts
        assert sum(len(world.storages[a].logs) for a in dirty) == slots
        chain.save_block(block, result.receipts, block.header.difficulty,
                         world)
        parent = block.header


def test_the_build_spans_misses_are_the_driver_threads_own(token_chain):
    """A second thread reads the same stores all through the replay:
    its misses are in the registry's totals and not in the tags."""
    storages = Storages(cache_size=1)
    seen = []  # (thread, when) of every miss, by a shim of the test's

    def shim(store):
        inner = store._unconfirmed.get

        def get(key):
            seen.append((threading.get_ident(), time.perf_counter()))
            return inner(key)

        store._unconfirmed.get = get

    for store in storages._node_storages:
        shim(store)
    stop = threading.Event()

    def other():
        n = 0
        while not stop.is_set():
            n += 1
            storages.account_node_storage.get(n.to_bytes(32, "big"))
            storages.storage_node_storage.get(n.to_bytes(32, "big"))

    reader = threading.Thread(target=other)
    reader.start()
    try:
        while not seen:  # the other thread is reading before the replay
            time.sleep(0.001)
        _, _, spans = replay(*token_chain, storages=storages)
    finally:
        stop.set()
        reader.join()
    builds = named(spans, "window.build")
    assert len(builds) == 4
    (driver,) = {s.tid for s in builds}
    assert driver == threading.get_ident() != reader.ident
    for s in builds:
        mine = sum(1 for who, when in seen
                   if who == driver and s.t0 <= when <= s.t1)
        assert s.tags["misses"] == mine > 0
        assert 0 <= s.tags["miss_wait_s"] + s.tags["miss_engine_s"] \
            <= s.tags["miss_s"] <= s.duration
        assert s.tags["miss_engine_s"] == 0  # the memory engine has none
    theirs = sum(1 for who, _ in seen if who == reader.ident)
    assert theirs > sum(s.tags["misses"] for s in builds)
    # the registry's totals are the sum over threads, names as before
    samples = storages.nodestore_samples()
    total = sum(v for name, _, labels, v in samples
                if name == "khipu_nodestore_reads_total"
                and labels["from"] != "cache")
    assert total == len(seen)
    by_thread = [storages.thread_misses(t) for t in {w for w, _ in seen}]
    assert sum(row[0] for row in by_thread) == total
    seconds = sum(v for name, _, _, v in samples
                  if name == "khipu_nodestore_source_seconds_total")
    assert seconds == pytest.approx(
        sum(row[1] for row in by_thread), abs=1e-5)
    waits = [v for name, _, _, v in samples
             if name == "khipu_nodestore_lock_wait_seconds_total"]
    assert len(waits) == 3 and 0 < sum(waits) < seconds
    absent = sum(v for name, _, labels, v in samples
                 if name == "khipu_nodestore_reads_total"
                 and labels["from"] == "absent")
    assert absent >= theirs  # every key the other thread asked for


def test_a_get_that_waits_for_the_engines_lock_books_it_as_wait(tmp_path):
    store = KesqueStore(str(tmp_path), "account", content_addressed=True)
    from khipu_tpu.base.crypto.keccak import keccak256

    value = b"node" * 20
    store.append_batch([], {keccak256(value): value})
    assert store.append_lock_held_seconds > 0
    assert store.get(keccak256(value)) == value
    gets, wait, read = store.read_book(threading.get_ident())
    assert gets == 1 and wait < 0.1 and 0 < read < 0.1
    held = threading.Event()

    def hold():
        with store._lock:
            held.set()
            time.sleep(0.2)

    holder = threading.Thread(target=hold)
    holder.start()
    held.wait()
    assert store.get(keccak256(value)) == value
    holder.join()
    gets, wait2, read2 = store.read_book(threading.get_ident())
    assert gets == 2 and wait2 - wait > 0.15 and read2 - read < 0.1
    assert store.read_book(holder.ident) == [0, 0.0, 0.0]
    assert store.read_book() == [gets, wait2, read2]  # over all threads
    assert store.get(b"\x00" * 32) is None  # absent: counted all the same
    assert store.read_book()[0] == 3


def test_the_kesque_engine_tells_a_miss_from_inside(tmp_path, token_chain):
    """On Kesque a miss has an engine part, the split stays inside the
    whole, and the collector exports it by topic."""
    storages = Storages(engine="kesque", data_dir=str(tmp_path),
                        cache_size=1)
    _, _, spans = replay(*token_chain, storages=storages)
    builds = named(spans, "window.build")
    assert sum(s.tags["misses"] for s in builds) > 0
    for s in builds:
        assert s.tags["miss_wait_s"] + s.tags["miss_engine_s"] \
            <= s.tags["miss_s"]
    assert sum(s.tags["miss_engine_s"] for s in builds) > 0
    samples = storages.kesque_engine._registry_samples()
    by_name = {}
    for name, kind, labels, v in samples:
        if name.startswith(("khipu_kesque_get_", "khipu_kesque_append_lock")):
            assert kind == "counter" and set(labels) == {"topic"}
            by_name.setdefault(name, {})[labels["topic"]] = v
    assert set(by_name) == {
        "khipu_kesque_get_total", "khipu_kesque_get_lock_wait_seconds_total",
        "khipu_kesque_get_read_seconds_total",
        "khipu_kesque_append_lock_held_seconds_total"}
    assert by_name["khipu_kesque_get_total"]["account"] > 0
    assert by_name["khipu_kesque_get_read_seconds_total"]["account"] > 0
    assert by_name["khipu_kesque_append_lock_held_seconds_total"][
        "account"] > 0
    storages.stop()


def test_with_the_tracer_off_no_book_is_read(token_chain, monkeypatch):
    assert not tracer.enabled
    assert span("commit", block=1) is _NULL_SPAN
    assert span("window.build", block=1).token is None

    def never(self, ident):
        raise AssertionError("a thread's book was read with the ring off")

    monkeypatch.setattr(Storages, "thread_misses", never)
    stats, chain, spans = replay(*token_chain, traced=False)
    assert stats.blocks == 4 and spans == []
    assert chain.storages.account_node_storage.source_reads > 0  # counted


def test_thread_books_keep_each_threads_own():
    books = ThreadBooks(0, 0.0)
    books.mine()[0] += 2

    def add():
        book = books.mine()
        book[0] += 5
        book[1] += 0.5

    t = threading.Thread(target=add)
    t.start()
    t.join()
    assert books.of(threading.get_ident()) == [2, 0.0]
    assert books.of(t.ident) == [5, 0.5]
    assert books.of() == [7, 0.5] and books.of(-1) == [0, 0.0]
    books.of(t.ident)[0] = 99  # a copy: the book is its thread's alone
    assert books.of() == [7, 0.5]


def test_thread_books_lose_no_add_under_a_short_switch_interval():
    """More writers than cores, each on its own book, a reader summing
    all the while: the total is every add made."""
    import os
    import sys

    books = ThreadBooks(0, 0.0)
    writers, adds = 2 * (os.cpu_count() or 4) + 1, 20_000
    start = threading.Barrier(writers + 1)
    sums = []

    def write():
        start.wait(timeout=30)
        for _ in range(adds):
            book = books.mine()
            book[0] += 1
            book[1] += 0.5

    def read():
        start.wait(timeout=30)
        while any(t.is_alive() for t in threads):
            sums.append(books.of()[0])

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=write) for _ in range(writers)]
        reader = threading.Thread(target=read)
        for t in threads + [reader]:
            t.start()
        for t in threads + [reader]:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
    assert books.of() == [writers * adds, 0.5 * writers * adds]
    assert sums == sorted(sums) and sums[-1] <= writers * adds
    # all alive at the barrier, so no ident was handed on: a book each
    assert len(books._books) == writers
