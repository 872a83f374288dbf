"""Driver ``fastsync``: a whole fast sync through the node's normal path.

A node assembled as an operator's is (``ServiceBoard`` on Kesque,
``start_network``, ``start_fast_sync``) downloads the seed's state
(``generators/state.py``: ``sync.deep``'s genesis, account trie, storage
tries and code) from ``peers`` peers over RLPx on loopback
(``generators/fullstate.py``: each the program's own serving stack in a
child process without JAX). All the work is ``FastSyncService.run()``:
it chooses the pivot from its peers, downloads state nodes, storage
nodes and code into the store, admits every verified trie node to the
node's device mirror (``SyncConfig.fast_sync_mirror_rows``, built by the
board), closes with the mirror's flush and the device verify over
everything resident, and backfills block data to the pivot. This file
hands the program its configuration and reads its counters, spans and
stores afterwards; it stands between the service and nothing.

The window is a resumed sync. Untimed set-up runs the same service
against the same peers until all but ``resume_remaining_nodes`` of the
state's nodes are stored and resident, then stops it as a node's
shutdown does (``FastSyncService.stop``: the batch in hand is stored,
the checkpoint written, ``run`` raises ``SyncStopped``), flushes the
mirror and runs the device verify over the resumed part (which also
compiles the closing pass's programs, so nothing compiles in the
window). The window is then one ``run()`` of a new service of the same
board, from pivot choice to the backfill's end; it ends by completion.

Artefacts handed to the readers: ``window`` (perf_counter at its two
ends), ``nodes`` (nodes of all three kinds stored in the window),
``windows`` (1: the window is one ``run()``, so ``span_ms`` per window is
per run), ``registry`` (the process registry's snapshot at the window's
opening and close; the window's service is built before the first, so
the ``khipu_fastsync_*`` families of its syncer start from zero and the
peer pool's process-wide counters are differenced), ``spans`` and
``spans_dropped`` (the program's span ring over the window; empty
unless ``--trace 1``), ``resident_bytes`` (the resident nodes' rows and
claims, what the closing verify has to read), ``trace``, and for the
log: ``requests_by_peer``, ``forged_sent``, ``by_kind``.

Controls (``--control``; ``correct`` must come out false):
``no-batch-check`` — the node trusts its peers: the pool files a blob
under the hash it was asked for and the syncer's batch check is told
the same, so forged blobs reach the parser, the store and the mirror;
``lost-code`` — one code blob is deleted from the store after the
window, so an account the sync claims complete cannot serve its code.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import threading
import time
from typing import Dict, List

import numpy as np

from benchmark.drivers.statesync import resident_bytes
from benchmark.drivers.sync import GAS_LIMIT, _key
from benchmark.generators import fullstate as gen
from benchmark.generators import state as gen_state
from benchmark.generators.accounts import PLAIN_BALANCE_BASE, SENDER_BALANCE
from benchmark.lib.outcome import Check, Outcome
from benchmark.reference import state_trie as ref
from benchmark.reference.keccak import keccak256_batch

STORES = ("account_node_storage", "storage_node_storage", "evmcode_storage")


# ------------------------------------------------------------ reference


def balance_slots(holders: List[bytes]) -> List[int]:
    """``generators/state.py``'s slot of each holder's balance, through
    the benchmark's own Keccak."""
    keys = keccak256_batch([h.rjust(32, b"\x00") + bytes(32) for h in holders])
    return [int.from_bytes(k, "big") for k in keys]


def reference_contracts(data: Dict, ranks: List[int]) -> Dict[int, Dict]:
    """For each sampled 0-based rank index: the contract's storage root
    by ``reference/state_trie.py`` and its storage as {slot: value}."""
    out = {}
    for r in ranks:
        who = data["holders"][r].tolist()
        slots = balance_slots([data["others"][i] for i in who])
        storage = dict(zip(slots, data["holdings"][r].tolist()))
        out[r] = {"root": ref.storage_trie(storage)[0], "storage": storage}
    return out


def sample_ranks(n_contracts: int, n: int, rng) -> List[int]:
    """Rank 1 (index 0) and seeded picks over all the other ranks."""
    rest = rng.choice(np.arange(1, n_contracts), min(n, n_contracts) - 1,
                      replace=False)
    return [0] + sorted(rest.tolist())


# ------------------------------------------------------------- the node


def node_config(data_dir: str, sizes: Dict, rows: Dict[int, int]):
    from khipu_tpu.config import DbConfig, SyncConfig, fixture_config

    return dataclasses.replace(
        fixture_config(chain_id=1),
        db=DbConfig(engine="kesque", data_dir=data_dir),
        sync=SyncConfig(
            nodes_per_request=int(sizes["nodes_per_request"]),
            min_peers_to_choose_pivot=int(sizes["min_peers_to_choose_pivot"]),
            pivot_block_offset=int(sizes["pivot_block_offset"]),
            peer_request_timeout=float(sizes["peer_request_timeout"]),
            fast_sync_mirror_rows=tuple(sorted(rows.items()))))


def boot_node(env, node_dir: str, cfg, genesis, peers: gen.Peers):
    """A node that knows the chain's genesis block and nothing of its
    state (as one started from a chain spec does), connected to every
    peer."""
    from khipu_tpu.domain.block import Block
    from khipu_tpu.domain.blockchain import Blockchain
    from khipu_tpu.service_board import ServiceBoard
    from khipu_tpu.storage.storages import Storages

    storages = Storages(engine="kesque", data_dir=node_dir)
    Blockchain(storages, cfg).save_block(Block(genesis), [],
                                         genesis.difficulty)
    storages.stop()
    board = ServiceBoard(cfg)
    if board.blockchain.get_header_by_number(0).hash != genesis.hash:
        raise RuntimeError("node did not reopen the seed's genesis block")
    board.start_network(port=0)
    for hello in peers.hellos:
        board.peer_manager.connect("127.0.0.1", hello["port"],
                                   bytes.fromhex(hello["pub"]))
    env.log(f"node: up, {len(board.peer_manager.peers)} peers connected")
    return board


def first_run(env, board, stop_at: int) -> Dict[str, int]:
    """Set-up's sync: the service's own ``run()``, stopped as a shutdown
    stops it once ``stop_at`` nodes are stored. Returns what it stored,
    by kind."""
    from khipu_tpu.sync.fast_sync import SyncStopped

    svc = board.start_fast_sync()
    stats = svc.syncer.stats
    over = threading.Event()

    def watch():
        while not over.wait(0.001):
            if sum(stats.nodes.values()) >= stop_at:
                svc.stop()
                return

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    t0 = time.perf_counter()
    try:
        svc.run()
    except SyncStopped as e:
        env.log(f"resume: first run {e} ({time.perf_counter() - t0:.1f} s)")
    else:
        raise RuntimeError("set-up's sync ran to completion: "
                           "resume_remaining_nodes exceeds the state")
    finally:
        over.set()
        watcher.join()
    return dict(stats.nodes)


@contextlib.contextmanager
def trusting_node(svc):
    """Control ``no-batch-check``, for as long as the block lasts: a
    blob is filed under the hash that was asked for if un-forging it
    gives that hash, in the pool and in the syncer's check alike."""
    from khipu_tpu.sync import fast_sync, fast_sync_service

    asked = set()
    honest = fast_sync.keccak256
    inner = svc.syncer.fetch

    def fetch(hashes):
        asked.clear()
        asked.update(hashes)
        return inner(hashes)

    def trusting(blob):
        digest = honest(blob)
        if digest not in asked:
            claimed = honest(gen.forge(blob))
            if claimed in asked:
                return claimed
        return digest

    honest_batch = fast_sync_service.keccak256_batch
    svc.syncer.fetch = fetch
    fast_sync.keccak256 = trusting
    fast_sync_service.keccak256_batch = lambda blobs: list(map(trusting, blobs))
    try:
        yield
    finally:
        svc.syncer.fetch = inner
        fast_sync.keccak256 = honest
        fast_sync_service.keccak256_batch = honest_batch


def programs_compiled(mirror) -> int:
    """Compiled shapes the mirror's jitted programs hold, where JAX
    says; read before and after the window."""
    total = 0
    for cm in mirror._classes.values():
        for fn in (cm._run, cm._set_tile, cm._verify):
            size = getattr(fn, "_cache_size", None)
            total += size() if size else 0
    return total


# --------------------------------------------------------------- window


def run(env) -> Outcome:
    try:
        from khipu_tpu.sync.fast_sync import SyncStopped  # noqa: F401
    except ImportError:
        # fail at once, before any seed data: no result line. In a fresh
        # checkout the import above started gcc on the RLP extension in
        # a daemon thread (base/rlp.py); leaving now would orphan that
        # compiler, so wait for it behind the build's own lock
        from khipu_tpu.native.build import load_rlp_ext

        load_rlp_ext()
        raise SystemExit(
            "this program's FastSyncService takes no mirror and cannot be "
            "stopped and resumed: it cannot run fastsync-fullstate")
    conf, traffic = env.config, env.traffic
    sizes = conf["sizes"]
    state_sizes = {k: sizes[k] for k in gen.STATE_KEYS}
    # the seed's genesis is the one `state_of`'s cells replay over, kept
    # where their driver keeps it
    tag = os.path.basename(env.cache_dir)
    seed_dir = os.path.join(
        os.path.dirname(os.path.dirname(env.cache_dir)), conf["state_of"],
        tag, f"{env.seed}-{_key(state_sizes)}")
    os.makedirs(seed_dir, exist_ok=True)
    genesis_dir = os.path.join(seed_dir, "genesis")
    builder = gen.start_genesis_builder(
        state_sizes, env.seed, GAS_LIMIT, genesis_dir,
        os.path.join(seed_dir, "genesis.ok"))
    if builder is not None:
        env.log("seed: building genesis in a child")
    # whatever the run started is stopped however it ends: the node
    # first, then its peers, then a builder still at work
    with contextlib.ExitStack() as started:
        if builder is not None:
            started.callback(builder.wait)
            started.callback(builder.kill)
        return _run(env, conf, traffic, state_sizes, genesis_dir, builder,
                    started)


def _run(env, conf, traffic, state_sizes, genesis_dir, builder,
         started) -> Outcome:
    import jax
    import jax.numpy as jnp

    from khipu_tpu.domain.block_header import BlockHeader
    from khipu_tpu.observability.registry import REGISTRY
    from khipu_tpu.observability.trace import tracer as program_tracer
    from khipu_tpu.sync.fast_sync import FastSyncStateStorage, SyncStopped

    sizes = conf["sizes"]
    n_peers = int(sizes["peers"])
    rows = {int(nb): int(n) for nb, n in sizes["mirror_rows"].items()}
    rng = np.random.default_rng([env.seed, 0x66756C6C])
    t0 = time.perf_counter()
    data = gen_state.make_state(state_sizes, env.seed)
    del data["alloc"]  # the peers read the dir; the checks use the draws
    ranks = sample_ranks(len(data["tokens"]), int(traffic["sample_contracts"]),
                         rng)
    contracts = reference_contracts(data, ranks)
    code_hashes = keccak256_batch([
        gen_state.token_code(r + 1) for r in range(len(data["tokens"]))])
    env.log(f"seed: state drawn, reference storage roots of "
            f"{len(ranks)} contracts "
            f"({sum(len(c['storage']) for c in contracts.values())} slots) "
            f"and {len(code_hashes)} code hashes in "
            f"{time.perf_counter() - t0:.1f} s")
    if builder is not None:
        env.log("seed: waiting for the genesis builder")
        if builder.wait() != 0:
            raise RuntimeError(f"genesis builder exited {builder.returncode}")

    bests = gen.heights(int(sizes["chain_blocks"]), n_peers)
    t0 = time.perf_counter()
    peers = gen.Peers.start(genesis_dir, env.run_dir, env.seed, bests,
                            int(traffic["forge_one_in"]))
    started.callback(peers.stop)
    counted = peers.hellos[0]["nodes"]
    total = sum(counted.values())
    env.log(f"peers: {n_peers} up in {time.perf_counter() - t0:.1f} s, bests "
            f"{bests}; the state has {total} nodes {counted}")
    genesis = BlockHeader.decode(bytes.fromhex(peers.hellos[0]["genesis"]))
    chain = gen.empty_chain(genesis, max(bests))
    pivot_n = gen.pivot_number(bests, int(sizes["pivot_block_offset"]))

    cfg = node_config(os.path.join(env.run_dir, "node"), sizes, rows)
    board = boot_node(env, cfg.db.data_dir, cfg, genesis, peers)
    started.callback(board.shutdown)
    storages, mirror = board.storages, board.fast_sync_mirror
    checkpoints = FastSyncStateStorage(storages.app_state.source)
    remaining = int(traffic["resume_remaining_nodes"])
    if not 0 < remaining < total:
        raise RuntimeError(f"resume_remaining_nodes {remaining} is not "
                           f"inside the state's {total} nodes")
    before = first_run(env, board, total - remaining)
    mirror.flush()
    bad0, resident0 = mirror.verify(), mirror.resident_count
    resumed = checkpoints.get_sync_state()
    env.log(f"resume: {sum(before.values())} nodes stored {before}, "
            f"{resident0} resident, checkpoint at "
            f"{resumed.downloaded_nodes if resumed else None} with "
            f"{len(resumed.pending) if resumed else 0} pending")
    asked0 = [row["requests"] for row in peers.report()]

    svc = board.start_fast_sync(log=env.log)
    gc.collect()
    tw = env.trace_window() if env.trace else None
    if tw:
        # the program's span ring, sized to hold the whole window: a
        # batch opens some twenty spans (a request a peer among them)
        batches = remaining // (int(sizes["nodes_per_request"]) * n_peers)
        program_tracer.enable(capacity=max(
            program_tracer.DEFAULT_CAPACITY, 40 * batches + 8192))
        program_tracer.reset()
        tw.start()
    compiled0 = programs_compiled(mirror)
    control = (trusting_node(svc) if env.control == "no-batch-check"
               else contextlib.nullcontext())
    # ------------------------------------------------------ the window
    reg_open = REGISTRY.snapshot()
    env.log("window: open")
    setup_s = time.perf_counter() - env.t_proc0
    t_open = time.perf_counter()
    final, aborted = None, None
    # the window ends by completion; a program too slow to complete in
    # four times --seconds (two minutes at least) is stopped, and the
    # checks say "not complete"
    limit = max(4 * env.seconds, 120.0)
    too_long = threading.Timer(limit, svc.stop)
    too_long.daemon = True
    too_long.start()
    with control:
        try:
            final = svc.run()
        except SyncStopped as e:
            aborted = f"not complete after {limit:.0f} s: {e}"
        except Exception as e:
            if not env.control:
                raise
            # the control fed the loop forged nodes: whatever it trips
            # over, the checks after the window have to say "not correct"
            aborted = f"{type(e).__name__}: {str(e)[:120]}"
    t_close = time.perf_counter()
    too_long.cancel()
    reg_close = REGISTRY.snapshot()
    spans, spans_dropped = [], 0
    if tw:
        tw.stop()
        program_tracer.disable()
        spans, spans_dropped = program_tracer.snapshot(), program_tracer.dropped
        env.log(f"span ring: {len(spans)} kept, {spans_dropped} dropped")
    stats = svc.syncer.stats
    by_kind = dict(stats.nodes)
    stored = sum(by_kind.values())
    window_s = t_close - t_open
    reports = peers.report()
    asked = [row["requests"] - a for row, a in zip(reports, asked0)]
    forged = {}
    for row in reports:
        forged.update(row["forged"])
    env.log(f"window: closed after {window_s:.3f} s, {stored} nodes stored "
            f"{by_kind}, {stats.batches} batches, {stats.retried} retried, "
            f"{stats.rejected} rejected, requests by peer {asked}, "
            f"{len(forged)} forged answers since set-up began"
            + (f"; sync aborted: {aborted}" if aborted else ""))
    env.log("window: phases " + " ".join(
        f"{k}={v:.3f}" for k, v in stats.phases.items()))

    # ------------------------------------ after the window: the checks
    t_checks = time.perf_counter()
    stores = [getattr(storages, name) for name in STORES]
    if env.control == "lost-code":
        lost = code_hashes[ranks[-1]]
        # underneath the node source, which swallows removes
        storages.evmcode_storage.source._store.append_batch([lost], {})
        storages.evmcode_storage._cache.remove(lost)
        env.log(f"control: code {lost.hex()[:16]} deleted from the store")
    chain_checks = backfill_checks(board.blockchain, chain, pivot_n, genesis)
    left = checkpoints.get_sync_state()
    checks = [
        Check("resumed_nodes_not_resident", abs(
            before["state"] + before["storage"] - resident0), 0),
        Check("resumed_nodes_failing_device_verify", bad0, 0),
        Check("resume_checkpoint_not_at_the_first_runs_end", int(
            resumed is None
            or resumed.downloaded_nodes != sum(before.values())), 0),
        Check("sync_did_not_complete", int(final is None), 0),
        Check("downloaded_nodes_minus_the_states", abs(
            (final.downloaded_nodes if final else 0) - total), 0),
        Check("checkpoint_left_behind", int(
            left is not None or not storages.app_state.fast_sync_done), 0),
        Check("pivot_not_median_less_offset", int(
            board.blockchain.best_block_number != pivot_n), 0),
        *chain_checks,
        Check("peers_not_asked_in_the_window", sum(a == 0 for a in asked), 0),
        Check("programs_compiled_in_the_window",
              programs_compiled(mirror) - compiled0, 0),
    ]
    checks += [
        Check(f"{kind}_nodes_stored_minus_counted",
              abs(before[kind] + by_kind[kind] - int(counted[kind])), 0)
        for kind in gen.KINDS]
    checks += [
        Check("forged_values_stored", sum(
            1 for h, v in forged.items()
            if any(s.get(h) == v for s in stores)), 0),
        Check("forged_values_resident", sum(
            1 for h, v in forged.items() if mirror.get(h) == v), 0),
        Check("trie_nodes_not_resident", abs(
            mirror.resident_count - int(counted["state"])
            - int(counted["storage"])), 0),
        Check("device_verify_mismatches", mirror.verify(), 0),
    ]
    # one forged claim planted after the window counts exactly 1
    cm = next(iter(mirror._classes.values()))
    poisoned = cm.claimed.at[0, 0, 0, 0].add(jnp.uint32(1))
    planted = int(jax.device_get(cm._verify(cm.resident, poisoned)))
    checks.append(Check("planted_forgery_miscounted", abs(planted - 1), 0))
    try:
        checks += state_checks(board.blockchain, genesis.state_root, data,
                               contracts, code_hashes, traffic, rng)
    except Exception as e:  # a node of the state is missing or unreadable
        env.log(f"checks: the node's read path raised {type(e).__name__}: "
                f"{str(e)[:120]}")
        checks.append(Check("state_reads_raised", 1, 0))
    checks += rehash_checks(stores, int(traffic["sample"]), rng)
    if stored == 0:
        checks.append(Check("nothing_synced", 1, 0))
    env.log(f"checks: {time.perf_counter() - t_checks:.1f} s after the "
            "window")

    e2e = {"setup_s": setup_s, "snap_nodes_per_s": stored / window_s}
    art = {
        "window": (t_open, t_close), "window_s": window_s, "nodes": stored,
        "windows": 1, "registry": (reg_open, reg_close),
        "spans": [s for s in spans if s.t1 > t_open and s.t0 < t_close],
        "spans_dropped": spans_dropped, "trace": tw,
        "resident_bytes": resident_bytes(mirror), "by_kind": by_kind,
        "requests_by_peer": asked, "forged_sent": len(forged),
        "complete": final is not None,
    }
    # every hash asked for was stored or went back to the queue: what is
    # left over failed
    failed = stats.requested - stored - stats.retried if not env.control else 0
    return Outcome(e2e, checks, stats.requested, max(0, failed), art)


# ---------------------------------------------------------- the checks


def backfill_checks(blockchain, chain: List, pivot_n: int,
                    genesis) -> List[Check]:
    """Headers, bodies and receipts 1..pivot are stored, are the peers'
    chain and link back to the genesis block."""
    s = blockchain.storages
    missing = unlinked = 0
    parent = genesis.hash
    for header in chain[:pivot_n]:
        n = header.number
        got = blockchain.get_header_by_number(n)
        if (got is None or s.block_body_storage.get(n) is None
                or s.receipts_storage.get(n) is None):
            missing += 1
            continue
        if got.hash != header.hash or got.parent_hash != parent:
            unlinked += 1
        parent = got.hash
    return [Check("backfilled_blocks_missing", missing, 0),
            Check("backfilled_blocks_not_chain_linked", unlinked, 0)]


def state_checks(blockchain, root: bytes, data: Dict, contracts: Dict,
                 code_hashes: List[bytes], traffic: Dict, rng) -> List[Check]:
    """What the synced node serves through its normal read path against
    the seeded alloc and the reference's roots; each equality exact."""
    world = blockchain.get_world_state(root)
    tokens, others = data["tokens"], data["others"]
    # the sampled contracts' storage roots; every contract's code
    bad_roots = bad_codes = 0
    for r, want in contracts.items():
        acc = world.get_account(tokens[r])
        bad_roots += acc is None or acc.storage_root != want["root"]
    for r, token in enumerate(tokens):
        acc = world.get_account(token)
        served = keccak256_batch([world.get_code(token)])[0]
        bad_codes += (acc is None or acc.code_hash != code_hashes[r]
                      or served != code_hashes[r])
    # sampled accounts: plain ones, every funded sender, every contract
    n = min(int(traffic["sample"]), len(others))
    bad_accounts = 0
    extra = data["extra"].tolist()
    for i in rng.choice(len(others), n, replace=False).tolist():
        acc = world.get_account(others[i])
        bad_accounts += acc is None or (
            acc.nonce, acc.balance, acc.storage_root, acc.code_hash) != (
            0, PLAIN_BALANCE_BASE + extra[i], ref.EMPTY_ROOT,
            ref.EMPTY_CODE_HASH)
    for addr in data["senders"]:
        acc = world.get_account(addr)
        bad_accounts += acc is None or (acc.nonce, acc.balance) != (
            0, SENDER_BALANCE)
    # sampled slots of the sampled contracts, by each contract's share
    bad_slots = 0
    sizes_ = np.array([len(c["storage"]) for c in contracts.values()])
    share = np.maximum(1, int(traffic["sample"]) * sizes_ // sizes_.sum())
    for (r, want), k in zip(contracts.items(), share.tolist()):
        slots = list(want["storage"])
        for j in rng.choice(len(slots), min(k, len(slots)),
                            replace=False).tolist():
            bad_slots += (world.get_storage(tokens[r], slots[j])
                          != want["storage"][slots[j]])
    return [
        Check("reference_storage_root_mismatches_of_%d" % len(contracts),
              bad_roots, 0),
        Check("code_hash_mismatches_of_%d" % len(tokens), bad_codes, 0),
        Check("sampled_account_mismatches", bad_accounts, 0),
        Check("sampled_slot_mismatches", bad_slots, 0),
    ]


def rehash_checks(stores: List, sample: int, rng) -> List[Check]:
    """A seeded sample of stored values of all three kinds, re-hashed by
    the benchmark's own Keccak against the keys they are stored under."""
    keys: List[bytes] = []
    total = max(1, sum(s.source.count for s in stores))
    for store in stores:
        have = store.source.keys()
        take = max(1, sample * len(have) // total)
        keys += [have[int(i)] for i in rng.choice(
            len(have), min(take, len(have)), replace=False)] if have else []
    values = [next((v for v in (s.get(h) for s in stores) if v is not None),
                   None) for h in keys]
    digests = keccak256_batch([v or b"" for v in values])
    return [
        Check("sampled_values_missing_from_store",
              sum(v is None for v in values), 0),
        Check("sampled_rehash_mismatches_of_%d" % len(keys), sum(
            d != h for d, h, v in zip(digests, keys, values)
            if v is not None), 0),
    ]
