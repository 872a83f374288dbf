"""Driver ``sync_contracts``: ``drivers/sync_state.py``'s run over
``fullsync-postmerge-contracts``: the deep deployment's state with
mainnet-shaped token and pair bytecode (``generators/contracts.py``),
blocks whose calls run it (``generators/chain_contracts.py``), and the
closing reads (balances, storage words, receipts with their logs and
blooms, ``eth_getLogs``) held to ``reference/ledger_contracts.py``.

``send``, ``node_config``, ``rpc``, ``StatsTap``, ``rest_of_chain`` and
``FALLBACK_COUNTERS`` are ``drivers/sync.py``'s; ``log_metrics_of`` and
``log_node_reads`` are ``drivers/sync_state.py``'s. Both of those
drivers' ``run`` call their own ``seed_data`` and ``ledger_checks`` by
module global, so the window loop stands here a third time, the same
(same window rule, same checks, same artefact keys, ``registry``
included, same ``--control wrong-root``) but for ``--control
wrong-log`` and for what the closing checks are handed: PERF.md
section 7 asks the next ``benchmark`` issue for one loop under all three.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
from typing import Dict, List

import numpy as np

from benchmark.drivers.sync import (
    FALLBACK_COUNTERS,
    GAS_LIMIT,
    StatsTap,
    _key,
    node_config,
    rest_of_chain,
    rpc,
    send,
)
from benchmark.drivers.sync_state import log_metrics_of, log_node_reads
from benchmark.generators import accounts as gen_accounts
from benchmark.generators import chain_contracts as gen_chain
from benchmark.generators import contracts as gen_contracts
from benchmark.lib.outcome import Check, Outcome
from benchmark.lib.tracewin import annotate
from benchmark.reference import ledger_contracts as ref

CONTROLS = {"wrong-root": "state_root", "wrong-log": "logs_bloom"}
EXEC_FAMILIES = ("khipu_exec_lane_txs_total", "khipu_exec_lane_seconds_total")


# ------------------------------------------------------------ seed data


def seed_data(env, sizes: Dict, traffic: Dict) -> Dict:
    """State, genesis data dir and chain for (configuration, traffic,
    seed), as ``sync_state.seed_data`` makes and keeps them: dir and
    chain built on first use under ``benchmark/cache/``, a new seed's
    chain by a child process beside this one's genesis build."""
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.storage.storages import Storages

    txs = int(sizes["txs_per_block"])
    blocks = int(traffic["chain_blocks"])
    t0 = time.perf_counter()
    data = gen_contracts.make_state(sizes, env.seed)
    env.log(f"seed: state of {len(data['alloc'])} accounts, "
            f"{len(data['tokens'])} tokens, {len(data['pairs'])} pairs, "
            f"{sum(len(data['alloc'][t].storage) for t in data['tokens'])} "
            f"token slots drawn in {time.perf_counter() - t0:.1f} s")
    data["picks"] = gen_chain.draw(
        traffic["params"], blocks, txs, len(data["others"]),
        data["holders"], len(data["pairs"]), env.seed)
    seed_dir = os.path.join(env.cache_dir, f"{env.seed}-{_key(sizes)}")
    os.makedirs(seed_dir, exist_ok=True)
    chain_file = os.path.join(
        seed_dir,
        f"chain-{_key([traffic['generator'], traffic['params'], blocks, txs])}"
        ".npz")
    head_file = chain_file[:-4] + ".head.npz"
    data.update(genesis_dir=os.path.join(seed_dir, "genesis"),
                chain_file=chain_file, builder=None)
    if not os.path.exists(chain_file):
        if os.path.exists(head_file):
            os.remove(head_file)
        env.log(f"seed: building chain ({blocks} blocks x {txs} tx) "
                "in a child")
        data["builder"] = subprocess.Popen(
            [sys.executable, os.path.abspath(gen_chain.__file__), json.dumps({
                "sizes": sizes, "blocks": blocks, "seed": env.seed,
                "params": traffic["params"], "gas_limit": GAS_LIMIT,
                "head_blocks": int(sizes["batch_blocks"]),
                "head_out": head_file, "out": chain_file})],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdout=subprocess.DEVNULL)
    try:
        if not os.path.exists(os.path.join(seed_dir, "genesis.ok")):
            env.log("seed: building genesis")
            t0 = time.perf_counter()
            shutil.rmtree(data["genesis_dir"], ignore_errors=True)
            os.makedirs(data["genesis_dir"])
            storages = Storages(engine="kesque", data_dir=data["genesis_dir"])
            Blockchain(storages, fixture_config(chain_id=1)).load_genesis(
                GenesisSpec(alloc=data["alloc"], gas_limit=GAS_LIMIT))
            storages.stop()  # flushes and closes: the node reopens a copy
            open(os.path.join(seed_dir, "genesis.ok"), "w").close()
            size = sum(os.path.getsize(os.path.join(d, f))
                       for d, _, files in os.walk(data["genesis_dir"])
                       for f in files)
            env.log("seed: genesis built in "
                    f"{time.perf_counter() - t0:.1f} s, "
                    f"{size / 1e6:.1f} MB on disk")
        del data["alloc"]  # the node reads the dir; the child drew its own
        if data["builder"] is None:
            data["wire"], data["roots"], _ = gen_chain.load(chain_file)
            return data
        while not os.path.exists(head_file):
            if data["builder"].poll() is not None:
                raise RuntimeError("chain builder exited "
                                   f"{data['builder'].returncode}")
            time.sleep(0.2)
        data["wire"], data["roots"], _ = gen_chain.load(head_file)
        data["token"] = b""  # rest_of_chain's third field
        return data
    except BaseException:  # no child outlives a set-up that failed
        if data["builder"] is not None:
            data["builder"].kill()
            data["builder"].wait()
        raise


# --------------------------------------------------------------- window


def forged(block, field: str):
    """``block`` with one bit of one header field flipped, its other
    fields kept: what the two controls send."""
    h = block.header
    value = getattr(h, field)
    return dataclasses.replace(block, header=dataclasses.replace(
        h, **{field: bytes([value[0] ^ 1]) + value[1:]}))


def run(env) -> Outcome:
    from khipu_tpu.bridge import BridgeClient
    from khipu_tpu.domain.block import Block
    from khipu_tpu.evm import native_vm
    from khipu_tpu.native import keccak as native_keccak
    from khipu_tpu.observability.recorder import compile_log
    from khipu_tpu.observability.registry import REGISTRY
    from khipu_tpu.service_board import ServiceBoard

    if env.control not in (None, *CONTROLS):
        raise SystemExit(f"unknown control {env.control!r}; this driver "
                         f"has {sorted(CONTROLS)}")
    conf, traffic = env.config, env.traffic
    sizes, program = conf["sizes"], conf["program"]
    batch = int(sizes["batch_blocks"])
    warm = int(traffic["warmup_blocks"])
    data = seed_data(env, sizes, traffic)
    board = client = None
    try:
        roots = data["roots"]
        blocks = [Block.decode(w) for w in data["wire"]]
        node_dir = os.path.join(env.run_dir, "node")
        shutil.copytree(data["genesis_dir"], node_dir)
        cfg = node_config(node_dir, program, observe=env.trace)
        env.log("node: boot")
        board = ServiceBoard(cfg)
        genesis = board.blockchain.get_header_by_number(0)
        if genesis is None or genesis.hash != blocks[0].header.parent_hash:
            raise RuntimeError("node did not reopen the seed's genesis")
        reg = REGISTRY.snapshot()
        at_boot = {k: reg.get(k, 0) for k in FALLBACK_COUNTERS}
        bridge_port = board.start_bridge(port=0, **program["bridge"])
        rpc_port = board.start_rpc(port=0)
        client = BridgeClient(f"127.0.0.1:{bridge_port}")
        failures: List[str] = []

        # warm-up: at least `warmup_blocks`, then on while the last
        # batch still compiled a fused signature, up to `warmup_max_blocks`
        warm_max = int(traffic.get("warmup_max_blocks", warm))
        lo = 0
        while lo < warm_max:
            if lo >= len(blocks):  # a new seed: the head is used up
                rest_of_chain(env, data)
                roots = data["roots"]
                blocks = [Block.decode(w) for w in data["wire"]]
            misses = compile_log.snapshot()["misses"]
            send(client, blocks, roots, lo, min(lo + batch, warm_max),
                 failures)
            lo = min(lo + batch, warm_max)
            quiet = compile_log.snapshot()["misses"] == misses
            env.log(f"warm-up: {lo} blocks, last batch "
                    f"{'quiet' if quiet else 'compiled'}")
            if failures or (lo >= warm and quiet):
                break
        warm = lo
        bad_headers = [i + 1 for i, b in enumerate(blocks)
                       if b.header.state_root != roots[i]]
        sent_blocks = blocks
        if env.control:
            # a control: the last header of the window's first batch
            # claims a state root, or a logs bloom, that no honest
            # execution reaches (the last, so that no later block of the
            # batch gives the forgery away by its parent hash first); a
            # node that checks both must refuse the block, and `correct`
            # must come out false
            at = min(warm + batch, len(blocks)) - 1
            sent_blocks = list(blocks)
            sent_blocks[at] = forged(blocks[at], CONTROLS[env.control])
            env.log(f"control: block {at + 1} carries a forged "
                    f"{CONTROLS[env.control]}")
        if failures:
            raise RuntimeError(f"warm-up failed: {failures[:3]}")
        tap = StatsTap(board._bridge_server)
        tw = env.trace_window() if env.trace else None
        if tw:
            tw.start()

        # ------------------------------------------------ the window
        reg_open = REGISTRY.snapshot()
        env.log("window: open")
        setup_s = time.perf_counter() - env.t_proc0
        wall_open = time.time()
        t_open = time.perf_counter()
        sent = good = 0
        idx = warm
        now = t_open
        batch_s = []
        while idx < len(blocks):
            hi = min(idx + batch, len(blocks))
            with (annotate("bench.execute_blocks", first=idx + 1)
                  if tw and tw.running else contextlib.nullcontext()):
                good += send(client, sent_blocks, roots, idx, hi, failures)
            batch_s.append(time.perf_counter() - now)
            now = time.perf_counter()
            sent += hi - idx
            idx = hi
            if tw and tw.running and \
                    now - t_open >= float(traffic.get("trace_seconds", 8)):
                tw.stop()
            if failures or now - t_open >= env.seconds:
                break
        t_close = now
        wall_close = time.time()
        reg_close = REGISTRY.snapshot()
        if tw and tw.running:
            tw.stop()
        env.log(f"window: closed after {t_close - t_open:.3f} s, "
                f"{good}/{sent} blocks, head {idx}")
        env.log("window: seconds per batch " + " ".join(
            f"{x:.2f}" for x in batch_s))
        log_node_reads(env, reg_open, reg_close)
        in_window = [st for t, st in tap.rows if t_open < t <= t_close]
        log_lanes(env, reg_open, reg_close, in_window)
        if batch_s and len(in_window) == len(batch_s):
            # a stalled batch says in which phase it stalled
            worst = max(range(len(batch_s)), key=batch_s.__getitem__)
            env.log(f"window: slowest batch {worst + 1}, phases " + " ".join(
                f"{k}={v:.2f}" for k, v in sorted(
                    in_window[worst].phases.items()) if v >= 0.01))

        # --------------------------------- after the window: the checks
        checks = [
            Check("builder_header_root_mismatches", len(bad_headers), 0),
            Check("blocks_failed_or_wrong_root", sent - good, 0),
        ]
        stored_bad = 0
        last_good = warm + good
        for n in range(1, last_good + 1):
            if client.get_state_root(n) != roots[n - 1]:
                stored_bad += 1
        checks.append(Check("stored_root_mismatches", stored_bad, 0))
        best, best_hash = client.best_block()
        checks.append(Check("head_mismatch", int(
            best != last_good or best_hash != blocks[last_good - 1].hash), 0))
        checks += ledger_checks(env, data, blocks, rpc_port, warm, best)
        reg = REGISTRY.snapshot()
        rose = {k: reg.get(k, 0) - at_boot[k] for k in FALLBACK_COUNTERS}
        env.log(f"fallback counters since boot: {rose}")
        checks.append(Check("fallback_counter_rises", sum(rose.values()), 0))
        clog = compile_log.snapshot()
        for e in clog["events"]:
            if e["kind"] == "miss":
                env.log(f"compile: {e.get('compile_s', 0.0):6.1f} s "
                        f"{'IN WINDOW ' if wall_open <= e['t'] <= wall_close else ''}"
                        f"{e['signature']}")
        want = "pallas" if env.device["platform"] == "tpu" else "jnp"
        backends = [e["signature"].split("backend=")[1].split()[0]
                    for e in clog["events"] if "backend=" in e["signature"]]
        checks.append(Check("fused_signatures_absent", int(not backends), 0))
        checks.append(Check("fused_signatures_not_" + want,
                            sum(b != want for b in backends), 0))
        native = [native_keccak.available(), native_vm.available()]
        checks.append(Check("native_pieces_missing", native.count(False), 0))
        if failures:
            env.log("failures: " + "; ".join(failures[:5]))

        window_s = t_close - t_open
        e2e = {"setup_s": setup_s,
               "sync_blocks_per_s": good / window_s if window_s else 0.0}
        attempted, failed = sent, sent - good
        spans = board.tracer.snapshot() if env.trace else []
        if env.trace:
            env.log(f"span ring: {len(spans)} kept, "
                    f"{board.tracer.dropped} dropped")
        art = {
            "window": (t_open, t_close), "wall_window": (wall_open, wall_close),
            "blocks": good,
            "windows": good / int(program["sync"]["commit_window_blocks"]),
            "spans": [s for s in spans if s.t1 > t_open and s.t0 < t_close],
            "spans_dropped": board.tracer.dropped if env.trace else 0,
            "replay_stats": in_window,
            "compile_events": clog["events"],
            "trace": tw,
            "registry": (reg_open, reg_close),
        }
        if env.trace:
            log_metrics_of(env, traffic.get("log_metrics_of"), art)
        return Outcome(e2e, checks, attempted, failed, art)
    finally:
        if client is not None:
            client.close()
        if board is not None:
            board.shutdown()
        if data["builder"] is not None:  # a failed run: stop the child
            data["builder"].kill()
            data["builder"].wait()


def log_lanes(env, reg_open: Dict, reg_close: Dict, in_window) -> None:
    """Transactions and seconds of the window by execute lane, and
    whether the lanes' transactions sum to those of its batches (a
    program without the counters, the parent, logs nothing)."""
    if any(f not in reg_close for f in EXEC_FAMILIES):
        return
    gained = [{k: reg_close[f][k] - reg_open[f].get(k, 0)
               for k in reg_close[f]} for f in EXEC_FAMILIES]
    lanes = {k.split('"')[1]: (int(gained[0][k]), gained[1][k])
             for k in gained[0]}
    total = sum(n for n, _ in lanes.values())
    env.log("execute lanes in the window: " + " ".join(
        f"{lane}={n}tx/{s:.2f}s" for lane, (n, s) in sorted(lanes.items()))
        + f"; sum {total} of {sum(st.txs for st in in_window)} transactions")


# --------------------------------------------------------------- checks


def ledger_checks(env, data: Dict, blocks, rpc_port: int, warm: int,
                  head: int) -> List[Check]:
    """What the node serves over HTTP at ``head`` against the reference
    folded to ``head``: plain balances, token balances and allowances
    slots, pair reserves, receipts of a seeded sample of the window's
    transactions (blocks warm + 1 .. head), and ``eth_getLogs``."""
    rng = np.random.default_rng([env.seed, 0x636865636B])
    others, senders, picks = data["others"], data["senders"], data["picks"]
    tokens, pairs = data["tokens"], data["pairs"]
    hx = lambda b: "0x" + b.hex()  # noqa: E731
    led, receipts = ref.fold(data, picks, head)
    storage = lambda a, slot: int(rpc(  # noqa: E731
        rpc_port, "eth_getStorageAt", hx(a), hex(slot), "latest"), 16)
    kind = picks["kind"][:head]

    # ---- 64 plain balances: 32 that a block paid, 32 of any
    paid = np.array(sorted(led.plain_gained))
    sample = list(rng.choice(paid, min(32, len(paid)), replace=False)) + \
        list(rng.choice(len(others), min(32, len(others)), replace=False))
    wrong = 0
    for i in sample:
        got = int(rpc(rpc_port, "eth_getBalance", hx(others[int(i)]),
                      "latest"), 16)
        wrong += got != (gen_accounts.PLAIN_BALANCE_BASE
                         + int(data["extra"][int(i)])
                         + led.plain_gained.get(int(i), 0))
    out = [Check("balance_mismatches_of_%d" % len(sample), wrong, 0)]

    # ---- token balances on 8 tokens, in deep's four kinds
    in_window = np.zeros(kind.shape, dtype=bool)
    in_window[warm:] = True
    ok_swap = np.array([[receipts[b, j][1] == 1 for j in range(kind.shape[1])]
                        for b in range(head)]) & (kind == ref.KIND_SWAP)
    swapped = np.unique(picks["pair"][:head][ok_swap & in_window])
    n = len(tokens)
    called = np.unique(picks["token"][:head][picks["token"][:head] >= 0])
    tail = called[called >= n // 2]
    chosen = [0] + ([int(rng.choice(tail))] if len(tail) else [])
    if len(swapped):
        chosen.append(int(rng.choice(swapped)) + 1)
    chosen = list(dict.fromkeys(chosen))
    rest = np.setdiff1d(called, chosen)
    chosen += [int(c) for c in rng.choice(
        rest, min(8 - len(chosen), len(rest)), replace=False)]
    wrong = reads = 0
    seen = {"untouched": 0, "updated": 0, "created": 0, "senders": 0}
    plain_index = led.plain_index
    for c in chosen:
        held = set(data["holders"][c].tolist())
        touched = {plain_index[a] for a in led.balances[c]
                   if a in plain_index}
        book = {
            "untouched": [others[i] for i in sorted(held - touched)],
            "updated": [others[i] for i in sorted(held & touched)],
            "created": [others[i] for i in sorted(touched - held)],
            "senders": [a for a in senders if a in led.balances[c]],
        }
        for what, who in book.items():
            who = [who[int(i)] for i in rng.choice(
                len(who), min(2, len(who)), replace=False)]
            for a, slot in zip(who, ref.balance_slots(who)):
                wrong += storage(tokens[c], slot) != led.balance_of(c, a)
                reads += 1
                seen[what] += 1
    env.log(f"token slots read on ranks {[c + 1 for c in chosen]}: {seen}")
    out += [
        Check("token_slot_mismatches_of_%d" % reads, wrong, 0),
        Check("token_contracts_sampled_under_8",
              max(0, min(8, n) - len(chosen)), 0),
        Check("token_sample_lacks_rank_1_or_a_lower_half_rank",
              int(not len(tail)), 0),
        Check("token_sample_lacks_a_swapped_pairs_tokens",
              int(not len(swapped)), 0),
        Check("token_slot_kinds_unsampled",
              sum(v == 0 for v in seen.values()), 0),
    ]

    # ---- 16 allowances: approved, spent by transferFrom, spent by a swap
    ns = len(senders)
    wanted = []
    for k, how_many in ((ref.KIND_APPROVE, 6), (ref.KIND_TRANSFER_FROM, 5),
                        (ref.KIND_SWAP, 5)):
        mask = (kind == k) & in_window
        if k == ref.KIND_SWAP:
            mask &= ok_swap
        where = np.argwhere(mask)
        for b, j in where[rng.choice(
                len(where), min(how_many, len(where)), replace=False)]:
            s = int(picks["sender"][b, j])
            if k == ref.KIND_APPROVE:
                wanted.append((k, int(picks["token"][b, j]), senders[s],
                               others[int(picks["receiver"][b, j])]))
            elif k == ref.KIND_TRANSFER_FROM:
                wanted.append((k, int(picks["token"][b, j]),
                               senders[(s + 1) % ns], senders[s]))
            else:
                p = int(picks["pair"][b, j])
                wanted.append((k, 0 if picks["flag"][b, j] else p + 1,
                               senders[s], pairs[p]))
    slots = ref.allowance_slots([(o, s) for _, _, o, s in wanted])
    wrong = sum(storage(tokens[c], slot) != led.allowance(c, o, s)
                for (_, c, o, s), slot in zip(wanted, slots))
    out += [
        Check("allowance_slot_mismatches_of_%d" % len(wanted), wrong, 0),
        Check("allowance_kinds_unsampled",
              3 - len({k for k, *_ in wanted}), 0),
    ]

    # ---- both reserves of every pair swapped in the window
    wrong = sum(storage(pairs[int(p)], side) != led.reserves[int(p)][side]
                for p in swapped for side in (0, 1))
    out.append(Check("reserve_mismatches_of_%d_pairs" % len(swapped),
                     wrong, 0))

    # ---- receipts: 96 of the window's transactions, 8 of each kind
    per_kind = []
    for k in ref.KIND_NAMES:
        where = np.argwhere((kind == k) & in_window)
        per_kind += [tuple(x) for x in where[rng.choice(
            len(where), min(8, len(where)), replace=False)]]
    everything = np.argwhere(in_window)
    more = [tuple(x) for x in everything[rng.choice(
        len(everything), min(96, len(everything)), replace=False)]]
    sample = list(dict.fromkeys(per_kind + more))[:96]
    wrong = logs_seen = 0
    by_kind: Dict[str, int] = {}
    for b, j in sample:
        k, status, logs = receipts[int(b), int(j)]
        got = rpc(rpc_port, "eth_getTransactionReceipt",
                  hx(blocks[int(b)].body.transactions[int(j)].hash))
        theirs = [] if got is None else [
            (bytes.fromhex(g["address"][2:]),
             tuple(bytes.fromhex(t[2:]) for t in g["topics"]),
             bytes.fromhex(g["data"][2:])) for g in got["logs"]]
        wrong += (got is None or int(got["status"], 16) != status
                  or theirs != logs
                  or bytes.fromhex(got["logsBloom"][2:]) != ref.bloom(logs))
        logs_seen += len(logs)
        by_kind[ref.KIND_NAMES[k]] = by_kind.get(ref.KIND_NAMES[k], 0) + 1
    env.log(f"receipts read: {by_kind}, {logs_seen} logs")
    out += [
        Check("receipt_mismatches_of_%d" % len(sample), wrong, 0),
        Check("receipt_kinds_unsampled",
              len(ref.KIND_NAMES) - len(by_kind), 0),
        Check("receipt_sample_has_no_logs", int(logs_seen == 0), 0),
    ]

    # ---- eth_getLogs: Transfer on the rank-1 token over the window
    want = [(b + 1, j, topics, payload)
            for (b, j), (_, _, logs) in sorted(receipts.items()) if b >= warm
            for address, topics, payload in logs
            if address == tokens[0] and topics[0] == ref.TOPIC_TRANSFER]
    got = rpc(rpc_port, "eth_getLogs", {
        "fromBlock": hex(warm + 1), "toBlock": hex(head),
        "address": hx(tokens[0]), "topics": [hx(ref.TOPIC_TRANSFER)]})
    got = [(int(g["blockNumber"], 16), int(g["transactionIndex"], 16),
            tuple(bytes.fromhex(t[2:]) for t in g["topics"]),
            bytes.fromhex(g["data"][2:])) for g in got]
    out += [
        Check("getlogs_differs_from_reference_of_%d" % len(want),
              int(got != want), 0),
        Check("getlogs_reference_is_empty", int(not want), 0),
    ]
    return out
