"""Driver ``sync_state``: ``drivers/sync.py``'s run over a state that
exists before block 1. The seed's genesis carries token contracts with
pre-populated storage (``generators/state.py``), the chain's token calls
go to those contracts (``generators/chain_state.py``), and the closing
reads are held to ``reference/ledger_state.py``.

``send``, ``node_config``, ``rpc``, ``StatsTap``, ``rest_of_chain`` and
``FALLBACK_COUNTERS`` are ``drivers/sync.py``'s. Its ``run`` calls its
own ``seed_data`` and ``ledger_checks`` by module global, so the window
loop is written again here, line for line the same (same window rule,
same checks, same artefact keys, same ``--control wrong-root``), with
one artefact more: ``registry``, the process registry's snapshot at the
window's opening and at its close, because its counters are cumulative
since boot and the counter readers take the difference.
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
from benchmark.generators import accounts as gen_accounts
from benchmark.generators import chain_state as gen_chain
from benchmark.generators import state as gen_state
from benchmark.lib.outcome import Check, Outcome
from benchmark.lib.tracewin import annotate
from benchmark.reference import ledger_state as ref_ledger

STORES = ("account", "storage", "evmcode")


# ------------------------------------------------------------ seed data


def seed_data(env, sizes: Dict, traffic: Dict) -> Dict:
    """State, genesis data dir and chain for (configuration, traffic,
    seed). Dir and chain are built on first use and kept under
    ``benchmark/cache/``; a new seed's chain is built by a child process
    (JAX held to the CPU there, the same spec loaded into a store of its
    own) beside this one's genesis build and the node's warm-up."""
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.storage.storages import Storages

    txs = int(sizes["txs_per_block"])
    blocks = int(traffic["chain_blocks"])
    t0 = time.perf_counter()
    data = gen_state.make_state(sizes, env.seed)
    env.log(f"seed: state of {len(data['alloc'])} accounts, "
            f"{len(data['tokens'])} contracts, "
            f"{sum(len(h) for h in data['holders'])} slots drawn in "
            f"{time.perf_counter() - t0:.1f} s")
    data["picks"] = gen_chain.draw(
        traffic["params"], blocks, txs, len(data["others"]),
        data["holders"], env.seed)
    state_sizes = {k: sizes[k] for k in (
        "accounts", "funded_senders", "token_contracts", "token_slots")}
    seed_dir = os.path.join(env.cache_dir, f"{env.seed}-{_key(state_sizes)}")
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
            data["wire"], data["roots"], data["token"] = gen_chain.load(
                chain_file)
            return data
        while not os.path.exists(head_file):
            if data["builder"].poll() is not None:
                raise RuntimeError("chain builder exited "
                                   f"{data['builder'].returncode}")
            time.sleep(0.2)
        data["wire"], data["roots"], data["token"] = gen_chain.load(head_file)
        return data
    except BaseException:  # no child outlives a set-up that failed
        if data["builder"] is not None:
            data["builder"].kill()
            data["builder"].wait()
        raise


# --------------------------------------------------------------- window


def run(env) -> Outcome:
    try:
        from khipu_tpu.domain.blockchain import GenesisAccount  # noqa: F401
    except ImportError:
        # fail at once, before any seed data: no result line. In a fresh
        # checkout the import above started gcc on the RLP extension in
        # a daemon thread (base/rlp.py); leaving now would orphan that
        # compiler, so wait for it behind the build's own lock
        from khipu_tpu.native.build import load_rlp_ext

        load_rlp_ext()
        raise SystemExit("this program's genesis takes balances only: it "
                         "cannot load a state with code and storage")
    from khipu_tpu.bridge import BridgeClient
    from khipu_tpu.domain.block import Block
    from khipu_tpu.evm import native_vm
    from khipu_tpu.native import keccak as native_keccak
    from khipu_tpu.observability.recorder import compile_log
    from khipu_tpu.observability.registry import REGISTRY
    from khipu_tpu.service_board import ServiceBoard

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
        if env.control == "wrong-root":
            # the control: one header in the window's first batch claims
            # a root that no honest execution reaches; a node that checks
            # every root must refuse the block, and `correct` must come
            # out false
            h = blocks[warm + 1].header
            blocks[warm + 1] = dataclasses.replace(
                blocks[warm + 1], header=dataclasses.replace(
                    h, state_root=bytes([h.state_root[0] ^ 1])
                    + h.state_root[1:]))
            env.log(f"control: block {warm + 2} carries a forged state root")
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
                good += send(client, blocks, roots, idx, hi, failures)
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
        in_window = [st for t, st in tap.rows if t > t_open]
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
        checks += ledger_checks(env, data, rpc_port, best)
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
            "replay_stats": [s for t, s in tap.rows if t_open < t <= t_close],
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


def log_metrics_of(env, cell: str, art: Dict) -> None:
    """The per-layer metrics that list ``cell`` alone, read from this
    run's artefacts by their own readers, for the log. ``sync.dense``
    has seven whose ``workloads`` a test of the benchmark pins to one
    cell (PERF.md section 7), so this cell's line cannot carry them."""
    if not cell:
        return
    from benchmark.lib import manifest

    for m in manifest.metrics_for(cell, "per_layer"):
        if m.get("workloads") == [cell]:
            spec = manifest.metric_file(m["name"])
            reader = manifest.load_module("readers", spec["reader"])
            value = reader.read(art, **spec.get("args", {}))
            env.log(f"also read, as {cell} reads it: {m['name']} = {value}")


def log_node_reads(env, reg_open: Dict, reg_close: Dict) -> None:
    """Where each node store's reads of the window were answered from."""
    reads = "khipu_nodestore_reads_total"
    if reads not in reg_close:
        return
    for store in STORES:
        n = {origin: reg_close[reads][f'from="{origin}",store="{store}"']
             - reg_open[reads][f'from="{origin}",store="{store}"']
             for origin in ("cache", "source", "mirror", "absent")}
        total = sum(n.values())
        env.log(f"node reads, {store} store, in the window: " + " ".join(
            f"{k}={v}" for k, v in n.items())
            + (f" hit rate {100.0 * n['cache'] / total:.1f} %"
               if total else ""))


def ledger_checks(env, data: Dict, rpc_port: int, head: int) -> List[Check]:
    """A seeded sample of 64 balances and of token slots on 8 contracts
    (rank 1 and a rank in the lower half among them), read over HTTP
    from the node, against the plain ledger folded to ``head``. On each
    contract the sample takes pre-populated slots never written,
    pre-populated slots written by a block, slots a block created, and
    senders' slots."""
    rng = np.random.default_rng([env.seed, 0x636865636B])
    others, picks = data["others"], data["picks"]
    hx = lambda b: "0x" + b.hex()
    balances = ref_ledger.plain_balances(
        gen_accounts.PLAIN_BALANCE_BASE, data["extra"], picks, head)
    kind = picks["kind"][:head]
    paid = np.unique(picks["receiver"][:head][kind == gen_chain.KIND_PLAIN])
    sample = list(rng.choice(paid, min(32, len(paid)), replace=False)) + \
        list(rng.choice(len(others), min(32, len(others)), replace=False))
    wrong = 0
    for i in sample:
        got = int(rpc(rpc_port, "eth_getBalance", hx(others[int(i)]),
                      "latest"), 16)
        wrong += got != balances[int(i)]
    out = [Check("balance_mismatches_of_%d" % len(sample), wrong, 0)]

    n = len(data["tokens"])
    called = np.unique(picks["token"][:head][kind == gen_chain.KIND_TOKEN])
    tail = called[called >= n // 2]
    chosen = [0] + ([int(rng.choice(tail))] if len(tail) else [])
    rest = np.setdiff1d(called, chosen)
    chosen += [int(c) for c in rng.choice(
        rest, min(8 - len(chosen), len(rest)), replace=False)]
    wrong = reads = 0
    seen = {"untouched": 0, "updated": 0, "created": 0, "senders": 0}
    for c in chosen:
        book = ref_ledger.contract_ledger(
            c, data["holders"][c], data["holdings"][c], picks, head)
        for what, want_by_index in book.items():
            who = sorted(want_by_index)
            who = [who[int(i)] for i in rng.choice(
                len(who), min(2, len(who)), replace=False)]
            owners = data["senders"] if what == "senders" else others
            slots = ref_ledger.token_slots([owners[i] for i in who])
            for i, slot in zip(who, slots):
                got = int(rpc(rpc_port, "eth_getStorageAt",
                              hx(data["tokens"][c]), hex(slot), "latest"), 16)
                wrong += got != want_by_index[i]
                reads += 1
                seen[what] += 1
    env.log(f"token slots read on ranks {[c + 1 for c in chosen]}: {seen}")
    out.append(Check("token_slot_mismatches_of_%d" % reads, wrong, 0))
    out.append(Check("token_contracts_sampled_under_8",
                     max(0, min(8, n) - len(chosen)), 0))
    out.append(Check("token_sample_lacks_rank_1_or_a_lower_half_rank",
                     int(not len(tail)), 0))
    out.append(Check("token_slot_kinds_unsampled",
                     sum(v == 0 for v in seen.values()), 0))
    return out
