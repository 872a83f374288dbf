"""Driver ``sync``: boot a node on a copy of the seed's genesis data dir,
stream the seed's chain over the gRPC bridge in batches, and hold what
it answers to the chain's own roots and to the plain ledger.

One process: bridge server, bridge client and HTTP server are threads
here, as in ``chip_smoke.py``. The program receives only generated inputs.

The measured window opens after warm-up, the client sends batches back
to back, and the window closes at the first reply that arrives at or
after ``--seconds`` (or when the chain is exhausted). Every block
replied is counted over the whole of that time, so no batch in flight
at a fixed deadline is lost to the count.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
import urllib.request
from typing import Dict, List, Tuple

import numpy as np

from benchmark.generators import accounts as gen_accounts
from benchmark.generators import chain as gen_chain
from benchmark.lib.outcome import Check, Outcome
from benchmark.lib.tracewin import annotate
from benchmark.reference import ledger as ref_ledger

# chip_smoke.FALLBACK_COUNTERS, copied: a pinned-device deployment in
# which any of these rose hashed on the host and measured something else
FALLBACK_COUNTERS = (
    "khipu_window_fused_fallbacks",
    "khipu_pipeline_sync_fallback_windows",
    "khipu_mirror_unspilled_evictions",
    "khipu_fused_async_copy_fallbacks",
)
GAS_LIMIT = 30_000_000


def _key(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True).encode()).hexdigest()[:10]


# ------------------------------------------------------------ seed data


def seed_data(env, sizes: Dict, traffic: Dict) -> Dict:
    """Genesis data dir and chain for (configuration, traffic, seed),
    built on first use and kept under ``benchmark/cache/``."""
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.storage.storages import Storages

    accounts, funded = int(sizes["accounts"]), int(sizes["funded_senders"])
    txs = int(sizes["txs_per_block"])
    blocks = int(traffic["chain_blocks"])
    keys, senders, others, extra, alloc = gen_accounts.make_alloc(
        accounts, funded, env.seed)
    spec = GenesisSpec(alloc=alloc, gas_limit=GAS_LIMIT)
    seed_dir = os.path.join(
        env.cache_dir, f"{env.seed}-{_key([accounts, funded])}")
    genesis_dir = os.path.join(seed_dir, "genesis")
    if not os.path.exists(os.path.join(seed_dir, "genesis.ok")):
        env.log(f"seed: building genesis ({accounts} accounts)")
        shutil.rmtree(genesis_dir, ignore_errors=True)
        os.makedirs(genesis_dir)
        storages = Storages(engine="kesque", data_dir=genesis_dir)
        Blockchain(storages, fixture_config(chain_id=1)).load_genesis(spec)
        storages.stop()  # flushes and closes: the node reopens a copy
        open(os.path.join(seed_dir, "genesis.ok"), "w").close()

    picks = gen_chain.draw(traffic["params"], blocks, txs, len(others),
                           env.seed)
    chain_file = os.path.join(
        seed_dir,
        f"chain-{_key([traffic['generator'], traffic['params'], blocks, txs])}"
        ".npz")
    data = {"genesis_dir": genesis_dir, "picks": picks, "senders": senders,
            "others": others, "extra": extra, "chain_file": chain_file,
            "builder": None}
    if os.path.exists(chain_file):
        data["wire"], data["roots"], data["token"] = gen_chain.load(chain_file)
        return data
    # a new seed: the chain is built by a child process (JAX held to the
    # CPU there) while this one boots the node and compiles on the head
    head_file = chain_file[:-4] + ".head.npz"
    if os.path.exists(head_file):
        os.remove(head_file)
    env.log(f"seed: building chain ({blocks} blocks x {txs} tx) in a child")
    child_env = dict(os.environ, JAX_PLATFORMS="cpu")
    data["builder"] = subprocess.Popen(
        [sys.executable, os.path.abspath(gen_chain.__file__), json.dumps({
            "accounts": accounts, "funded": funded, "txs": txs,
            "blocks": blocks, "seed": env.seed, "params": traffic["params"],
            "gas_limit": GAS_LIMIT, "head_blocks": int(sizes["batch_blocks"]),
            "head_out": head_file, "out": chain_file})],
        env=child_env, stdout=subprocess.DEVNULL)
    while not os.path.exists(head_file):
        if data["builder"].poll() is not None:
            raise RuntimeError("chain builder exited "
                               f"{data['builder'].returncode}")
        time.sleep(0.2)
    data["wire"], data["roots"], data["token"] = gen_chain.load(head_file)
    return data


def rest_of_chain(env, data: Dict) -> None:
    """Wait for the child that builds a new seed's chain, and load it."""
    proc = data["builder"]
    if proc is None:
        return
    env.log("seed: waiting for the chain builder")
    if proc.wait() != 0:
        raise RuntimeError(f"chain builder exited {proc.returncode}")
    data["builder"] = None
    head = data["wire"]
    data["wire"], data["roots"], data["token"] = gen_chain.load(
        data["chain_file"])
    if data["wire"][: len(head)] != head:
        raise RuntimeError("chain builder's head differs from its chain")
    os.remove(data["chain_file"][:-4] + ".head.npz")


# ----------------------------------------------------------------- node


def node_config(data_dir: str, program: Dict, observe: bool):
    """The node's config: the configuration file's ``program`` overrides
    on ``fixture_config``; the flight recorder and movement ledger are on
    only in the traced run."""
    from khipu_tpu.config import (
        DbConfig,
        ObservabilityConfig,
        SyncConfig,
        fixture_config,
    )

    return dataclasses.replace(
        fixture_config(chain_id=1),
        db=DbConfig(data_dir=data_dir, **program["db"]),
        sync=SyncConfig(**program["sync"]),
        observability=ObservabilityConfig(
            enabled=observe, ring_capacity=1 << 19, ledger_enabled=observe),
    )


def rpc(port: int, method: str, *params):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}",
        data=json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                         "params": list(params)}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        out = json.loads(resp.read())
    if "error" in out:
        raise RuntimeError(f"{method}: {out['error']}")
    return out["result"]


class StatsTap:
    """Keeps what ``ReplayDriver.replay`` returns for each batch: the
    bridge server drops it (listed for the program in PERF.md)."""

    def __init__(self, bridge_server):
        self.rows: List[Tuple[float, object]] = []
        driver = bridge_server._driver
        inner = driver.replay

        def replay(blocks):
            stats = inner(blocks)
            self.rows.append((time.perf_counter(), stats))
            return stats

        driver.replay = replay


# --------------------------------------------------------------- window


def send(client, blocks, roots, lo: int, hi: int, failures: List[str]) -> int:
    """One ExecuteBlocks call for blocks [lo, hi) (0-based; block
    number = index + 1). Returns how many replies matched."""
    batch = blocks[lo:hi]
    try:
        reply = client.execute_blocks(batch)
    except Exception as e:  # the bridge aborts the call on a bad block
        failures.append(f"blocks {lo + 1}..{hi}: {type(e).__name__}: "
                        f"{str(e)[:200]}")
        return 0
    good = 0
    for (number, root), idx in zip(reply, range(lo, hi)):
        if number == idx + 1 and root == roots[idx]:
            good += 1
        else:
            failures.append(f"block {idx + 1}: replied root differs")
    if len(reply) != len(batch):
        failures.append(f"blocks {lo + 1}..{hi}: {len(reply)} replies")
    return good


def run(env) -> Outcome:
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
    roots = data["roots"]
    blocks = [Block.decode(w) for w in data["wire"]]
    node_dir = os.path.join(env.run_dir, "node")
    shutil.copytree(data["genesis_dir"], node_dir)
    cfg = node_config(node_dir, program, observe=env.trace)
    env.log("node: boot")
    board = ServiceBoard(cfg)
    client = None
    try:
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
        if tw and tw.running:
            tw.stop()
        env.log(f"window: closed after {t_close - t_open:.3f} s, "
                f"{good}/{sent} blocks, head {idx}")
        env.log("window: seconds per batch " + " ".join(
            f"{x:.2f}" for x in batch_s))
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
        }
        return Outcome(e2e, checks, attempted, failed, art)
    finally:
        if client is not None:
            client.close()
        board.shutdown()
        if data["builder"] is not None:  # a failed run: stop the child
            data["builder"].kill()
            data["builder"].wait()


def ledger_checks(env, data: Dict, rpc_port: int, head: int) -> List[Check]:
    """A seeded sample of 64 balances and 32 token slots, read over HTTP
    from the node, against the plain ledger folded to ``head``."""
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

    held, sent = ref_ledger.token_balances(
        len(others), len(data["senders"]), picks, head)
    credited = np.unique(
        picks["receiver"][:head][kind == gen_chain.KIND_TOKEN])
    pool = np.unique(picks["sender"][:head][kind == gen_chain.KIND_TOKEN])
    holders = [(others[int(i)], held[int(i)]) for i in
               rng.choice(credited, min(24, len(credited)), replace=False)]
    holders += [(data["senders"][int(s)], sent[int(s)]) for s in
                rng.choice(pool, min(8, len(pool)), replace=False)]
    slots = ref_ledger.token_slots([h for h, _ in holders])
    wrong = nonzero = 0
    for (holder, want), slot in zip(holders, slots):
        got = int(rpc(rpc_port, "eth_getStorageAt", hx(data["token"]),
                      hex(slot), "latest"), 16)
        wrong += got != want
        nonzero += want != 0
    out.append(Check("token_slot_mismatches_of_%d" % len(holders), wrong, 0))
    if head > 1:
        out.append(Check("token_slots_all_zero", int(nonzero == 0), 0))
    return out
