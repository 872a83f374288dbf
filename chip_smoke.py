#!/usr/bin/env python3
"""chip_smoke.py — does the main path run on the attached chip?

One process drives bridge -> windowed replay -> fused device commit ->
JSON-RPC once, through the entry points a node operator uses, at a state
size an operator would recognise (BASELINE configs #2-#5), and checks
every answer against the host oracles the repo keeps. It proves that the
program starts and is right on the chip; it measures nothing. Wall
seconds per leg and compile seconds are printed as set-up facts, never
as rates.

    python chip_smoke.py            # on a machine with a TPU; exit 0 = ok

* exits non-zero, before any work and with no result line, unless
  ``jax.devices()[0].platform == "tpu"`` (the script sets no
  JAX_PLATFORMS);
* one process holds the chip: the gRPC bridge, its client, the HTTP
  server and its client are all threads of this process;
* every leg raises on failure — nothing is caught so that a later leg
  can still run;
* last stdout line: exactly ``{"ok": true, "device": {"platform": ...,
  "kind": ..., "count": ...}}``, the device as JAX reports it. The line
  before it, ``chip_smoke: detail {...}`` (also written to
  ``chiprun_out/chip_smoke/chip_smoke.json``), carries per-leg wall
  seconds, compile seconds and whether the persistent compile cache
  was hit.

The legs are importable functions with size arguments
(tests/test_chip_smoke.py runs each at a tiny size on the CPU); only
``main`` enforces ``tpu`` and the full sizes.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import shutil
import sys
import threading
import time
import urllib.request
from typing import Dict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# full sizes (minimums from ISSUE 21 / BASELINE configs #2-#5)
KERNEL_ROWS = 1 << 20      # 1,048,576 x 576 B rows (config #2)
NODE_BYTES = 576
CLASS_ROWS = 16 * 1024     # one batch per rate class 1-4
ORACLE_ROWS = 4096         # scalar-oracle prefix + seeded sample size
ACCOUNTS = 100_000         # genesis accounts (config #3)
BLOCKS = 64                # config #4's shape: 64 blocks x 200 tx
TXS_PER_BLOCK = 200
WINDOW_BLOCKS = 4          # -> 16 windows, > 2 x adaptive_dwell_windows
BATCH_BLOCKS = 16          # blocks per ExecuteBlocks call
MIRROR_ROWS = 262_144      # per class: ~0.4 GB over classes 1-4
MULTICHIP_SESSION_KEYS = 8192  # -> >= 10,000 deferred trie nodes

# The replay leg PINS device commit (adaptive_commit=False) and says so
# in its result. Not a tuning: on the attached v5e the default
# controller chooses the host at this window size, which would leave the
# smoke nothing on the device to check (my chip runs, PR 21). The
# one-shot probe reads d2d gather 0.90 GB/s against host memcpy
# 8.63 GB/s (it needs >= 1.5x), downgrades before window 0, and all 16
# windows hash on the host (flips_total 1, host EWMA 43 us/hash). With
# the probe skipped the EWMA trigger flips to host at the dwell (window
# 6, ratio 2.53 against a scalar-keccak host estimate ten times below
# the host path's real cost) and back at the next (window 12, ratio
# 0.25: device 11 us/hash against a measured host 44 us/hash). Whether
# the controller's inputs are the right ones on a chip is ROADMAP
# Queue 3 item 3's decision; CHANGES.md has the numbers.
ADAPTIVE = "pinned-device"


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------- compile meter


class CompileMeter:
    """What JAX spent getting programs, from jax.monitoring, per leg:
    ``xla_s`` is backend compile time (what the persistent cache turns
    into a load), ``trace_lower_s`` is Python tracing and lowering
    (which no cache saves; nested traces are merged, not summed), the
    longest single backend compile, and persistent-cache hits/misses."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    FRONT = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
    )

    def __init__(self):
        import jax

        self.leg = "startup"
        self.per_leg: Dict[str, dict] = {}
        self.longest = {"seconds": 0.0, "fun": "", "leg": ""}
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _row(self) -> dict:
        return self.per_leg.setdefault(
            self.leg, {"programs": 0, "xla_s": 0.0, "front": {}}
        )

    def _dur(self, event: str, seconds: float, **kw) -> None:
        if event == self.BACKEND:
            row = self._row()
            row["programs"] += 1
            row["xla_s"] += seconds
            if seconds > self.longest["seconds"]:
                self.longest = {
                    "seconds": round(seconds, 3),
                    "fun": str(kw.get("fun_name", "")),
                    "leg": self.leg,
                }
        elif event in self.FRONT:
            # the event fires when the span ENDS; an inner jit's trace
            # ends inside its caller's, so keep intervals per thread
            # and merge them at report time
            end = time.perf_counter()
            self._row()["front"].setdefault(
                threading.get_ident(), []
            ).append((end - seconds, end))

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    @staticmethod
    def _union(intervals) -> float:
        total, cur_lo, cur_hi = 0.0, None, None
        for lo, hi in sorted(intervals):
            if cur_hi is None or lo > cur_hi:
                if cur_hi is not None:
                    total += cur_hi - cur_lo
                cur_lo, cur_hi = lo, hi
            else:
                cur_hi = max(cur_hi, hi)
        return total + (cur_hi - cur_lo if cur_hi is not None else 0.0)

    def report(self) -> dict:
        per_leg = {
            leg: {
                "programs": r["programs"],
                "xla_s": round(r["xla_s"], 3),
                "trace_lower_s": round(sum(
                    self._union(iv) for iv in r["front"].values()), 3),
            }
            for leg, r in self.per_leg.items()
        }
        return {
            "xla_s": round(sum(r["xla_s"] for r in per_leg.values()), 3),
            "trace_lower_s": round(
                sum(r["trace_lower_s"] for r in per_leg.values()), 3),
            "per_leg": per_leg,
            "longest": self.longest,
        }


# ------------------------------------------------------------ fixtures


def host_digests(rows: np.ndarray) -> np.ndarray:
    """Host-computed Keccak-256 of every row (native batch, chunked):
    the independent claims the kernel and snapshot legs compare to."""
    from khipu_tpu.native.keccak import keccak256_batch

    out = np.empty((rows.shape[0], 32), dtype=np.uint8)
    step = 1 << 16
    for lo in range(0, rows.shape[0], step):
        chunk = rows[lo : lo + step]
        out[lo : lo + len(chunk)] = np.frombuffer(
            b"".join(keccak256_batch([r.tobytes() for r in chunk])),
            dtype=np.uint8,
        ).reshape(len(chunk), 32)
    return out


def make_nodes(rows: int, seed: int):
    """(raw u8[rows, 576], host digests u8[rows, 32]) from ``seed``."""
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, 256, (rows, NODE_BYTES), dtype=np.uint8)
    return raw, host_digests(raw)


def _oracle_check(messages, got, rng, what: str) -> None:
    """Row-for-row against the scalar host oracle on the first
    ORACLE_ROWS rows and a seeded sample of the rest."""
    from khipu_tpu.base.crypto.keccak import keccak256

    n = len(messages)
    idx = list(range(min(n, ORACLE_ROWS)))
    if n > ORACLE_ROWS:
        idx += rng.choice(
            np.arange(ORACLE_ROWS, n), size=min(ORACLE_ROWS, n - ORACLE_ROWS),
            replace=False,
        ).tolist()
    for i in idx:
        want = keccak256(bytes(messages[i]))
        if bytes(got[i]) != want:
            raise AssertionError(f"{what}: row {i} diverges from the oracle")


# ---------------------------------------------------------- leg: kernel


def leg_kernel(raw: np.ndarray, digests: np.ndarray,
               class_rows: int = CLASS_ROWS, seed: int = 1) -> dict:
    """The Keccak kernel the platform selects (compiled Pallas on tpu,
    the jnp sponge on cpu) over ``raw`` and one batch in each of rate
    classes 1-4, every row compared with the host."""
    from khipu_tpu import device
    from khipu_tpu.native.keccak import keccak256_batch as host_batch
    from khipu_tpu.ops.keccak import keccak256_batch
    from khipu_tpu.ops.keccak_jnp import RATE

    rng = np.random.default_rng(seed)
    impl = "pallas" if device.platform() == "tpu" else "jnp"
    if impl == "pallas":
        from khipu_tpu.ops.keccak_pallas import keccak256_fixed

        got = keccak256_fixed(raw)  # interpret=False: the compiled kernel
    else:
        got = np.frombuffer(
            b"".join(keccak256_batch([r.tobytes() for r in raw])),
            dtype=np.uint8,
        ).reshape(-1, 32)
    if got.shape != digests.shape or not np.array_equal(got, digests):
        bad = int(np.sum(np.any(got != digests, axis=1)))
        raise AssertionError(f"kernel: {bad} of {len(raw)} 576 B rows wrong")
    _oracle_check(raw, got, rng, "kernel 576 B")

    classes = {}
    for nb in (1, 2, 3, 4):
        lens = rng.integers((nb - 1) * RATE, nb * RATE, class_rows)
        blob = rng.integers(0, 256, int(lens.sum()), dtype=np.uint8).tobytes()
        msgs, pos = [], 0
        for ln in lens:
            msgs.append(blob[pos : pos + ln])
            pos += ln
        out = keccak256_batch(msgs, impl=impl)
        if out != host_batch(msgs):
            raise AssertionError(f"kernel: rate class {nb} batch wrong")
        _oracle_check(msgs, out, rng, f"kernel class {nb}")
        classes[nb] = len(msgs)
    return {"impl": impl, "rows_576": int(raw.shape[0]),
            "class_rows": classes}


# ----------------------------------------------------------- leg: state


def smoke_config(data_dir: str, adaptive_commit: bool,
                 mirror_rows: int = MIRROR_ROWS):
    """The node's config for the smoke: persistent engine, windowed
    device commit at the default pipeline depth, flight recorder and
    movement ledger on (the smoke reads both)."""
    from khipu_tpu.config import (
        DbConfig,
        ObservabilityConfig,
        SyncConfig,
        fixture_config,
    )

    return dataclasses.replace(
        fixture_config(chain_id=1),
        db=DbConfig(engine="kesque", data_dir=data_dir),
        sync=SyncConfig(
            commit_window_blocks=WINDOW_BLOCKS,
            adaptive_commit=adaptive_commit,
            mirror_capacity_rows=mirror_rows,
        ),
        observability=ObservabilityConfig(
            enabled=True, ring_capacity=1 << 18, ledger_enabled=True,
        ),
    )


# ERC-20 transfer(to, amount) with REAL keccak mapping slots: balances
# live at keccak(pad32(holder) ++ pad32(0)) — sender slot debits by the
# amount word, recipient slot credits. Calldata is the raw two words
# (no ABI selector), so arg0 = recipient, arg1 = amount. Straight-line
# and fully whitelisted for the purity scan (const memory offsets, const
# SHA3 size), which is what lets the learner derive ("map_caller", 0) /
# ("map_arg", 0, 0) write rules and trust the code after confirmation.
_ERC20_RUNTIME = bytes([
    0x33,                    # CALLER
    0x60, 0x00, 0x52,        # PUSH1 0  MSTORE   mem[0:32] = caller
    0x60, 0x00,              # PUSH1 0  (mapping base slot)
    0x60, 0x20, 0x52,        # PUSH1 32 MSTORE   mem[32:64] = 0
    0x60, 0x40, 0x60, 0x00,  # PUSH1 64 PUSH1 0
    0x20,                    # SHA3              sender slot
    0x80, 0x54,              # DUP1 SLOAD        sender balance
    0x60, 0x20, 0x35,        # PUSH1 32 CALLDATALOAD   amount
    0x90, 0x03,              # SWAP1 SUB         bal - amount
    0x90, 0x55,              # SWAP1 SSTORE      debit sender
    0x60, 0x00, 0x35,        # PUSH1 0 CALLDATALOAD    recipient
    0x60, 0x00, 0x52,        # PUSH1 0  MSTORE   mem[0:32] = recipient
    0x60, 0x40, 0x60, 0x00,  # PUSH1 64 PUSH1 0  (mem[32:64] still 0)
    0x20,                    # SHA3              recipient slot
    0x80, 0x54,              # DUP1 SLOAD        recipient balance
    0x60, 0x20, 0x35,        # PUSH1 32 CALLDATALOAD   amount
    0x01,                    # ADD               bal + amount
    0x90, 0x55,              # SWAP1 SSTORE      credit recipient
    0x00,                    # STOP
])

# the runtime is wider than one word, so the constructor CODECOPYs it
# out of the init code
_ERC20_INIT = bytes([
    0x60, len(_ERC20_RUNTIME),  # PUSH1 len
    0x60, 0x0C,                 # PUSH1 12 (runtime offset in init code)
    0x60, 0x00,                 # PUSH1 0
    0x39,                       # CODECOPY
    0x60, len(_ERC20_RUNTIME),  # PUSH1 len
    0x60, 0x00,                 # PUSH1 0
    0xF3,                       # RETURN
]) + _ERC20_RUNTIME


def make_alloc(accounts: int, senders: int):
    """Genesis alloc: ``senders`` funded key-holders plus plain
    accounts up to ``accounts``. Returns (keys, sender_addrs, others,
    alloc)."""
    from scenarios import _replay_keys

    keys, addrs = _replay_keys(senders, seed_base=2101)
    others = [
        (0xC0FFEE0000 + i).to_bytes(20, "big")
        for i in range(accounts - senders)
    ]
    alloc = {a: 10**24 for a in addrs}
    alloc.update((a, 10**18 + i) for i, a in enumerate(others))
    return keys, addrs, others, alloc


def leg_state(alloc: dict, data_dir: str, cfg) -> dict:
    """Genesis through ``Blockchain.load_genesis(on_device=True)`` on
    the persistent engine; the root must equal the host bulk build and
    the fused (one-dispatch) bulk build."""
    from khipu_tpu.domain.account import Account, address_key
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.trie.bulk import bulk_build, host_hasher

    spec = GenesisSpec(alloc=alloc, gas_limit=30_000_000)
    storages = Storages(engine="kesque", data_dir=data_dir)
    chain = Blockchain(storages, cfg)
    genesis = chain.load_genesis(spec, on_device=True)
    root = genesis.header.state_root
    # stop() flushes and closes: the node below reopens this directory
    storages.stop()

    pairs = [
        (address_key(a),
         Account(nonce=cfg.blockchain.account_start_nonce,
                 balance=b).encode())
        for a, b in alloc.items()
    ]
    host_root, host_nodes = bulk_build(pairs, hasher=host_hasher)
    fused_root, fused_nodes = bulk_build(pairs, fused=True)
    if not (root == host_root == fused_root):
        raise AssertionError(
            f"genesis roots differ: device {root.hex()} host "
            f"{host_root.hex()} fused {fused_root.hex()}"
        )
    if fused_nodes != host_nodes:
        raise AssertionError("fused bulk build node set differs from host")
    return {"accounts": len(alloc), "trie_nodes": len(host_nodes),
            "root": root.hex(), "spec": spec}


# ---------------------------------------------------------- leg: replay


def build_chain(spec, keys, senders, others, blocks: int,
                txs_per_block: int, seed: int):
    """BASELINE config #4's shape on the host: block 1 deploys the
    ERC-20 fixture (``_ERC20_INIT``) beside plain transfers; every later block
    is half ERC-20 ``transfer`` calls, half plain transfers, one tx per
    sender, receivers drawn across the whole alloc. Built by
    ChainBuilder with the host hasher — the roots the replay must hit.
    Returns (builder_chain, blocks, token, touched addresses)."""
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.block import Block
    from khipu_tpu.domain.blockchain import Blockchain
    from khipu_tpu.domain.transaction import (
        Transaction,
        contract_address,
        sign_transaction,
    )
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.chain_builder import ChainBuilder

    cfg = fixture_config(chain_id=1)
    chain = Blockchain(Storages(), cfg)
    builder = ChainBuilder(chain, cfg, spec)
    rng = np.random.default_rng(seed)
    token = contract_address(senders[0], 0)
    coinbase = b"\xaa" * 20
    nonces = [0] * txs_per_block
    touched = set()
    out = []
    for n in range(blocks):
        picks = rng.integers(0, len(others), txs_per_block)
        txs = []
        for j in range(txs_per_block):
            rcpt = others[int(picks[j])]
            if n == 0 and j == 0:
                tx = Transaction(0, 10**9, 500_000, None, 0,
                                 payload=_ERC20_INIT)
            elif n > 0 and j < txs_per_block // 2:
                amount = 1_000 + 13 * j + n
                tx = Transaction(
                    nonces[j], 10**9, 100_000, token, 0,
                    payload=rcpt.rjust(32, b"\x00")
                    + amount.to_bytes(32, "big"),
                )
            else:
                tx = Transaction(nonces[j], 10**9, 21_000, rcpt,
                                 1_000 + n)
                touched.add(rcpt)
            txs.append(sign_transaction(tx, keys[j], chain_id=1))
            nonces[j] += 1
        out.append(builder.add_block(txs, coinbase=coinbase))
    # through wire RLP, as a peer or the JVM side would send them
    wire = [Block.decode(b.encode()) for b in out]
    return chain, wire, token, sorted(touched)


# process-wide fall-back counters: zero in a fresh process (main), so
# the audit compares against their value when the node booted
FALLBACK_COUNTERS = (
    "khipu_window_fused_fallbacks",
    "khipu_pipeline_sync_fallback_windows",
    "khipu_mirror_unspilled_evictions",
    "khipu_fused_async_copy_fallbacks",
)


class Node:
    """The booted node of the replay/serve legs: board, bridge client,
    and the host-built chain it must agree with."""

    def __init__(self, board, client, rpc_port, builder_chain, blocks,
                 token, touched, senders, others):
        from khipu_tpu.observability.registry import REGISTRY

        reg = REGISTRY.snapshot()
        self.counters_at_boot = {k: reg.get(k, 0) for k in FALLBACK_COUNTERS}
        self.board = board
        self.client = client
        self.rpc_port = rpc_port
        self.builder_chain = builder_chain
        self.blocks = blocks
        self.token = token
        self.touched = touched
        self.senders = senders
        self.others = others

    def shutdown(self) -> None:
        self.client.close()
        self.board.shutdown()


def leg_replay(cfg, spec, keys, senders, others, blocks: int = BLOCKS,
               txs_per_block: int = TXS_PER_BLOCK,
               batch_blocks: int = BATCH_BLOCKS, seed: int = 3):
    """Boot the node on the state leg's data dir, start the bridge
    (device commit) and RPC, stream the host-built chain through
    ``BridgeClient`` in batches; every returned root must equal the
    builder's header root. Returns (values, Node)."""
    from khipu_tpu.bridge import BridgeClient
    from khipu_tpu.service_board import ServiceBoard

    t0 = time.perf_counter()
    builder_chain, wire, token, touched = build_chain(
        spec, keys, senders, others, blocks, txs_per_block, seed
    )
    build_s = time.perf_counter() - t0

    board = ServiceBoard(cfg)  # reopens the persisted genesis
    genesis = board.blockchain.get_header_by_number(0)
    want0 = builder_chain.get_header_by_number(0)
    if genesis is None or genesis.hash != want0.hash:
        raise AssertionError("node did not reopen the persisted genesis")
    bridge_port = board.start_bridge(port=0, device_commit=True)
    rpc_port = board.start_rpc(port=0)
    client = BridgeClient(f"127.0.0.1:{bridge_port}")
    node = Node(board, client, rpc_port, builder_chain, wire, token,
                touched, senders, others)

    t0 = time.perf_counter()
    roots = 0
    for lo in range(0, len(wire), batch_blocks):
        batch = wire[lo : lo + batch_blocks]
        reply = client.execute_blocks(batch)
        if [n for n, _ in reply] != [b.number for b in batch]:
            raise AssertionError("bridge reply numbers differ from batch")
        for (number, root), block in zip(reply, batch):
            want = builder_chain.get_header_by_number(number).state_root
            if root != want or block.header.state_root != want:
                raise AssertionError(f"block {number}: root mismatch")
            if client.get_state_root(number) != want:
                raise AssertionError(f"block {number}: stored root differs")
            roots += 1
    replay_s = time.perf_counter() - t0
    head, head_hash = client.best_block()
    if head != wire[-1].number or head_hash != wire[-1].hash:
        raise AssertionError("bridge head differs from the built chain")
    windows = -(-len(wire) // cfg.sync.commit_window_blocks)
    return {
        "blocks": len(wire), "txs": sum(
            len(b.body.transactions) for b in wire),
        "roots_checked": roots, "windows": windows,
        "chain_build_s": round(build_s, 3),
        "bridge_replay_s": round(replay_s, 3),
    }, node


# ------------------------------------------ leg: the device did the work


def leg_device_work(node: Node, windows: int, backend: str,
                    adaptive: bool) -> dict:
    """From the registry, the movement ledger, the span ring and the
    compile log: the replay's windows were hashed on the device by the
    ``backend`` kernel, nothing fell back, and the native host pieces
    are loaded. Prints every value it asserts on."""
    import khipu_tpu.base.rlp as rlp_mod
    from khipu_tpu.evm import native_vm
    from khipu_tpu.native import keccak as native_keccak
    from khipu_tpu.native.build import load_rlp_ext
    from khipu_tpu.observability.profiler import D2H, H2D, LEDGER
    from khipu_tpu.observability.recorder import compile_log
    from khipu_tpu.observability.registry import REGISTRY

    reg = REGISTRY.snapshot()
    values = {k: reg[k] for k in FALLBACK_COUNTERS}
    if adaptive:
        for k in ("khipu_adaptive_device_mode",
                  "khipu_adaptive_flips_total",
                  "khipu_adaptive_windows_observed",
                  "khipu_adaptive_probe_d2d_bytes_per_s",
                  "khipu_adaptive_probe_memcpy_bytes_per_s",
                  "khipu_adaptive_ewma_device_hash_s",
                  "khipu_adaptive_ewma_host_hash_s",
                  "khipu_adaptive_depth_hint"):
            values[k] = reg[k]
    spans = [s for s in node.board.tracer.snapshot()
             if s.name == "fused.dispatch"]
    values["fused_dispatch_spans"] = len(spans)
    values["fused_dispatch_backends"] = sorted(
        {s.tags.get("backend") for s in spans})
    values["fused_dispatch_nodes"] = sum(
        int(s.tags.get("nodes", 0)) for s in spans)
    clog = compile_log.snapshot()
    values["fused_signatures_compiled"] = clog["misses"]
    values["fused_compile_s"] = round(sum(
        e.get("compile_s", 0.0) for e in clog["events"]), 3)
    values["fused_signature_backends"] = sorted({
        e["signature"].split("backend=")[1].split()[0]
        for e in clog["events"]})
    totals = LEDGER.totals()
    for site, direction in (("seal.upload", H2D), ("seal.rootcheck", D2H)):
        agg = totals.get((site, direction), {"bytes": 0, "count": 0})
        values[f"{site}.{direction}.bytes"] = agg["bytes"]
        values[f"{site}.{direction}.count"] = agg["count"]
    ext = load_rlp_ext()
    values["native_keccak"] = native_keccak.available()
    values["native_evm"] = native_vm.available()
    values["rlp_is_c"] = ext is not None and rlp_mod.rlp_encode is ext.encode
    for k, v in values.items():
        log(f"  {k} = {v}")

    for k in FALLBACK_COUNTERS:
        since_boot = values[k] - node.counters_at_boot[k]
        if since_boot != 0:
            raise AssertionError(f"{k} rose by {since_boot}, want 0")
    if adaptive and (values["khipu_adaptive_device_mode"] != 1
                     or values["khipu_adaptive_flips_total"] != 0):
        raise AssertionError("adaptive controller left device mode")
    if len(spans) < windows:
        raise AssertionError(
            f"{len(spans)} fused dispatches for {windows} windows")
    if values["fused_dispatch_backends"] != [backend] or \
            values["fused_signature_backends"] != [backend]:
        raise AssertionError(f"fused dispatches did not all run {backend}")
    if values[f"seal.upload.{H2D}.bytes"] <= 0 or \
            values[f"seal.upload.{H2D}.count"] < windows:
        raise AssertionError("seal.upload moved no bytes on some window")
    if values[f"seal.rootcheck.{D2H}.bytes"] <= 0:
        raise AssertionError("seal.rootcheck fetched nothing")
    for k in ("native_keccak", "native_evm", "rlp_is_c"):
        if not values[k]:
            raise AssertionError(f"{k} is not loaded")
    return values


# ----------------------------------------------------------- leg: serve


def _rpc(port: int, method: str, *params):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}",
        data=json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                         "params": list(params)}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        out = json.loads(resp.read())
    if "error" in out:
        raise AssertionError(f"{method}: {out['error']}")
    return out["result"]


def leg_serve(node: Node, gauges: dict, seed: int = 5) -> dict:
    """Over HTTP against the same node: head, balances of touched and
    untouched accounts, token balances and an ``eth_call`` equal what
    the builder's chain holds; ``khipu_metrics`` carries the gauges."""
    from khipu_tpu.base.crypto.keccak import keccak256

    rng = np.random.default_rng(seed)
    chain = node.builder_chain
    head = chain.get_header_by_number(chain.best_block_number)
    port = node.rpc_port
    hx = lambda b: "0x" + b.hex()

    if int(_rpc(port, "eth_blockNumber"), 16) != head.number:
        raise AssertionError("eth_blockNumber differs from the built head")

    touched = set(node.touched)
    untouched = [a for a in node.others if a not in touched]
    sample = (
        [node.touched[int(i)] for i in
         rng.choice(len(node.touched), min(10, len(node.touched)),
                    replace=False)]
        + [untouched[int(i)] for i in
           rng.choice(len(untouched), min(10, len(untouched)),
                      replace=False)]
        + list(node.senders[:2])
    )
    for addr in sample:
        acc = chain.get_account(addr, head.state_root)
        got = int(_rpc(port, "eth_getBalance", hx(addr), "latest"), 16)
        if got != (acc.balance if acc else 0):
            raise AssertionError(f"eth_getBalance({addr.hex()}) differs")

    # the fixture token (_ERC20_RUNTIME) is transfer-only — it
    # has no balanceOf getter — so a holder's balance is read where
    # balanceOf would read it: mapping slot keccak(pad32(holder) ++ 0)
    world = chain.get_world_state(head.state_root)
    def slot_of(holder: bytes) -> int:
        return int.from_bytes(
            keccak256(holder.rjust(32, b"\x00") + bytes(32)), "big")

    credited = (a for a in node.others
                if world.get_storage(node.token, slot_of(a)))
    holders = list(node.senders[:5]) + list(itertools.islice(credited, 5))
    nonzero = 0
    for holder in holders:
        slot = slot_of(holder)
        want = world.get_storage(node.token, slot)
        got = int(_rpc(port, "eth_getStorageAt", hx(node.token),
                       hex(slot), "latest"), 16)
        if got != want:
            raise AssertionError(f"token balance of {holder.hex()} differs")
        nonzero += want != 0
    if node.blocks[-1].number > 1 and nonzero == 0:
        raise AssertionError("no token balance was ever written")
    code = _rpc(port, "eth_getCode", hx(node.token), "latest")
    if bytes.fromhex(code[2:]) != world.get_code(node.token):
        raise AssertionError("token code differs")
    # eth_call runs the EVM over the replayed state: a transfer dry run
    call = {"from": hx(node.senders[1]), "to": hx(node.token),
            "data": hx(node.others[0].rjust(32, b"\x00")
                       + (7).to_bytes(32, "big"))}
    if _rpc(port, "eth_call", call, "latest") != "0x":
        raise AssertionError("eth_call on the token returned data")

    reg = _rpc(port, "khipu_metrics")["registry"]
    for k, v in gauges.items():
        if k.startswith("khipu_") and reg.get(k) != v:
            raise AssertionError(f"khipu_metrics {k} = {reg.get(k)} != {v}")
    return {"head": head.number, "balances": len(sample),
            "token_balances": len(holders),
            "token_balances_nonzero": int(nonzero)}


# -------------------------------------------------------- leg: snapshot


def leg_snapshot(raw: np.ndarray, digests: np.ndarray) -> dict:
    """BASELINE config #5 on one chip: every row admitted into the
    device mirror's exact-length class under its host-computed claim,
    ``verify() == 0``, and one forged claim counted as exactly 1."""
    import jax
    import jax.numpy as jnp

    from khipu_tpu.ops.keccak_jnp import RATE
    from khipu_tpu.storage.device_mirror import DeviceNodeMirror

    n, width = raw.shape
    mirror = DeviceNodeMirror(capacity_rows_per_class=n)
    hashes = [digests[i].tobytes() for i in range(n)]
    mirror.admit_packed(hashes, raw, [width] * n, exact=True)
    if mirror.resident_count != n:
        raise AssertionError(f"{mirror.resident_count} resident, want {n}")
    bad = mirror.verify()
    if bad != 0:
        raise AssertionError(f"snapshot verify: {bad} mismatches")
    cm = mirror._classes[(width // RATE + 1, width)]
    poisoned = cm.claimed.at[0, 0, 0, 0].add(jnp.uint32(1))
    forged = int(jax.device_get(cm._verify(cm.resident, poisoned)))
    if forged != 1:
        raise AssertionError(f"forged claim counted {forged}, want 1")
    return {"resident": n, "mismatches": bad, "forged_detected": forged}


# ------------------------------------------------------- leg: multichip


def leg_multichip(rows: int = KERNEL_ROWS,
                  session_keys: int = MULTICHIP_SESSION_KEYS) -> dict:
    """On >= 4 devices: __graft_entry__'s sharded hash + all_gather +
    psum step and the sharded fused finalize over a mesh of four real
    devices, bit-exact against the host, inputs and gathered table on
    four distinct device ids. Fewer devices: not run — never a virtual
    mesh."""
    import jax

    n = len(jax.devices())
    if n < 4:
        log(f"multichip: not run ({n} device)")
        return {"ran": False, "devices": n}
    from __graft_entry__ import multichip_check

    return {"ran": True,
            **multichip_check(4, rows, NODE_BYTES, session_keys)}


# ----------------------------------------------------------------- main


def result_line(stamp: dict) -> str:
    """The last stdout line of a passing run. The chip check reads it
    and holds it to exactly these keys — detail goes on the line before,
    never in here."""
    return json.dumps({"ok": True, "device": {
        "platform": str(stamp["platform"]),
        "kind": str(stamp["kind"]),
        "count": int(stamp["count"]),
    }})


def main() -> int:
    import jax

    devices = jax.devices()
    stamp = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    log(f"chip_smoke: platform={stamp['platform']} "
        f"device_kind={stamp['kind']} count={stamp['count']}")
    if stamp["platform"] != "tpu":
        print("chip_smoke: no TPU — this smoke only means something on "
              "the chip; run it through the chip tool", file=sys.stderr)
        return 2

    from khipu_tpu import device

    cache_dir = device.place_compile_cache()
    meter = CompileMeter()
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    data_dir = os.path.join(OUT_DIR, "node")
    adaptive = ADAPTIVE != "pinned-device"
    walls: Dict[str, float] = {}
    legs: Dict[str, dict] = {}

    def run(name, fn, *args, **kw):
        meter.leg = name
        log(f"[{name}] start")
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        walls[name] = round(time.perf_counter() - t0, 3)
        log(f"[{name}] ok in {walls[name]} s")
        return out

    raw, digests = run("fixture", make_nodes, KERNEL_ROWS, 0)
    legs["kernel"] = run("kernel", leg_kernel, raw, digests)
    if legs["kernel"]["impl"] != "pallas":
        raise AssertionError("kernel leg did not run the Pallas kernel")

    keys, senders, others, alloc = make_alloc(ACCOUNTS, TXS_PER_BLOCK)
    cfg = smoke_config(data_dir, adaptive_commit=adaptive)
    state = run("state", leg_state, alloc, data_dir, cfg)
    spec = state.pop("spec")
    legs["state"] = state

    legs["replay"], node = run(
        "replay", leg_replay, cfg, spec, keys, senders, others)
    try:
        legs["device_work"] = run(
            "device_work", leg_device_work, node,
            legs["replay"]["windows"], "pallas", adaptive)
        legs["serve"] = run("serve", leg_serve, node, legs["device_work"])
    finally:
        node.shutdown()

    legs["snapshot"] = run("snapshot", leg_snapshot, raw, digests)
    del raw, digests
    legs["multichip"] = run("multichip", leg_multichip)
    if legs["multichip"].get("resolve_nodes", 10_000) < 10_000:
        raise AssertionError("sharded resolve ran under 10,000 nodes")
    shutil.rmtree(data_dir, ignore_errors=True)

    detail = {
        "device": stamp,
        "adaptive": ADAPTIVE,
        "leg_wall_s": walls,
        "legs": legs,
        "compile": meter.report(),
        "compile_cache": {
            "dir": cache_dir,
            "hits": meter.cache_hits,
            "misses": meter.cache_misses,
            "hit": meter.cache_hits > 0,
        },
    }
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(detail, f, indent=1)
    log("chip_smoke: detail " + json.dumps(detail))
    print(result_line(stamp), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
