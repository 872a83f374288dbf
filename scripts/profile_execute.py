#!/usr/bin/env python3
"""Where ``execute_block`` spends a block, for a sync cell's own traffic.

    python3 <repo>/scripts/profile_execute.py --workload sync.contracts \
        [--accounts 20000] [--warmup 10] [--blocks 4] [--seed 39000001]

Profiles the checkout it is started in (the working tree, or a copy of
another commit: a parent without ``khipu_trie_*`` prints no counters).
Builds the cell's state and blocks with the benchmark's own generators
(the cell's configuration and traffic files, found through
``BENCHMARK.json``) at ``--accounts`` accounts, the storage slots cut in
the same proportion (0: the cell's full size, which takes its set-up's
minutes and memory), and runs them through ``ChainBuilder`` as the
benchmark's chain children do. ``cProfile`` is on around
``execute_block`` for the last ``--blocks`` blocks, after ``--warmup``
blocks without it. Prints, a block: the trie's look-ups (count, us
each, ms), ``TrieStorage.key_bytes``, every ``keccak256`` through
ctypes, ``BlockWorldState.copy``, the planner, the two batch lanes,
``bloom_of_logs``, the blocks' own ``Stats`` (lanes, the parts outside
them, world copies) and ``khipu_trie_*`` over the profiled blocks.

Host Python is host Python: shares carry from a sandbox to the chip's
host, rates do not, and cProfile itself inflates what is made of many
small calls. Sender recovery is left out (the transactions are signed
here, so their senders are known; replay's prefetcher hides it).
Touches nothing a cell runs.
"""

import argparse
import cProfile
import importlib
import json
import os
import pstats
import sys
import time

ROOT = os.getcwd()

# (row label, the functions whose cumulative time it sums)
ROWS = [
    ("MerklePatriciaTrie.get/get_hashed", [
        "khipu_tpu.trie.mpt:MerklePatriciaTrie.get",
        "khipu_tpu.trie.mpt:MerklePatriciaTrie.get_hashed"]),
    ("  of it, in _resolve", ["khipu_tpu.trie.mpt:MerklePatriciaTrie._resolve"]),
    ("TrieStorage.key_bytes", ["khipu_tpu.ledger.world:TrieStorage.key_bytes"]),
    ("keccak256 through ctypes", ["khipu_tpu.native.keccak:keccak256"]),
    ("BlockWorldState.copy", ["khipu_tpu.ledger.world:BlockWorldState.copy"]),
    ("plan_block", ["khipu_tpu.ledger.schedule:plan_block"]),
    ("execute_call_batch", ["khipu_tpu.ledger.batch_call:execute_call_batch"]),
    ("execute_fast_batch", ["khipu_tpu.ledger.batch_exec:execute_fast_batch"]),
    ("bloom_of_logs", ["khipu_tpu.ledger.bloom:bloom_of_logs"]),
    ("recover_senders (a cell's replay has done it before)",
     ["khipu_tpu.domain.transaction:recover_senders"]),
    ("execute_block", ["khipu_tpu.ledger.ledger:execute_block"]),
]


def code_key(dotted: str):
    """cProfile's key of ``module:attr.attr``, None where this checkout
    has no such function."""
    module, _, path = dotted.partition(":")
    try:
        obj = importlib.import_module(module)
        for part in path.split("."):
            obj = getattr(obj, part)
    except (ImportError, AttributeError):
        return None
    code = getattr(obj, "__func__", obj).__code__
    return (code.co_filename, code.co_firstlineno, code.co_name)


def scaled(sizes: dict, accounts: int) -> dict:
    if not accounts or accounts >= sizes["accounts"]:
        return dict(sizes)
    share = accounts / sizes["accounts"]
    out = dict(sizes, accounts=accounts)
    if "token_slots" in sizes:
        out["token_slots"] = max(
            int(sizes["token_slots"] * share),
            4 * int(sizes["token_contracts"]))
    return out


def blocks_of(generator: str, sizes: dict, params: dict, blocks: int,
              seed: int):
    """build(): the cell's generator over ``blocks`` blocks, through
    ChainBuilder."""
    from benchmark.drivers.sync import GAS_LIMIT
    from khipu_tpu.domain.blockchain import GenesisSpec

    txs = int(sizes["txs_per_block"])
    if generator == "chain":
        from benchmark.generators import accounts as gen_accounts
        from benchmark.generators import chain as gen

        keys, senders, others, _extra, alloc = gen_accounts.make_alloc(
            sizes["accounts"], sizes["funded_senders"], seed)
        spec = GenesisSpec(alloc=alloc, gas_limit=GAS_LIMIT)
        picks = gen.draw(params, blocks, txs, len(others), seed)
        return lambda: gen.build(spec, keys, senders, others, picks)
    if generator == "chain_state":
        from benchmark.generators import chain_state as gen
        from benchmark.generators import state as gen_state

        state = gen_state.make_state(sizes, seed)
        picks = gen.draw(params, blocks, txs, len(state["others"]),
                         state["holders"], seed)
    elif generator == "chain_contracts":
        from benchmark.generators import chain_contracts as gen
        from benchmark.generators import contracts as gen_state

        state = gen_state.make_state(sizes, seed)
        picks = gen.draw(params, blocks, txs, len(state["others"]),
                         state["holders"], len(state["pairs"]), seed)
    else:
        raise SystemExit(f"no chain generator {generator!r}")
    spec = GenesisSpec(alloc=state["alloc"], gas_limit=GAS_LIMIT)
    return lambda: gen.build(spec, state, picks)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--accounts", type=int, default=20_000)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--blocks", type=int, default=4)
    ap.add_argument("--seed", type=int, default=39_000_001)
    ap.add_argument("--plain", action="store_true",
                    help="no cProfile: the wall time and khipu_trie_* only")
    ap.add_argument("--top", type=int, default=0,
                    help="also print cProfile's top N by cumulative time")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    conf_file = next(c["file"] for c in bench["configs"]
                     if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf_file)) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    sizes = scaled(conf["sizes"], args.accounts)

    from khipu_tpu.base import rlp
    from khipu_tpu.sync import chain_builder
    from khipu_tpu.trie import mpt

    rlp._bind_rlp_ext(forwarded=True)  # not the Python walk by a race
    trie_read_samples = getattr(mpt, "trie_read_samples", list)
    prof = cProfile.Profile()
    inner = chain_builder.execute_block
    seen = {"blocks": 0, "wall": 0.0, "txs": 0, "before": None}
    booked: dict = {}  # the blocks' own Stats: lanes, parts, copies

    def execute_block(block, *a, **kw):
        seen["blocks"] += 1
        if seen["blocks"] <= args.warmup:
            return inner(block, *a, **kw)
        if seen["before"] is None:
            seen["before"] = trie_read_samples()
        seen["txs"] += len(block.body.transactions)
        t0 = time.perf_counter()
        if not args.plain:
            prof.enable()
        try:
            result = inner(block, *a, **kw)
        finally:
            if not args.plain:
                prof.disable()
            seen["wall"] += time.perf_counter() - t0
        st = result.stats
        for k, secs in {**st.lane_seconds,
                        **getattr(st, "part_seconds", {})}.items():
            booked[k + "_s"] = booked.get(k + "_s", 0.0) + secs
        booked["copies"] = booked.get("copies", 0) + getattr(st, "copies", 0)
        booked["copy_ms (inside the lanes)"] = booked.get(
            "copy_ms (inside the lanes)", 0.0
        ) + 1000 * getattr(st, "copy_seconds", 0.0)
        return result

    chain_builder.execute_block = execute_block
    t0 = time.perf_counter()
    build = blocks_of(traffic["generator"], sizes, traffic["params"],
                      args.warmup + args.blocks, args.seed)
    print(f"{args.workload}: sizes {sizes}, state drawn in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    build()
    n = args.blocks
    print(f"{n} blocks after {args.warmup} of warm-up, "
          f"{seen['txs'] / n:.0f} txs a block, "
          f"execute_block {1000 * seen['wall'] / n:.1f} ms a block"
          f"{'' if args.plain else ' under cProfile'}; walk: "
          f"{'native' if getattr(rlp, 'native_ext', None) else 'python'}")
    if not args.plain:
        # {(file, line, name): (cc, nc, tt, ct, callers)}
        stats = pstats.Stats(prof).stats
        print(f"{'inside execute_block':<36}{'calls/blk':>10}{'us/call':>10}"
              f"{'ms/blk':>10}")
        for label, funcs in ROWS:
            found = [stats[k] for k in map(code_key, funcs) if k in stats]
            calls = sum(f[1] for f in found)
            cum = sum(f[3] for f in found)
            each = 1e6 * cum / calls if calls else 0.0
            print(f"{label:<36}{calls / n:>10.1f}{each:>10.2f}"
                  f"{1000 * cum / n:>10.2f}")
    named = sum(v for k, v in booked.items() if k.endswith("_s"))
    print("the blocks' own Stats, ms a block (a parent has no parts): "
          + ", ".join(f"{k} {(1000 if k.endswith('_s') else 1) * v / n:.3f}"
                      for k, v in booked.items() if v)
          + f"; no lane or part (here mostly recover_senders, which a "
          f"cell's replay has done before execute): "
          f"{1000 * (seen['wall'] - named) / n:.3f}")
    after = trie_read_samples()
    print("khipu_trie_* over the profiled blocks, a block:")
    for (name, _k, labels, a), (_n, _k2, _l, b) in zip(
            after, seen["before"]):
        print(f"  {name}{labels or ''}: {(a - b) / n:.6g}")
    if args.top and not args.plain:
        pstats.Stats(prof).sort_stats("cumulative").print_stats(args.top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
