#!/usr/bin/env python3
"""Which fused-program signatures a sync cell's chain meets, and when,
at several commit windows, in ONE process.

    python3 benchmark/tools/signature_census.py --workload <sync cell> \
        --seed <n> --windows 3,2,4,5 --blocks 210

For each window w (batch = 5 windows, as the cells are shaped) a node is
booted on a fresh copy of the seed's genesis dir and the first
``--blocks`` blocks of the seed's chain are sent through the bridge. Every
fused dispatch lands in the program's compile log with its signature
(a hit or a miss), so the census is read from there: per batch the
signatures met for the first time at this window, and at the end each
signature's number of dispatches and the block at which it first came,
and (from the ``fused.dispatch`` spans' ``live`` and ``ext_live`` tags,
where the program writes them) the least and most of every count the
signature buckets: how far the window sits from each bucket's edge.
A window value is steady when its list stops growing early. One process:
the seed's data is made once and a signature compiled for one window is
not compiled again for the next. Not a measurement of any rate.

``--cpu`` runs the cell's full sizes with JAX held to the CPU: the counts
before bucketing are the workload's and come out the same there, the
signatures do not (the CPU path buckets rows to powers of two from 16, the
chip to whole Pallas tiles). So the census also lays the chip's own
buckets over the counts, window by window through a ``HeldBuckets`` of its
own as the replay driver does, and prints each change of that signature
with the block it came at (``census: on the chip ...``): a window value
is steady on the chip when the last change comes inside warm-up.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import chip, manifest  # noqa: E402


class ChipSignature:
    """The signature the Pallas-backed program would be asked for, from
    a window's counts before bucketing: the program's own bucket rules
    (`trie/fused.py` `_fused_submit`) through a `HeldBuckets`."""

    def __init__(self):
        from khipu_tpu.trie.fused import HeldBuckets

        self.held = HeldBuckets()
        self.last = None

    def window(self, counts: dict):
        """The signature's dimensions that changed with this window
        (the whole signature for the first), or None."""
        from khipu_tpu.ops.keccak_pallas import _pallas_target_count
        from khipu_tpu.trie.fused import _pow2

        sig = {}
        for nb in sorted({int(k.split(".")[0]) for k in counts if "." in k}):
            sig[f"{nb}.rows"] = self.held.take(
                (nb, "rows"),
                _pallas_target_count(nb, counts[f"{nb}.rows"] + 1))
            sig[f"{nb}.subs"] = self.held.take(
                (nb, "subs"), _pow2(counts[f"{nb}.subs"] + 1, floor=4096))
        sig["ext"] = self.held.take(("ext",), _pow2(counts["ext"], floor=64))
        last, self.last = self.last, sig
        if last is None:
            return " ".join(f"{k}={v}" for k, v in sig.items())
        diff = [f"{k} {last[k]}->{v}" for k, v in sig.items() if last[k] != v]
        return ", ".join(diff) or None


def census(env, driver, data, window: int, n_blocks: int) -> None:
    from khipu_tpu.bridge import BridgeClient
    from khipu_tpu.domain.block import Block
    from khipu_tpu.observability.recorder import compile_log
    from khipu_tpu.service_board import ServiceBoard

    program = dict(env.config["program"])
    program["sync"] = dict(program["sync"], commit_window_blocks=window)
    batch = 5 * window
    node_dir = os.path.join(env.run_dir, f"node-w{window}")
    shutil.copytree(data["genesis_dir"], node_dir)
    board = ServiceBoard(driver.node_config(node_dir, program, observe=True))
    client = None
    try:
        port = board.start_bridge(port=0, **program["bridge"])
        client = BridgeClient(f"127.0.0.1:{port}")
        blocks = [Block.decode(w) for w in data["wire"][:n_blocks]]
        roots, failures = data["roots"], []
        seen = {}  # signature -> [first block, dispatches]
        cursor = len(compile_log.snapshot()["events"])
        for lo in range(0, len(blocks), batch):
            hi = min(lo + batch, len(blocks))
            driver.send(client, blocks, roots, lo, hi, failures)
            if failures:
                raise RuntimeError(f"replay failed: {failures[:2]}")
            events = compile_log.snapshot()["events"]
            new = []
            for e in events[cursor:]:
                if e["kind"] not in ("hit", "miss"):
                    continue
                if e["signature"] not in seen:
                    seen[e["signature"]] = [hi, 0]
                    new.append(f"{e['signature']}"
                               f" ({e.get('compile_s', 0.0):.1f} s)")
                seen[e["signature"]][1] += 1
            cursor = len(events)
            env.log(f"census w={window}: blocks {lo + 1}..{hi}: "
                    f"{len(new)} new of {len(seen)}"
                    + "".join(f"\n    + {s}" for s in new))
        print(f"census: window {window} batch {batch}, {len(blocks)} blocks, "
              f"{len(seen)} signatures", flush=True)
        for sig, (first, count) in sorted(seen.items(), key=lambda kv: kv[1]):
            print(f"census:   first by block {first:4d}, {count:3d} "
                  f"dispatches: {sig}", flush=True)
        ranges = {}  # count's name -> [least, most] before its bucket
        dispatches = [s for s in board.tracer.snapshot()
                      if s.name == "fused.dispatch" and "live" in s.tags]
        dispatches.sort(key=lambda s: s.t0)
        chip = ChipSignature()
        for i, s in enumerate(dispatches):
            counts = {"ext": int(s.tags["ext_live"]),
                      "rounds": int(s.tags["rounds"])}
            for part in s.tags["live"].split(","):
                nb, rest = part.split("x")
                rows, rest = rest.split("/")
                subs, admit = rest.split("+a")
                counts.update({f"{nb}.rows": int(rows), f"{nb}.subs":
                               int(subs), f"{nb}.admit": int(admit)})
            for k, v in counts.items():
                lo, hi = ranges.get(k, (v, v))
                ranges[k] = (min(lo, v), max(hi, v))
            changed = chip.window(counts)
            if changed:
                print(f"census:   on the chip, by block {(i + 1) * window}"
                      f": {changed}", flush=True)
        print("census:   least..most before bucketing: " + " ".join(
            f"{k}={lo}..{hi}" for k, (lo, hi) in sorted(ranges.items())),
            flush=True)
    finally:
        if client is not None:
            client.close()
        board.shutdown()
        shutil.rmtree(node_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", default="3,2,4,5")
    ap.add_argument("--blocks", type=int, default=210)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="full sizes with JAX on the CPU: counts only")
    args = ap.parse_args(argv)
    args.seconds, args.trace, args.control = 0, 0, None
    cell = manifest.cell(args.workload)
    if args.rehearse or args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    device = chip.require(int(cell["chips"]), args.rehearse or args.cpu)
    chip.place_compile_cache()
    env = bench_run.Env(args, cell, device)
    driver = manifest.load_module("drivers", env.config["driver"])
    data = driver.seed_data(env, env.config["sizes"], env.traffic)
    driver.rest_of_chain(env, data)
    for window in (int(w) for w in args.windows.split(",")):
        census(env, driver, data, window, args.blocks)
    shutil.rmtree(env.run_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
