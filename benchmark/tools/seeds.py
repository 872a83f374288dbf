#!/usr/bin/env python3
"""Several runs of one cell in ONE process, one seed after another.

    python3 benchmark/tools/seeds.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--control <name>] [--expect-correct 0|1]

One process holds the chip and the programs compile once, so reading
`correct` over many seeds (or a control over three) costs each seed only
its own data and window. Every run is ``run.py``'s own ``main``; its
output is printed as it comes, each run under a ``seeds:`` header line.
Not a measurement of ``setup_s`` (later runs find the process warm) and
not what the driver calls. Exits 1 if a run's ``correct`` is not what
``--expect-correct`` says.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time
from contextlib import redirect_stdout

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as bench_run  # noqa: E402


class Tee(io.StringIO):
    def write(self, text):
        sys.__stdout__.write(text)
        sys.__stdout__.flush()
        return super().write(text)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--expect-correct", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    wrong = 0
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        argv_run = ["--workload", args.workload, "--seed", str(seed),
                    "--seconds", str(args.seconds), "--trace", str(args.trace)]
        if args.control:
            argv_run += ["--control", args.control]
        if args.rehearse:
            argv_run.append("--rehearse")
        print(f"seeds: {args.workload} seed {seed} control {args.control}",
              flush=True)
        bench_run.T_PROC0 = time.perf_counter()
        out = Tee()
        with redirect_stdout(out):
            bench_run.main(argv_run)
        line = json.loads(out.getvalue().strip().splitlines()[-1])
        if bool(line["correct"]) != bool(args.expect_correct):
            wrong += 1
            print(f"seeds: seed {seed}: correct={line['correct']}, expected "
                  f"{bool(args.expect_correct)}", flush=True)
    print(f"seeds: {wrong} run(s) not as expected", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
