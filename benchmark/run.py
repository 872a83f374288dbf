#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, checks what the timed path
produced, and prints one JSON object as the last line of stdout (the
contract is in benchmark/README.md). Exits non-zero with no result line
unless a TPU with the cell's chips is attached; ``--rehearse`` runs the
configuration's tiny rehearsal sizes on whatever JAX finds (the CPU,
here) and is never a measurement. ``--list`` prints what the harness
finds in its directories. ``--control <name>`` runs the cell with one
guarantee broken underneath (see PERF.md); `correct` must come out false.
"""

from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from benchmark.lib import manifest  # noqa: E402
from benchmark.lib.outcome import print_checks, result_line  # noqa: E402


class Env:
    """What a driver is handed."""

    def __init__(self, args, cell, device):
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.control = args.control
        self.device = device
        self.t_proc0 = T_PROC0
        self.config = merged(cell["config_file"], args.rehearse)
        self.traffic = merged(cell["traffic_file"], args.rehearse)
        tag = "rehearse" if args.rehearse else "full"
        self.cache_dir = os.path.join(
            BENCH_DIR, "cache", cell["config"], tag)
        self.run_dir = os.path.join(BENCH_DIR, "cache", "_run")
        shutil.rmtree(self.run_dir, ignore_errors=True)
        os.makedirs(self.run_dir)
        os.makedirs(self.cache_dir, exist_ok=True)

    def log(self, msg: str) -> None:
        print(f"[{time.perf_counter() - T_PROC0:8.2f}] {msg}", flush=True)

    def trace_window(self):
        from benchmark.lib.tracewin import TraceWindow

        return TraceWindow(os.path.join(self.run_dir, "trace"))


def merged(doc: dict, rehearse: bool) -> dict:
    """The file as run: with ``--rehearse`` its ``rehearse`` block is
    laid over the top-level keys (one level deep)."""
    out = {k: v for k, v in doc.items() if k != "rehearse"}
    if rehearse:
        for k, v in doc.get("rehearse", {}).items():
            out[k] = {**out.get(k, {}), **v} if isinstance(v, dict) else v
    return out


def per_layer(cell_name: str, outcome) -> dict:
    """Each per-layer metric of this cell through its reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in manifest.metrics_for(cell_name, "per_layer"):
        spec = manifest.metric_file(m["name"])
        reader = manifest.load_module("readers", spec["reader"])
        value = reader.read(outcome.artefacts, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default=None)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)
    if args.list:
        print(json.dumps(manifest.listing(), indent=1))
        return 0
    if not args.workload:
        ap.error("--workload is required")
    cell = manifest.cell(args.workload)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from benchmark.lib import chip

    device = chip.require(int(cell["chips"]), args.rehearse)
    chip.place_compile_cache()
    env = Env(args, cell, device)
    driver = manifest.load_module("drivers", env.config["driver"])
    outcome = driver.run(env)
    print_checks(outcome.checks)
    outcome.artefacts["device_kind"] = device["kind"]

    device = dict(device, memory_peak_bytes=chip.memory_peak_bytes())
    breakdown = None
    if env.trace:
        from benchmark.readers import trace_share

        reduced = trace_share.reduced(outcome.artefacts)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            if reduced["cut"]:
                env.log("trace: device events stop early; judged over "
                        f"the {reduced['window_s']:.3f} s they cover")
            env.log("programs by device time: "
                    + json.dumps(reduced["programs"]))
        metrics = per_layer(cell["name"], outcome)
    else:
        units = {m["name"]: m["unit"]
                 for m in manifest.metrics_for(cell["name"], "end_to_end")}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in outcome.end_to_end.items()}
    shutil.rmtree(env.run_dir, ignore_errors=True)
    print(result_line(outcome, metrics, device, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
