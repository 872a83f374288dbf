#!/usr/bin/env python3
"""PR 41, the observer runs of call p41e: what each observer of a traced
``snap.statesync`` window costs. ``run.py`` of the checkout it is started
in, with one of

    MODE=none   --trace 0 as the driver runs it
    MODE=spans  --trace 0, the program's span ring on around ``sync_once``
    MODE=prof   --trace 1, the span ring left off (``tracer.enable`` a no-op)
    MODE=both   --trace 1 as the driver runs it

and, before the result line, one line ``observer: {...}``: the window's
and the loop's seconds and every per-layer metric of the cell that reads
without a device trace, so that the four modes read the same names.
Edits nothing under ``benchmark/``; `--trace` on the command line is
overridden by the mode.

    MODE=prof python3 benchmark/tools/calls/pr41-d.py \
        --workload snap.statesync --seed <n> --seconds 45
"""

import json
import os
import sys


def main() -> int:
    sys.path.insert(0, os.getcwd())
    mode = os.environ.get("MODE", "none")
    from benchmark import run
    from benchmark.drivers import statesync
    from benchmark.lib import manifest
    from khipu_tpu.observability.trace import tracer

    if mode == "prof":
        tracer.enable = lambda *a, **kw: None
    if mode == "spans":
        inner = statesync.sync_once

        def sync_once(*args, **kwargs):
            tracer.enable(capacity=204_800)
            tracer.reset()
            try:
                return inner(*args, **kwargs)
            finally:
                tracer.disable()
                print(f"observer: ring {len(tracer.snapshot())} kept, "
                      f"{tracer.dropped} dropped", flush=True)

        statesync.sync_once = sync_once

    inner_line = run.result_line

    def result_line(outcome, *args, **kwargs):
        art, read = outcome.artefacts, {}
        for m in manifest.metrics_for("snap.statesync", "per_layer"):
            spec = manifest.metric_file(m["name"])
            reader = manifest.load_module("readers", spec["reader"])
            try:
                value = reader.read(art, **spec.get("args", {}))
            except Exception:  # a reader of the device trace, untraced
                value = None
            if value is not None:
                read[m["name"]] = float(value)
        print("observer: " + json.dumps({
            "mode": mode, "window_s": art["window_s"], "loop_s": art["loop_s"],
            "nodes": art["nodes"], "spans": len(art["spans"]),
            "per_layer": read}), flush=True)
        return inner_line(outcome, *args, **kwargs)

    run.result_line = result_line
    trace = "1" if mode in ("prof", "both") else "0"
    return run.main(sys.argv[1:] + ["--trace", trace])


if __name__ == "__main__":
    sys.exit(main())
