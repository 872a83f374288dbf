#!/usr/bin/env python3
"""One ``snap.statesync`` run with what its window cost the host.

    python3 <repo>/scripts/statesync_window_dump.py --workload snap.statesync \
        --seed <n> --seconds <s> --trace 0

Runs ``benchmark/run.py`` of the checkout it is started in and, around
the window's ``sync_once`` (the last call of the run), reads the clocks
that tell a slow host from a slow program: the wall, the driver
thread's and the whole process's CPU seconds (a thread that was slow ON
the CPU, not kept off it, is the host's doing when every phase slows
with it), full garbage collections, and the syncer's own phase seconds
(``khipu_fastsync_phase_seconds_total``). One line, ``window host:
{...}``, before the result line. ``getrusage``'s switches and faults and
``/proc/stat`` read 0 on the chip tool's sealed machine, so they are not
read. Edits nothing under ``benchmark/``: it wraps the driver's
``sync_once``.
"""

import gc
import json
import os
import sys
import time


def main() -> int:
    sys.path.insert(0, os.getcwd())
    from benchmark import run
    from benchmark.drivers import statesync

    inner = statesync.sync_once
    calls = []
    full = [0, 0.0, 0.0]  # gen-2 collections, their seconds, start

    def on_gc(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                full[2] = time.perf_counter()
            else:
                full[0] += 1
                full[1] += time.perf_counter() - full[2]

    def sync_once(*args, **kwargs):
        full[0], full[1] = 0, 0.0
        t0 = (time.perf_counter(), time.process_time(), time.thread_time())
        try:
            return inner(*args, **kwargs)
        finally:
            t1 = (time.perf_counter(), time.process_time(),
                  time.thread_time())
            calls.append({
                "wall_s": t1[0] - t0[0], "process_cpu_s": t1[1] - t0[1],
                "thread_cpu_s": t1[2] - t0[2],
                "full_gcs": full[0], "full_gc_s": full[1],
                "cpus": os.cpu_count(),
            })

    statesync.sync_once = sync_once
    gc.callbacks.append(on_gc)

    inner_line = run.result_line

    def result_line(*args, **kwargs):
        from khipu_tpu.observability.registry import REGISTRY

        phases = REGISTRY.snapshot().get(
            "khipu_fastsync_phase_seconds_total", {})
        print("window host: " + json.dumps(
            dict(calls[-1] if calls else {}, phases=phases)), flush=True)
        return inner_line(*args, **kwargs)

    run.result_line = result_line
    return run.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
