#!/usr/bin/env python3
"""PR 41, calls p41h and p41i (the third session, after the driver's
refusal): ``run.py`` of the checkout it is started in, untraced, as the
driver runs it, with two more lines on standard output and nothing
inside the window:

``host probe (<when>): {...}``, just before ``window: open`` is logged
(so inside set-up, ~0.2 s) and just after ``window: closed`` (after the
window's last clock read): what a kernel entry, a wake-up, a page fault,
a stretch of interpreter work and a stretch of random memory reads cost
this process at that moment. PERF.md §6 predicted, and could not show,
that a window in the slow state reads dearer kernel entries.

``observer: {...}``, before the result line: the window's and the loop's
seconds, every per-layer metric of the cell that reads without a device
trace, and the CPU seconds the process's other threads burned over the
window, by thread name, and how many of its tasks /proc calls running or
sleeping at the window's two ends.

Edits nothing under ``benchmark/``.

    python3 benchmark/tools/calls/pr41-h.py \
        --workload snap.statesync --seed <n> --seconds 45 --trace 0
"""

import json
import mmap
import os
import sys
import threading
import time


def task_cpu():
    """CPU seconds (user + system) of every task of this process, by
    thread name; empty where /proc does not say."""
    out, tick = {}, os.sysconf("SC_CLK_TCK")
    try:
        for tid in os.listdir("/proc/self/task"):
            with open(f"/proc/self/task/{tid}/stat") as f:
                stat = f.read()
            name = stat[stat.index("(") + 1:stat.rindex(")")]
            rest = stat[stat.rindex(")") + 2:].split()
            out[name] = out.get(name, 0.0) + (
                int(rest[11]) + int(rest[12])) / tick
            out["state " + rest[0]] = out.get("state " + rest[0], 0) + 1
    except (OSError, ValueError, IndexError):
        pass
    return out


def probe(scratch: str) -> dict:
    try:
        return _probe(scratch)
    except Exception as e:  # a probe that fails costs the run nothing
        return {"probe_failed": f"{type(e).__name__}: {e}"[:200]}


def _probe(scratch: str) -> dict:
    import numpy as np

    clock = time.perf_counter
    buf = b"x" * 100
    fd = os.open("/dev/null", os.O_WRONLY)
    t = clock()
    for _ in range(2000):
        os.write(fd, buf)
    null_us = (clock() - t) / 2000 * 1e6
    os.close(fd)
    path = os.path.join(scratch, "_probe.bin")
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o600)
    t = clock()
    for _ in range(2000):
        os.write(fd, buf)
    append_us = (clock() - t) / 2000 * 1e6
    os.close(fd)
    os.unlink(path)
    efd = os.eventfd(0)
    one = (1).to_bytes(8, "little")
    t = clock()
    for _ in range(2000):
        os.write(efd, one)
        os.read(efd, 8)
    eventfd_us = (clock() - t) / 4000 * 1e6
    os.close(efd)
    pages = 2000
    m = mmap.mmap(-1, 4096 * pages)
    t = clock()
    for i in range(pages):
        m[4096 * i] = 1
    fault_us = (clock() - t) / pages * 1e6
    m.close()
    # a wake-up: two threads hand an event back and forth
    ping, pong, n = threading.Event(), threading.Event(), 300

    def other():
        for _ in range(n):
            ping.wait()
            ping.clear()
            pong.set()

    th = threading.Thread(target=other)
    th.start()
    t = clock()
    for _ in range(n):
        ping.set()
        pong.wait()
        pong.clear()
    wake_us = (clock() - t) / n * 1e6
    th.join()
    t = clock()
    s = 0
    for i in range(300_000):
        s += i * i
    interp_ms = (clock() - t) * 1e3
    rng = np.random.default_rng(7)
    table = rng.integers(0, 1 << 40, 1 << 23)  # 64 MiB
    idx = rng.integers(0, 1 << 23, 1 << 21)
    t = clock()
    table[idx].sum()
    gather_ms = (clock() - t) * 1e3
    return {"write_devnull_us": null_us, "append_file_us": append_us,
            "eventfd_us": eventfd_us, "page_fault_us": fault_us,
            "thread_wake_us": wake_us, "interp_300k_ms": interp_ms,
            "gather_2M_of_64MiB_ms": gather_ms}


def main() -> int:
    sys.path.insert(0, os.getcwd())
    from benchmark import run
    from benchmark.lib import manifest

    inner_log = run.Env.log
    cpu = {}

    def log(self, msg):
        if msg == "window: open":
            print("host probe (before): " + json.dumps(probe(self.run_dir)),
                  flush=True)
            cpu["open"] = task_cpu()
        inner_log(self, msg)
        if msg.startswith("window: closed"):
            cpu["closed"] = task_cpu()
            print("host probe (after): " + json.dumps(probe(self.run_dir)),
                  flush=True)

    run.Env.log = log
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
        a, b = cpu.get("open", {}), cpu.get("closed", {})
        burned = {k: round(b[k] - a.get(k, 0.0), 2) for k in b
                  if not k.startswith("state ")
                  and b[k] - a.get(k, 0.0) >= 0.05}
        states = {w: {k: v for k, v in cpu.get(w, {}).items()
                      if k.startswith("state ")} for w in ("open", "closed")}
        print("observer: " + json.dumps({
            "window_s": art["window_s"], "loop_s": art["loop_s"],
            "nodes": art["nodes"], "per_layer": read,
            "timers_s": {k: round(v[0], 3) for k, v in art["timers"].items()},
            "fetch_s": art["fetch_s"], "threads_cpu_s": burned,
            "tasks_by_state": states}), flush=True)
        return inner_line(outcome, *args, **kwargs)

    run.result_line = result_line
    return run.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
