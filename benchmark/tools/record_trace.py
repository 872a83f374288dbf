"""Record a small device trace for tests/benchmark/data and print what
the profiler's planes, lines and events look like on this machine.

    chiprun -- python benchmark/tools/record_trace.py

Writes chiprun_out/trace_probe/{small.xplane.pb,summary.txt}. Not part
of a benchmark run; kept because the reduction in benchmark/reduce/xplane.py
was written against what this prints.
"""
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    out = os.path.join(ROOT, "chiprun_out", "trace_probe")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()), flush=True)

    from khipu_tpu import device
    from khipu_tpu.ops.keccak import keccak256_batch

    impl = "pallas" if device.platform() == "tpu" else "jnp"
    rng = np.random.default_rng(0)
    msgs = [rng.integers(0, 256, 100, dtype=np.uint8).tobytes() for _ in range(2048)]

    @jax.jit
    def scatter(x, idx, v):
        return x.at[idx].set(v).sum()

    x = jnp.zeros((1 << 16, 32), jnp.uint8)
    idx = jnp.arange(0, 1 << 16, 7)
    v = jnp.ones((idx.shape[0], 32), jnp.uint8)
    keccak256_batch(msgs, impl=impl)          # warm
    scatter(x, idx, v).block_until_ready()

    tdir = os.path.join(out, "t")
    from benchmark.lib.tracewin import TraceWindow, annotate

    t_perf0 = time.perf_counter()
    tw = TraceWindow(tdir)
    tw.start()
    for i in range(3):
        with annotate("bench.batch", index=i):
            keccak256_batch(msgs, impl=impl)
            with annotate("bench.pause"):
                time.sleep(0.02)          # a host gap with a name
            scatter(x, idx, v).block_until_ready()
    tw.stop()
    print("trace wall s", time.perf_counter() - t_perf0, flush=True)

    pb = tw.xplane_path()
    shutil.copy(pb, os.path.join(out, "small.xplane.pb"))
    print("xplane bytes", os.path.getsize(pb))
    pd = jax.profiler.ProfileData.from_file(pb)
    lines_out = []
    for plane in pd.planes:
        lines_out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            lines_out.append(f"  LINE {line.name!r} events={len(evs)}")
            seen = {}
            for e in evs:
                seen.setdefault(e.name, []).append(e)
            for name, es in list(seen.items())[:40]:
                e = es[0]
                stats = dict(e.stats) if hasattr(e, "stats") else {}
                lines_out.append(
                    f"    {name!r} n={len(es)} start_ns={e.start_ns} dur_ns={e.duration_ns} stats={str(stats)[:300]}")
    text = "\n".join(lines_out)
    with open(os.path.join(out, "summary.txt"), "w") as f:
        f.write(text)
    print(text[-20000:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
