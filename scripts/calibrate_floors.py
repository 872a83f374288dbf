#!/usr/bin/env python3
"""Measure one device's row for observability/costmodel.DEVICE_FLOORS.

    python scripts/calibrate_floors.py          # on the chip

Prints one JSON object: the device stamp and the three floors the cost
model classifies against (docs/roofline.md "Method"), each a median of
many readings inside this run:

* ``fetch_round_trip_s`` — one materialised device->host fetch of a
  32 B x 4 result (the root check's shape): dispatch a jitted gather on
  a resident array and ``device_get`` it.
* ``h2d_bytes_per_s`` — ``device_put`` of a host array at the window
  upload size (``--upload-bytes``, default 4 MiB) until ready.
* ``kernel_hashes_per_s`` — the Keccak kernel alone on resident
  word-major 576 B tiles: salted rounds inside one program, rate from
  the DELTA between a long and a short program so dispatch and fetch
  cancel.

Refuses to run off the chip: a CPU number is not a device floor.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--upload-bytes", type=int, default=4 << 20)
    ap.add_argument("--tiles", type=int, default=1024)  # x1024 rows
    ap.add_argument("--reps", type=int, default=50)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from khipu_tpu import device
    from khipu_tpu.ops.keccak_pallas import _build

    dev = jax.devices()[0]
    if device.platform() != "tpu":
        print("calibrate_floors: no TPU", file=sys.stderr)
        return 2
    device.place_compile_cache()

    # 1. round trip of one materialised fetch
    table = jax.device_put(np.zeros((4096, 32), np.uint8))
    rows = np.arange(4, dtype=np.int32)
    pick = jax.jit(lambda t, r: t[r])
    np.asarray(jax.device_get(pick(table, rows)))
    rtt = []
    for _ in range(4 * args.reps):
        t0 = time.perf_counter()
        np.asarray(jax.device_get(pick(table, rows)))
        rtt.append(time.perf_counter() - t0)

    # 2. sustained host->device bytes/s at the window upload size
    host = np.random.default_rng(0).integers(
        0, 256, args.upload_bytes, dtype=np.uint8)
    jax.device_put(host).block_until_ready()
    up = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        jax.device_put(host).block_until_ready()
        up.append(time.perf_counter() - t0)

    # 3. kernel-only hashes/s on resident 576 B word-major tiles
    nwords = 576 // 4
    run = _build(5, False, nwords_in=nwords)
    tiled = jax.random.bits(
        jax.random.PRNGKey(0), (args.tiles, nwords, 8, 128), jnp.uint32)

    def program(rounds):
        @jax.jit
        def step(t, salt0):
            def body(i, carry):
                acc, salt = carry
                return acc ^ run(t ^ salt), salt + jnp.uint32(1)
            acc, _ = jax.lax.fori_loop(
                0, rounds, body,
                (jnp.zeros((args.tiles, 8, 8, 128), jnp.uint32), salt0))
            return acc
        return step

    def timed(step, salt):
        t0 = time.perf_counter()
        np.asarray(jax.device_get(step(tiled, jnp.uint32(salt))[0, 0, 0, :1]))
        return time.perf_counter() - t0

    short_r, long_r = 8, 40
    short, long_ = program(short_r), program(long_r)
    timed(short, 0), timed(long_, 0)
    ts = [timed(short, i) for i in range(1, 8)]
    tl = [timed(long_, i) for i in range(1, 8)]
    delta = statistics.median(tl) - statistics.median(ts)
    hashes = (long_r - short_r) * args.tiles * 1024

    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "fetch_round_trip_s": statistics.median(rtt),
        "h2d_bytes_per_s": args.upload_bytes / statistics.median(up),
        "upload_bytes": args.upload_bytes,
        "kernel_hashes_per_s": hashes / delta,
        "kernel_rows": args.tiles * 1024,
        "kernel_delta_s": delta,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
