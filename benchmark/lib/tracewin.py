"""A few seconds of the measured window under ``jax.profiler``.

The Python tracer is off (it writes tens of thousands of events a second
and slows the host it is measuring); device events and
``TraceAnnotation``s stay. An anchor annotation carries the host's
``perf_counter`` so that spans the program recorded on that clock can be
laid over the device's timeline.
"""

from __future__ import annotations

import glob
import os
import shutil
import time
from typing import Optional


class TraceWindow:
    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation(
                "bench.anchor", perf_ns=int(self.t_start * 1e9)):
            pass

    def stop(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation("bench.anchor_end"):
            pass
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def running(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    def xplane_path(self) -> Optional[str]:
        found = glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb"))
        return found[0] if found else None


def annotate(name: str, **kw):
    import jax

    return jax.profiler.TraceAnnotation(name, **kw)
