"""From a profiler trace (``.xplane.pb``) to device busy/idle, the
operations that took most device time, the Mosaic (Pallas) share, and
the longest idle gaps named by what the host was doing.

What the trace carries on a TPU v5e today (looked at by hand, PR 23,
``benchmark/tools/record_trace.py``): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed
HLO instruction (name = the instruction's text, start and duration in
ns) and whose line ``XLA Modules`` has one event per executed program
(``jit_<fn>(<fingerprint>)``). A Pallas kernel is the instruction whose
text carries ``custom_call_target="tpu_custom_call"``; the program
gives its kernels and jitted programs no other stable name. Host
threads are lines of ``/host:CPU``; a ``jax.profiler.TraceAnnotation``
is an event there under its own name with its keyword arguments as
stats. On the CPU backend (rehearsals) there is no device plane and the
instructions run on host lines with an ``hlo_op`` stat; they are taken
as the "device" so that the reduction can be exercised end to end.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANCHOR = "bench.anchor"          # stat perf_ns: host perf_counter in ns
ANCHOR_END = "bench.anchor_end"
MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
Span = Tuple[str, float, float]  # name, start_ns, end_ns (trace clock)


def union_ns(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _clip(intervals, lo: float, hi: float):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def op_label(text: str) -> str:
    """``%fusion.12 = u8[...] fusion(...)`` -> ``fusion``; a Mosaic call
    keeps ``mosaic:`` in front so it can be told apart in a breakdown."""
    name = text.split(" = ", 1)[0].lstrip("%")
    name = re.sub(r"[.\d]+$", "", name) or name
    return ("mosaic:" + name) if MOSAIC_MARK in text else name


def _module_label(text: str) -> str:
    return text.split("(", 1)[0]


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def _device_lines(profile):
    """[(chip name, ops events, module events)] — see module docstring."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = list(line.events)
                elif line.name == "XLA Modules":
                    mods = list(line.events)
            out.append((plane.name, ops, mods))
    if out:
        return out
    ops = []
    for plane in profile.planes:  # CPU backend: rehearsal only
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("end: "):
                    continue
                if any(k == "hlo_op" for k, _ in e.stats):
                    ops.append(e)
    return [("/host:CPU", ops, [])] if ops else []


def annotations(profile, prefix: str = "bench.") -> List[Tuple[str, float, float, Dict]]:
    out = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda a: a[1])


def clock_offset_ns(profile) -> Optional[float]:
    """trace_ns = perf_counter_ns - offset, from the anchor annotation
    the harness writes as the trace starts."""
    for name, start, _end, stats in annotations(profile):
        if name == ANCHOR and "perf_ns" in stats:
            return float(stats["perf_ns"]) - start
    return None


def reduce(profile, host_spans: Sequence[Span] = (),
           window_ns: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Dict:
    """See module docstring. ``host_spans`` are on the trace's clock
    (use :func:`clock_offset_ns`); annotations named ``bench.*`` found
    in the trace are added to them. ``window_ns`` defaults to the span
    between the two anchors, else to the extent of the device events."""
    chips = _device_lines(profile)
    notes = annotations(profile)
    if window_ns is None:
        a = [s for n, s, _e, _ in notes if n == ANCHOR]
        b = [e for n, _s, e, _ in notes if n == ANCHOR_END]
        if a and b:
            window_ns = (a[0], b[-1])
    if window_ns is None:
        starts = [e.start_ns for _, ops, _ in chips for e in ops]
        ends = [e.start_ns + e.duration_ns for _, ops, _ in chips for e in ops]
        if not starts:
            return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                    "idle_gaps": [], "mosaic_s": 0.0, "chips": 0,
                    "programs": [], "programs_all": [], "cut": False}
        window_ns = (min(starts), max(ends))
    w0, w1 = window_ns
    # the profiler's device buffer is bounded: a trace whose device
    # events stop in the first three quarters of the window was cut, and
    # is judged over the part it covers
    ends = [e.start_ns + e.duration_ns for _, ops, _ in chips for e in ops]
    cut = bool(ends) and max(ends) < w0 + 0.75 * (w1 - w0)
    if cut:
        w1 = max(ends)
    spans = [s for s in host_spans if s[2] > w0 and s[1] < w1]
    spans.sort(key=lambda s: s[1])
    spans += [(n, s, e) for n, s, e, _ in notes
              if n not in (ANCHOR, ANCHOR_END) and e > w0 and s < w1]

    busy_total = mosaic_total = 0.0
    by_op: Dict[str, float] = {}
    by_program: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for _chip, ops, mods in chips:
        mod_iv = sorted((m.start_ns, m.start_ns + m.duration_ns,
                         _module_label(m.name)) for m in mods)
        for lo, hi, label in mod_iv:
            for a, b in _clip([(lo, hi)], w0, w1):
                by_program[label] = by_program.get(label, 0.0) + (b - a)
        iv = []
        mi = 0
        for e in sorted(ops, key=lambda e: e.start_ns):
            lo, hi = e.start_ns, e.start_ns + e.duration_ns
            for a, b in _clip([(lo, hi)], w0, w1):
                iv.append((a, b))
                label = op_label(e.name)
                while mi + 1 < len(mod_iv) and mod_iv[mi][1] <= lo:
                    mi += 1
                if mod_iv and mod_iv[mi][0] <= lo < mod_iv[mi][1]:
                    label = mod_iv[mi][2] + "/" + label
                by_op[label] = by_op.get(label, 0.0) + (b - a)
                if MOSAIC_MARK in e.name:
                    mosaic_total += b - a
        merged = union_ns(iv)
        busy_total += sum(b - a for a, b in merged)
        edge = w0
        for a, b in merged + [(w1, w1)]:
            if a > edge:
                for name, ns in _attribute(edge, a, spans).items():
                    gaps[name] = gaps.get(name, 0.0) + ns
            edge = max(edge, b)
    n = max(1, len(chips))

    def ranked(d: Dict[str, float]) -> List[List]:
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "busy_s": busy_total / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "mosaic_s": mosaic_total / n / 1e9,
        "device_ops": ranked(by_op),
        "programs": ranked(by_program),
        "programs_all": [[k, v / n / 1e9] for k, v in by_program.items()],
        "idle_gaps": ranked(gaps),
        "chips": len(chips),
        "cut": cut,
    }


def _attribute(lo: float, hi: float, spans: Sequence[Span]) -> Dict[str, float]:
    """Split the gap [lo, hi) at the boundaries of the host spans that
    overlap it and give each piece to the shortest span that covers it
    (the innermost one open at that time); pieces no span covers go to
    ``(no host span)``. Returns name -> ns."""
    near = [(s, e, n) for n, s, e in spans if e > lo and s < hi]
    cuts = sorted({lo, hi, *(x for s, e, _ in near for x in (s, e)
                             if lo < x < hi)})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        best, best_len = "(no host span)", None
        for s, e, n in near:
            if s <= a and e >= b and (best_len is None or e - s < best_len):
                best, best_len = n, e - s
        out[best] = out.get(best, 0.0) + (b - a)
    return out
