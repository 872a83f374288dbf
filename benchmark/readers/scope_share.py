"""Device time of one stage of the fused fixpoint program as a share of
device busy time.

A trace names a device event by its HLO instruction and carries no
scope; the program hands out ``{instruction name: stage or None}`` for
each of its compiled fixpoint programs
(``khipu_tpu.trie.fused.scope_map()``, read out of the executables' own
text), and the two are joined by name. All those programs are
``jit_fused_fixpoint`` to the trace and number their instructions
differently, so each run of the program is first matched to the cached
programs that have every instruction it executed; a name those put in
different stages is ``ambiguous``. Only leaf instructions count (a
``while`` is the container of the fusions under it, which are events of
their own) and each instant goes to one event, so the stages and the
``unmapped`` rest add up to no more than the program's own time. The
join is made once per run and logged, however many metrics read it. No
map (a program without ``scope_map``, an empty compile cache) or no
trace reads as None."""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional

from benchmark.readers import trace_share
from benchmark.reduce import xplane

CONTAINERS = ("while", "conditional", "call")
AMBIGUOUS, UNMAPPED = "ambiguous", "unmapped"


def merged(maps: Iterable[Dict[str, Optional[str]]]) -> Dict[str, str]:
    """Instruction name -> stage over several programs' maps."""
    out: Dict[str, str] = {}
    for scopes in maps:
        for name, scope in scopes.items():
            scope = scope or UNMAPPED
            out[name] = scope if out.get(name, scope) == scope else AMBIGUOUS
    return out


def _name(event) -> str:
    return event.name.split(" = ", 1)[0].lstrip("%")


def _runs(ops: List, mods: List, program: str) -> List[List]:
    """The leaf events of each run of ``program``, in time order."""
    leaves = [e for e in sorted(ops, key=lambda e: e.start_ns)
              if xplane.op_label(e.name).split(":")[-1] not in CONTAINERS]
    if not mods:  # the CPU backend (rehearsals) has no module line
        return [[e for e in leaves
                 if dict(e.stats).get("hlo_module") == program]]
    out = []
    i = 0
    for m in sorted(mods, key=lambda m: m.start_ns):
        lo, hi = m.start_ns, m.start_ns + m.duration_ns
        while i < len(leaves) and leaves[i].start_ns < lo:
            i += 1
        j = i
        while j < len(leaves) and leaves[j].start_ns < hi:
            j += 1
        if m.name.split("(", 1)[0] == program:
            out.append(leaves[i:j])
        i = j
    return out


def by_scope(profile, per_program: Dict[str, Dict], program: str) -> Dict:
    """Seconds per stage (and ``unmapped``, ``ambiguous``) of the leaf
    instructions that ran inside ``program``, averaged over the chips,
    within the window between the harness's two anchors; ``worst`` lists
    the unmapped and ambiguous instructions that took most time."""
    notes = xplane.annotations(profile)
    lo = [s for n, s, _e, _ in notes if n == xplane.ANCHOR]
    hi = [e for n, _s, e, _ in notes if n == xplane.ANCHOR_END]
    w0, w1 = (lo[0], hi[-1]) if lo and hi else (float("-inf"), float("inf"))
    chips = xplane._device_lines(profile)
    seconds: Dict[str, float] = {}
    worst: Dict[str, float] = {}
    for _chip, ops, mods in chips:
        for run in _runs(ops, mods, program):
            seen = {_name(e) for e in run}
            fits = [m for m in per_program.values() if seen <= m.keys()]
            scopes = merged(fits or per_program.values())
            edge = w0
            for e in run:
                a = max(e.start_ns, edge)
                b = min(e.start_ns + e.duration_ns, w1)
                if b <= a:
                    continue
                edge = b
                name = _name(e)
                scope = scopes.get(name, UNMAPPED)
                seconds[scope] = seconds.get(scope, 0.0) + (b - a)
                if scope in (AMBIGUOUS, UNMAPPED):
                    worst[name] = worst.get(name, 0.0) + (b - a)
    n = max(1, len(chips)) * 1e9
    return {"seconds": {k: v / n for k, v in seconds.items()},
            "worst": [[k, v / n] for k, v in
                      sorted(worst.items(), key=lambda kv: -kv[1])[:8]]}


def joined(art: Dict, program: str) -> Optional[Dict]:
    if "scope_joined" in art:
        return art["scope_joined"]
    from khipu_tpu.trie import fused

    out = None
    tw = art.get("trace")
    path = tw.xplane_path() if tw is not None else None
    # a program from before scope_map() has nothing to join with
    per_program = getattr(fused, "scope_map", dict)() if path else {}
    if per_program:
        out = by_scope(xplane.load(path), per_program, program)
        total = sum(out["seconds"].values())
        if total > 0:
            print(f"scope_share: {total:.4f} s of leaf instructions inside "
                  f"{program}: " + ", ".join(
                      f"{k} {100 * v / total:.2f} %" for k, v in
                      sorted(out["seconds"].items(), key=lambda kv: -kv[1]))
                  + f"; unmapped and ambiguous by time: {out['worst']}",
                  flush=True)
        else:
            out = None
    art["scope_joined"] = out
    return out


def read(art: Dict, scope: str, program: str = "jit_fused_fixpoint"):
    r = trace_share.reduced(art)
    j = joined(art, program)
    if r is None or j is None or r["busy_s"] <= 0:
        return None
    return 100.0 * j["seconds"].get(scope, 0.0) / r["busy_s"]
