"""What no phase of the fast-sync loop covers: ``100 *
(khipu_fastsync_loop_seconds - sum of the phase seconds) /
khipu_fastsync_loop_seconds``. The program books a phase from one clock
read to the next and leaves the per-batch bookkeeping between phases
unbooked; this is that rest, which should stay small."""

from typing import Dict

from benchmark.readers.registry_us import fastsync


def read(art: Dict):
    fams = fastsync(art)
    if fams is None:
        return None
    loop = fams.get("khipu_fastsync_loop_seconds", {}).get("", 0.0)
    if loop <= 0:
        return None
    phases = sum(fams["khipu_fastsync_phase_seconds_total"].values())
    return 100.0 * (loop - phases) / loop
