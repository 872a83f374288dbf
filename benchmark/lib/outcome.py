"""What a driver hands back, and the last line of a run."""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class Check:
    """One number compared and its limit. ``ok`` iff value <= limit
    (exact comparisons count mismatches against the limit 0)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    end_to_end: Dict[str, float]            # name -> value, setup_s too
    checks: List[Check]
    attempted: int
    failed: int
    artefacts: Dict = field(default_factory=dict)   # for the readers

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def print_checks(checks: List[Check]) -> None:
    for c in checks:
        print(f"check: {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", flush=True)


def result_line(outcome: Outcome, metrics: Dict[str, dict], device: Dict,
                breakdown: Optional[Dict] = None) -> str:
    line = {
        "correct": outcome.correct,
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)
