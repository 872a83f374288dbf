"""``snap.statesync`` as PR 41 re-sized it: the window's clock (artefact
``slices``), the sizes that keep the cell a resumed sync of half a
minute, and the program's spans reaching a traced run's breakdown. One
traced ``--rehearse`` on the CPU serves all of it. Says nothing of the
chip. No TPU topology call anywhere."""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import statesync  # noqa: E402
from benchmark.lib import manifest  # noqa: E402

CELL = manifest.cell("snap.statesync")
CONF, TRAFFIC = CELL["config_file"], CELL["traffic_file"]

# The fewest nodes a 900,000-account source trie had over the seeds run
# on the chip (PERF.md section 4: 1,216,162-1,217,605).
TRIE_NODES_AT_900K = 1_216_162
# The rate the window's length is reckoned with: the ledger's
# 36,000-38,000 nodes/s (PRs 35-40), rounded up. A program twice as
# fast fails the test below, which is the prompt to re-size the cell
# (`resume_remaining_nodes`, or a larger share of BASELINE #5) and not
# to let its window shrink to 12 s in silence, as it did from PR 26 on.
ASSUMED_NODES_PER_S = 40_000


def rehearse(trace):
    """(rc, result line, printed lines, the driver's artefacts) of one
    rehearsal."""
    kept = {}
    inner = bench_run.result_line

    def result_line(outcome, *args, **kwargs):
        kept["art"] = outcome.artefacts
        return inner(outcome, *args, **kwargs)

    bench_run.result_line = result_line
    out = io.StringIO()
    try:
        with redirect_stdout(out):
            rc = bench_run.main([
                "--workload", "snap.statesync", "--seed", "3000000019",
                "--seconds", "2", "--trace", str(trace), "--rehearse"])
    finally:
        bench_run.result_line = inner
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), lines, kept["art"]


@pytest.fixture(scope="module", autouse=True)
def own_run_dir(tmp_path_factory):
    """``run.py`` empties ``<BENCH_DIR>/cache/_run`` at the start of
    every run, and xdist runs the other files' rehearsals in other
    processes at the same time: these get a directory of their own."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(bench_run, "BENCH_DIR",
                      str(tmp_path_factory.mktemp("statesync")))
        yield


@pytest.fixture(scope="module")
def traced():
    return rehearse(trace=1)


def test_the_windows_clock_adds_up_and_never_runs_backwards(traced):
    rc, line, lines, art = traced
    assert rc == 0 and line["correct"] is True
    rows = art["slices"]
    # a row at the opening, one every `every` requests (a twentieth of
    # the window's: 30 requests in a rehearsal, so each), one at the
    # loop's end
    batch = bench_run.merged(CONF, True)["sizes"]["nodes_per_request"]
    every = max(1, 1500 // (statesync.SLICES * batch))
    assert len(rows) == 2 + art["requests"] // every >= 5
    assert rows[0][0] == art["window"][0] and rows[0][3:] == (0, 0)
    assert [r[3] for r in rows[1:-1]] == [
        every * (i + 1) for i in range(len(rows) - 2)]
    assert rows[-1][3] == art["requests"]
    # the nodes handed over, slice by slice, are the window's nodes
    assert rows[-1][4] == art["nodes"] == 1500
    rates = statesync.slice_rates(rows)
    assert sum(n for n, _w, _c, _o in rates) == art["nodes"]
    for a, b in zip(rows, rows[1:]):
        assert b[0] > a[0]                      # wall
        assert b[1] >= a[1] and b[2] >= a[2]    # thread and process CPU
        assert b[3] >= a[3] and b[4] >= a[4]    # requests, nodes
    assert art["window"][0] <= rows[-1][0] <= art["window"][1]
    for nodes, wall, cpu, _other in rates:
        assert nodes > 0 and wall > 0 and 0 < cpu <= wall * 1.05
    (closed,) = [l for l in lines if "window: closed" in l]
    printed = closed.split("verify): ")[1].split()
    assert len(printed) == len(rates)
    assert all(len(p.split("/")) == 3 for p in printed)


def test_slice_rates_leaves_out_a_slice_with_nothing_in_it():
    rows = [(10.0, 1.0, 1.5, 0, 0), (11.0, 1.9, 2.6, 1000, 50_000),
            (11.0, 1.9, 2.6, 1000, 50_000), (12.5, 3.3, 4.4, 2000, 99_000)]
    assert statesync.slice_rates(rows) == [
        (50_000, 1.0, pytest.approx(0.9), pytest.approx(0.2)),
        (49_000, 1.5, pytest.approx(1.4), pytest.approx(0.4))]
    assert statesync.slice_rates(rows[:1]) == []


def test_the_traffic_keeps_the_cell_resumed_and_half_a_minute_long():
    remaining = TRAFFIC["resume_remaining_nodes"]
    assert CONF["sizes"]["accounts"] == 900_000
    # resumed, not cold (`snap.statesync.cold` is another cell): part of
    # the trie is in the store and the mirror before the window opens
    assert 0 < remaining < TRIE_NODES_AT_900K
    # long: at the rate assumed the pull alone is 20 s or more ...
    assert remaining / ASSUMED_NODES_PER_S >= 20
    # ... and at three fifths of it (a slow host) the window still ends
    # by completion, so the closing verify covers the whole mirror
    run_seconds = manifest.benchmark_json()["run_seconds"]
    assert remaining / (0.6 * ASSUMED_NODES_PER_S) <= run_seconds
    # the mirror holds the whole trie, whatever the split
    assert sum(CONF["sizes"]["mirror_rows"].values()) > TRIE_NODES_AT_900K
    assert TRAFFIC["rehearse"]["resume_remaining_nodes"] == 1500
    # a slice is 50,000 nodes: the window's clock has a row every
    # 1,000th request
    batch = CONF["sizes"]["nodes_per_request"]
    assert remaining // (statesync.SLICES * batch) == 1000


def test_the_programs_spans_reach_a_traced_runs_breakdown(traced):
    rc, line, lines, art = traced
    assert rc == 0
    names = {s.name for s in art["spans"]}
    assert {"fastsync.batch", "fastsync.queue", "fastsync.fetch",
            "fastsync.parse", "fastsync.store", "mirror.admit",
            "mirror.flush", "mirror.verify"} <= names
    t_open, t_close = art["window"]
    assert all(s.t1 > t_open and s.t0 < t_close for s in art["spans"])
    assert art["spans_dropped"] == 0
    (ring,) = [l for l in lines if "span ring: " in l]
    assert ring.endswith(f"{len(art['spans'])} kept, 0 dropped")
    # 30 batches of 50, each with its queue, fetch, parse, store, admit
    batches = [s for s in art["spans"] if s.name == "fastsync.batch"]
    assert len(batches) == art["requests"]
    gaps = dict(line["breakdown"]["idle_gaps"])
    assert any(n.startswith(("fastsync.", "mirror.")) for n in gaps)
    named = sum(v for n, v in gaps.items()
                if n.startswith(("fastsync.", "mirror.")))
    assert named > gaps.get("(no host span)", 0.0)


def test_the_tracer_is_off_again_after_a_traced_window_and_in_an_untraced_run(
        traced):
    from khipu_tpu.observability.trace import tracer

    assert tracer.enabled is False
    rc, _line, _lines, art = rehearse(trace=0)
    assert rc == 0 and art["spans"] == []
    assert art["spans_dropped"] == 0 and tracer.enabled is False
    assert len(art["slices"]) >= 5  # the clock runs untraced too
