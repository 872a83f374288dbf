"""PR 42's fourteen per-layer metrics of the driver thread: their
entries and files, the one new reader on hand-made spans, and a traced
rehearsal of ``sync.contracts`` that reports them all with the parts
inside their wholes. Says nothing of the chip."""

import io
import json
import os
import sys
from contextlib import redirect_stdout
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import manifest  # noqa: E402
from benchmark.readers import span_tag_rest  # noqa: E402

SEED = 2_147_484_061
CELLS = ["sync.dense", "sync.deep", "sync.contracts"]
FG = "driver foreground (sync/replay.py)"
STORE = "node store reads (storage/node_storage.py)"
# name -> (unit, layer, reader), in the manifest's order
NEW = {
    "fg_commit_ms_per_block.sync": ("ms", FG, "phases"),
    "exec_plan_ms_per_block.sync": ("ms", FG, "span_tag_sum"),
    "exec_post_ms_per_block.sync": ("ms", FG, "span_tag_sum"),
    "exec_checkpoint_ms_per_block.sync": ("ms", FG, "span_tag_sum"),
    "exec_validate_ms_per_block.sync": ("ms", FG, "span_tag_sum"),
    "exec_world_copy_ms_per_block.sync": ("ms", FG, "span_tag_sum"),
    "exec_unnamed_share.sync": ("%", FG, "span_tag_rest"),
    "commit_storage_ms_per_block.sync": ("ms", FG, "span_tag_sum"),
    "commit_account_ms_per_block.sync": ("ms", FG, "span_tag_sum"),
    "commit_root_ms_per_block.sync": ("ms", FG, "span_tag_sum"),
    "fg_misses_per_block.sync": ("count", STORE, "span_tag_sum"),
    "fg_miss_ms_per_block.sync": ("ms", STORE, "span_tag_sum"),
    "fg_miss_lock_wait_ms_per_block.sync": ("ms", STORE, "span_tag_sum"),
    "fg_miss_engine_ms_per_block.sync": ("ms", STORE, "span_tag_sum"),
}
BENCH = manifest.benchmark_json()


def test_the_fourteen_are_appended_after_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-14:] == list(NEW) and len(set(names)) == len(names)
    assert names[41] == "exec_rerun_txs_share.sync"  # PR 41's last


@pytest.mark.parametrize("name", list(NEW))
def test_a_new_metric_is_an_entry_with_a_file_in_all_three_cells(name):
    unit, layer, reader = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower",
        "source": "program_span", "layer": layer,
        "moves": "sync_blocks_per_s", "workloads": CELLS}
    spec = manifest.metric_file(name)
    assert set(spec) == {"reader", "args", "what"}
    assert spec["reader"] == reader and len(spec["what"]) > 40
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "readers", reader + ".py"))
    for cell in CELLS:
        assert name in {m["name"] for m in manifest.metrics_for(
            cell, "per_layer")}
    assert name not in {m["name"] for m in manifest.metrics_for(
        "snap.statesync", "per_layer")}


def span(name="execute", t0=0.0, t1=1.0, **tags):
    return SimpleNamespace(name=name, t0=t0, t1=t1, tags=tags, sid=1,
                           parent=None)


def test_span_tag_rest_reads_what_the_named_tags_leave():
    args = {"name": "execute", "named": ["a_s", "b_s"]}
    art = {"spans": [span(t1=1.0, a_s=0.5, b_s=0.25),
                     span(t0=2.0, t1=5.0, a_s=2.0, b_s=0.25),
                     span(name="commit", t1=9.0)]}
    # 4 s of execute, 3 s of it named
    assert span_tag_rest.read(art, **args) == pytest.approx(25.0)
    all_named = {"spans": [span(t1=2.0, a_s=1.5, b_s=0.5)]}
    assert span_tag_rest.read(all_named, **args) == 0.0
    # a program that lacks a tag, no such span, an empty duration
    assert span_tag_rest.read({"spans": [span(a_s=0.5)]}, **args) is None
    assert span_tag_rest.read({"spans": [span(name="commit")]},
                              **args) is None
    assert span_tag_rest.read({}, **args) is None
    assert span_tag_rest.read({"spans": [span(t1=0.0, a_s=0.0, b_s=0.0)]},
                              **args) is None


def test_a_parent_without_the_tags_leaves_all_fourteen_out():
    """What the driver's run of the parent under this benchmark needs:
    every new metric's reader returns nothing there and does not raise
    (the parent has the lane tags and the commit phase, so that one
    metric reads; it lacks every new tag and the commit span)."""
    lanes = {lane + "_s": 0.01 for lane in (
        "vector", "checked", "residue", "optimistic", "sequential")}
    parent = {"blocks": 2, "windows": 1,
              "replay_stats": [SimpleNamespace(phases={"commit": 0.05})],
              "spans": [span(txs=200, block=7, **lanes),
                        span(name="window.build", block=7, txs=200)]}
    for name in NEW:
        spec = manifest.metric_file(name)
        reader = manifest.load_module("readers", spec["reader"])
        got = reader.read(parent, **spec["args"])
        if name == "fg_commit_ms_per_block.sync":
            assert got == pytest.approx(25.0)
        else:
            assert got is None, name
        assert reader.read({"blocks": 2}, **spec["args"]) is None


# --------------------------------------------- the rehearsal, end to end


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """``run.py`` empties ``<BENCH_DIR>/cache/_run`` at the start of
    every run, and xdist runs the other files' rehearsals in other
    processes at the same time: this one gets a directory of its own."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(bench_run, "BENCH_DIR",
                      str(tmp_path_factory.mktemp("driver_metrics")))
        out = io.StringIO()
        with redirect_stdout(out):
            rc = bench_run.main([
                "--workload", "sync.contracts", "--seed", str(SEED),
                "--seconds", "2", "--trace", "1", "--rehearse"])
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1])


def test_traced_rehearsal_reports_all_fourteen(traced_run):
    rc, line = traced_run
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(NEW) <= set(got)
    for name, (unit, _, _) in NEW.items():
        assert got[name]["unit"] == unit
        assert isinstance(got[name]["value"], float), name
        assert got[name]["value"] >= 0, name
    # what the cell had is still there under its name
    assert {"fg_busy_ms_per_block.sync", "fg_execute_ms_per_block.sync",
            "node_read_ms_per_block.sync", "exec_vector_ms_per_block.sync",
            "exec_interpreter_ms_per_block.sync",
            "trie_reads_per_block.sync"} <= set(got)


def test_the_parts_lie_inside_their_wholes(traced_run):
    _, line = traced_run
    v = {name: m["value"] for name, m in line["metrics"].items()}
    assert v["fg_miss_lock_wait_ms_per_block.sync"] + \
        v["fg_miss_engine_ms_per_block.sync"] <= \
        v["fg_miss_ms_per_block.sync"]
    commit = (v["commit_storage_ms_per_block.sync"]
              + v["commit_account_ms_per_block.sync"]
              + v["commit_root_ms_per_block.sync"])
    assert 0 < commit <= v["fg_commit_ms_per_block.sync"]
    named = sum(v[f"exec_{part}_ms_per_block.sync"]
                for part in ("plan", "post", "checkpoint", "validate",
                             "vector"))
    assert 0 < named < v["fg_execute_ms_per_block.sync"]
    assert 0 <= v["exec_unnamed_share.sync"] < 100
    assert v["exec_plan_ms_per_block.sync"] > 0
    assert v["exec_post_ms_per_block.sync"] > 0
    assert v["exec_world_copy_ms_per_block.sync"] > 0  # nested calls
    # the driver thread's misses are among every thread's
    assert v["fg_miss_ms_per_block.sync"] <= \
        v["node_read_ms_per_block.sync"] * 1.001
