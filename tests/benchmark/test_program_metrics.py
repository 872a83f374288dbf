"""The per-layer metrics that read what the program measures from
inside (PR 25): each new reader on hand-made artefacts, its None cases
(a program that does not serve the counter or the map yet), the join of
a recorded TPU trace with a stage map, and a traced ``--rehearse`` of
each cell reporting the new metrics a CPU run can produce. Says nothing
of the chip. No TPU topology call anywhere."""

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
from benchmark.readers import (  # noqa: E402
    registry_share,
    registry_us,
    scope_share,
    span_offcpu,
    span_tag_ratio,
)
from benchmark.reduce import xplane  # noqa: E402

BENCH = manifest.benchmark_json()
NEW = {
    "snap.statesync": {
        "loop_checkpoint_us_per_node.snap", "loop_queue_us_per_node.snap",
        "loop_parse_us_per_node.snap", "loop_check_us_per_node.snap",
        "loop_untimed_share.snap"},
    "sync.dense": {
        "fg_offcpu_share.sync", "persist_save_offcpu_share.sync",
        "fg_stall_ms_per_block.sync", "fused_hash_share_of_busy.sync",
        "fused_gather_share_of_busy.sync", "fused_subst_share_of_busy.sync",
        "fused_row_amplification.sync"},
}


def span(name, t0, t1, cpu, **tags):
    return SimpleNamespace(name=name, t0=t0, t1=t1, tt0=10.0, tt1=10.0 + cpu,
                           tags=tags, sid=id(tags), parent=None)


# ------------------------------------------------ the manifest entries


@pytest.mark.parametrize("cell", sorted(NEW))
def test_new_metrics_are_entries_with_files_and_workloads(cell):
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    layers = {m["layer"] for m in BENCH["per_layer"]
              if m["name"] not in set().union(*NEW.values())}
    for name in NEW[cell]:
        m = entries[name]
        assert m["workloads"] == [cell]
        assert m["layer"] in layers  # a layer the benchmark already names
        spec = manifest.metric_file(name)
        assert set(spec) == {"reader", "args", "what"}
        manifest.load_module("readers", spec["reader"])
    # appended: what was there is still first, in its order
    assert [m["name"] for m in BENCH["per_layer"]][:17] == [
        "ingress_ms_per_block.sync", "fg_busy_ms_per_block.sync",
        "fg_execute_ms_per_block.sync", "seal_bg_ms_per_window.sync",
        "persist_save_bg_ms_per_window.sync", "store_mb_per_s.sync",
        "device_window_share.sync", "fused_wait_ms_per_window.sync",
        "compiles_in_window.sync", "keccak_share_of_busy.sync",
        "device_idle_share.sync", "loop_us_per_node.snap",
        "mirror_admit_us_per_node.snap", "store_update_us_per_node.snap",
        "verify_ms.snap", "keccak_verify_hbm_share.snap",
        "device_idle_share.snap"]


# ------------------------------------------------- registry_us / _share


@pytest.fixture
def fastsync_slot():
    """A hand-made ``SyncStats`` in the registry's ``fastsync`` slot."""
    from khipu_tpu.observability.registry import REGISTRY
    from khipu_tpu.sync.fast_sync import SyncStats

    stats = SyncStats()
    stats.phases.update(queue=0.5, fetch=0.1, check=0.02, parse=0.03,
                        store=0.05, checkpoint=1.2)
    stats.nodes["state"] = 2000
    stats.loop_seconds = 2.0
    REGISTRY.register_collector("fastsync", stats.samples)
    yield stats
    REGISTRY.unregister_collector("fastsync")


def test_registry_readers_on_a_hand_made_slot(fastsync_slot):
    art = {"nodes": 2000}
    assert registry_us.read(art, phase="checkpoint") == pytest.approx(600.0)
    assert registry_us.read(art, phase="queue") == pytest.approx(250.0)
    assert registry_us.read(art, phase="parse") == pytest.approx(15.0)
    assert registry_us.read(art, phase="flush") == 0.0  # booked, empty
    # 2.0 s of loop, 1.9 s inside phases
    assert registry_share.read(art) == pytest.approx(5.0)
    assert registry_us.read(art, phase="no-such-phase") is None


def test_registry_readers_refuse_another_syncers_numbers(fastsync_slot):
    """The slot belongs to the newest syncer; if that is not the one the
    driver counted (warm-up's, or none stored anything), read nothing."""
    for art in ({"nodes": 1999}, {"nodes": 0}, {}):
        assert registry_us.read(art, phase="queue") is None
        assert registry_share.read(art) is None


def test_registry_readers_without_the_counters():
    from khipu_tpu.observability.registry import REGISTRY

    REGISTRY.unregister_collector("fastsync")  # the parent's program
    assert registry_us.read({"nodes": 2000}, phase="queue") is None
    assert registry_share.read({"nodes": 2000}) is None


# --------------------------------------------- span_offcpu / _tag_ratio


def test_offcpu_share_of_named_spans():
    art = {"spans": [
        span("window.build", 0.0, 1.0, cpu=0.75),
        span("window.build", 2.0, 3.0, cpu=0.25),
        span("window.persist", 0.0, 4.0, cpu=1.0),
        span("window.save", 4.0, 6.0, cpu=2.5),  # clock grain: cpu > wall
    ]}
    assert span_offcpu.read(art, names=["window.build"]) == pytest.approx(50)
    assert span_offcpu.read(
        art, names=["window.persist", "window.save"]) == pytest.approx(50)
    assert span_offcpu.read(art, names=["window.seal"]) is None
    assert span_offcpu.read({"spans": []}, names=["window.build"]) is None


def test_tag_ratio_and_a_program_that_lacks_the_tags():
    args = {"name": "fused.dispatch", "num": ["rows_padded", "rounds"],
            "den": "nodes"}
    art = {"spans": [
        span("fused.dispatch", 0, 1, 0, nodes=1000, rows_padded=4096,
             rounds=4),
        span("fused.dispatch", 1, 2, 0, nodes=3000, rows_padded=8192,
             rounds=8),
        span("seal.upload", 1, 2, 0, nbytes=1),
    ]}
    assert span_tag_ratio.read(art, **args) == pytest.approx(
        (4096 * 4 + 8192 * 8) / 4000)
    old = {"spans": [span("fused.dispatch", 0, 1, 0, nodes=1000)]}
    assert span_tag_ratio.read(old, **args) is None
    assert span_tag_ratio.read({"spans": []}, **args) is None


# ------------------------------------------------------- scope_share


@pytest.fixture(scope="module")
def recorded():
    """PR 23's 0.1 s v5e trace: three runs of ``jit_scatter`` (a fusion,
    a sort, copies) between runs of a Keccak program."""
    return xplane.load(os.path.join(HERE, "data", "small.xplane.pb"))


def executed(profile, program):
    _chip, ops, mods = xplane._device_lines(profile)[0]
    return {scope_share._name(e)
            for run in scope_share._runs(ops, mods, program) for e in run}


def test_stage_join_on_a_recorded_tpu_trace(recorded):
    names = executed(recorded, "jit_scatter")
    assert {"fusion", "sort"} <= names and "run.1" not in names
    fits = {n: None for n in names}
    fits.update(fusion="fused.subst", sort="fused.subst",
                convert_reduce_fusion="fused.gather")
    # another signature's program: numbers its instructions differently
    # (lacks `sort`, has `sort.7`) and would put `fusion` elsewhere
    other = {n: "fused.hash" for n in names if n != "sort"}
    other["sort.7"] = "fused.subst"
    got = scope_share.by_scope(
        recorded, {"a": fits, "b": other}, "jit_scatter")
    sec = got["seconds"]
    assert set(sec) == {"fused.subst", "fused.gather", "unmapped"}
    busy = xplane.reduce(recorded)["busy_s"]
    programs = dict(xplane.reduce(recorded)["programs_all"])
    assert sum(sec.values()) <= programs["jit_scatter"] * 1.001 < busy
    assert sec["fused.subst"] > sec["unmapped"] > 0
    assert [n for n, _ in got["worst"]][0].startswith("copy")
    # no cached program has every executed instruction: all are merged,
    # and a name they disagree on is ambiguous
    del fits["sort"]
    got = scope_share.by_scope(
        recorded, {"a": fits, "b": other}, "jit_scatter")
    assert got["seconds"]["ambiguous"] > 0
    assert "fused.subst" not in got["seconds"]
    assert scope_share.by_scope(recorded, {"a": fits}, "jit_absent") == {
        "seconds": {}, "worst": []}


def test_scope_share_with_an_empty_map_or_no_trace(monkeypatch):
    from khipu_tpu.trie import fused

    monkeypatch.setattr(fused, "scope_map", dict)
    trace = SimpleNamespace(xplane_path=lambda: os.path.join(
        HERE, "data", "small.xplane.pb"))
    art = {"trace": trace, "spans": []}
    assert scope_share.read(art, scope="fused.subst") is None
    assert art["scope_joined"] is None
    monkeypatch.delattr(fused, "scope_map")  # the parent's program
    assert scope_share.read({"trace": trace, "spans": []},
                            scope="fused.subst") is None
    assert scope_share.read({"trace": None}, scope="fused.subst") is None


def test_scopes_of_on_lines_of_the_v5e_executable():
    """Lines of ``jit_fused_fixpoint`` as compiled for a v5e (shapes and
    argument lists shortened). The compiler gives what it makes of the
    scatter no metadata: the custom fusion is the one stage of what was
    fused into it; the ``sort`` stays None although it reads a
    ``fused.subst`` reshape, and so does a layout ``copy`` of a hash
    output; a kernel body that mentions a scope in its backend_config
    does not count."""
    from khipu_tpu.trie.fused import _scopes_of

    subst = ('metadata={op_name="jit(fused_fixpoint)/while/body/'
             'closed_call/fused.subst/c1/reshape" stack_frame_id=25}')
    text = """\
HloModule jit_fused_fixpoint, is_scheduled=true

%compare (name: s32[], name.1: s32[]) -> pred[] {
  %name = s32[]{:T(128)} parameter(0)
  %name.1 = s32[]{:T(128)} parameter(1)
  ROOT %lt.1 = pred[]{:T(512)} compare(%name, %name.1), direction=LT
}

%fused_computation.12.clone.clone.clone (param_0.356: u8[557056], param_1.430: s32[131072], param_2.375: u8[131072]) -> u8[557056] {
  %param_0.356 = u8[557056]{0:T(1024)(128)(4,1)S(1)} parameter(0)
  %param_1.430 = s32[131072]{0:T(1024)S(1)} parameter(1)
  %reshape.409 = s32[131072]{0:T(1024)} reshape(%param_1.430)
  %transpose.203 = s32[131072]{0:T(1024)} transpose(%reshape.409), dimensions={0}
  %param_2.375 = u8[131072]{0:T(1024)(128)(4,1)S(1)} parameter(2)
  %reshape.410 = u8[131072]{0:T(1024)(128)(4,1)} reshape(%param_2.375), SUBST
  %transpose.204 = u8[131072]{0:T(1024)(128)(4,1)} transpose(%reshape.410), dimensions={0}, SUBST
  ROOT %scatter.35 = u8[557056]{0:T(1024)(128)(4,1)S(1)} scatter(%param_0.356, %transpose.203, %transpose.204), update_window_dims={}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, indices_are_sorted=true, to_apply=%region_1.9
}

%wide.region_0.14.clone.sunk (wide.arg_tuple.1: (s32[], u8[4096,136])) -> (s32[], u8[4096,136]) {
  %wide.arg_tuple.1 = (s32[], u8[4096,136]) parameter(0)
  %keccak_nb1_sponge.4 = u32[4,8,8,128]{3,2,1,0:T(8,128)} custom-call(%wide.arg_tuple.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(fused_fixpoint)/while/body/closed_call/fused.hash/jit(keccak_from_bytes_nb1)/jit(keccak_tiles_nb1)/keccak_nb1_sponge"}, backend_config={"custom_call_config": {"body": "op_name=\\"fused.gather\\""}}
  %copy.99 = u32[4,8,8,128]{2,3,1,0:T(8,128)} copy(%keccak_nb1_sponge.4)
  %broadcast_select_fusion.8 = s32[131072]{0:T(1024)S(1)} fusion(%wide.arg_tuple.1), kind=kLoop, calls=%compare
  %reshape.424 = u8[131072]{0:T(1024)(128)(4,1)S(1)} reshape(%copy.99), SUBST
  %sort.8 = (s32[131072]{0:T(1024)S(1)}, u8[131072]{0:T(1024)(128)(4,1)S(1)}) sort(%broadcast_select_fusion.8, %reshape.424), dimensions={0}, to_apply=%compare
  %fusion.43 = u8[557056]{0:T(1024)(128)(4,1)S(1)} fusion(%copy.99, %sort.8), kind=kCustom, calls=%fused_computation.12.clone.clone.clone
  ROOT %tuple.9 = (s32[], u8[4096,136]) tuple(%fusion.43)
}
""".replace("SUBST", subst)
    got = _scopes_of(text)
    assert {k: v for k, v in got.items() if v} == {
        "reshape.410": "fused.subst", "transpose.204": "fused.subst",
        "keccak_nb1_sponge.4": "fused.hash",
        "reshape.424": "fused.subst", "fusion.43": "fused.subst",
    }
    assert {"sort.8", "copy.99", "broadcast_select_fusion.8", "scatter.35",
            "tuple.9", "lt.1", "name.1"} <= set(got)


def test_scope_map_on_the_jnp_backend_names_every_stage():
    from khipu_tpu.trie import fused

    fused.compile_cache(((1, 16, 1024, 16), (2, 16, 1024, 0)), 4, True, 64)
    per_program = fused.scope_map()
    label = next(k for k in per_program if "1x16/1024+a16" in k)
    assert "backend=jnp" in label
    stages = set(per_program[label].values())
    assert {"fused.hash", "fused.gather", "fused.subst", "fused.admit",
            None} <= stages
    assert fused.scope_map()[label] is per_program[label]  # kept, not re-read


# ------------------------------------------- traced rehearsals, end to end


@pytest.fixture(autouse=True)
def own_run_dir(monkeypatch, tmp_path):
    """``run.py`` empties ``<BENCH_DIR>/cache/_run`` at the start of
    every run, and xdist runs test_harness.py's rehearsals in another
    process at the same time: these get a directory of their own."""
    monkeypatch.setattr(bench_run, "BENCH_DIR", str(tmp_path))


def rehearse(cell, seed):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                             "--seconds", "2", "--trace", "1", "--rehearse"])
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), lines


def test_traced_snapshot_rehearsal_reports_the_loop_from_inside():
    rc, line, _ = rehearse("snap.statesync", seed=3_000_000_019)
    assert rc == 0 and line["correct"] is True
    got = line["metrics"]
    assert NEW["snap.statesync"] <= set(got)
    assert {"loop_us_per_node.snap", "verify_ms.snap"} <= set(got)  # kept
    assert 0 <= got["loop_untimed_share.snap"]["value"] < 5
    for name in NEW["snap.statesync"] - {"loop_untimed_share.snap"}:
        assert got[name]["unit"] == "us" and got[name]["value"] > 0


def test_traced_sync_rehearsal_reports_threads_and_stages():
    rc, line, lines = rehearse("sync.dense", seed=2_147_483_777)
    assert rc == 0 and line["correct"] is True
    got = line["metrics"]
    assert NEW["sync.dense"] <= set(got)
    for name in ("fg_offcpu_share.sync", "persist_save_offcpu_share.sync"):
        assert 0 <= got[name]["value"] <= 100
    stages = [got[f"fused_{s}_share_of_busy.sync"]["value"]
              for s in ("hash", "gather", "subst")]
    assert all(v > 0 for v in stages) and sum(stages) <= 100
    assert got["fused_row_amplification.sync"]["value"] >= 1
    assert any(l.startswith("scope_share: ") and "unmapped" in l
               for l in lines)
    # no program on the cell's path is anonymous any more
    ops = [name for name, _ in line["breakdown"]["device_ops"]]
    assert not any(n.startswith(("jit_run/", "jit_go/")) for n in ops)
