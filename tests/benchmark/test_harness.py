"""The benchmark harness on the CPU: ``run.py --rehearse`` end to end at
tiny sizes, the comparisons that decide ``correct`` seen to fail, the
manifest held to its own naming rules, and the data-driven promise (a
new cell or metric is new files and ``BENCHMARK.json`` entries, no
edit). Says nothing of the chip. No TPU topology call anywhere."""

import io
import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time
from contextlib import redirect_stdout
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import manifest  # noqa: E402
from benchmark.reduce import xplane  # noqa: E402

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
BENCH = manifest.benchmark_json()
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]


def rehearse(cell, seed, trace=0, control=None, seconds=2):
    """run.py's main in this process (one process may hold JAX here, and
    a second rehearsal reuses the first one's compiled programs)."""
    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--rehearse"]
    if control:
        argv += ["--control", control]
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(argv)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), lines


@pytest.fixture(scope="module")
def sync_run():
    return rehearse("sync.dense", seed=2_147_483_777)


@pytest.fixture(scope="module")
def snap_run():
    return rehearse("snap.statesync", seed=3_000_000_019)


# ------------------------------------------------------------ end to end


def test_sync_cell_rehearsal_ends_in_the_contracts_line(sync_run):
    rc, line, lines = sync_run
    assert rc == 0
    assert list(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"sync_blocks_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["device"]["platform"] == "cpu"  # a rehearsal, and says so
    assert set(line["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"}
    # every number compared is printed beside its limit
    checks = [l for l in lines if l.startswith("check: ")]
    assert len(checks) >= 8 and all(" limit " in l for l in checks)


def test_snapshot_cell_rehearsal_ends_in_the_contracts_line(snap_run):
    rc, line, lines = snap_run
    assert rc == 0 and list(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"snap_nodes_per_s", "setup_s"}
    assert any("forged_values_stored = 0" in l for l in lines)


def test_traced_rehearsal_reports_per_layer_metrics_and_breakdown(sync_run):
    rc, line, _ = rehearse("sync.dense", seed=2_147_483_777, trace=1)
    assert rc == 0
    assert list(line) == RESULT_KEYS + ["breakdown"]
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] >= line["device"]["busy_s"]
    per_layer = {m["name"] for m in BENCH["per_layer"]
                 if "sync.dense" in m.get("workloads", ["sync.dense"])}
    assert set(line["metrics"]) <= per_layer
    assert {"fg_busy_ms_per_block.sync", "device_idle_share.sync",
            "compiles_in_window.sync"} <= set(line["metrics"])
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10


# ------------------------------------- `correct` has been seen to fail


def test_a_chain_with_one_wrong_header_root_is_not_correct(sync_run):
    rc, line, lines = rehearse(
        "sync.dense", seed=2_147_483_777, control="wrong-root")
    assert rc == 0 and line["correct"] is False
    assert line["failed"] > 0
    assert any("blocks_failed_or_wrong_root" in l and "FAILED" in l
               for l in lines)


def test_a_syncer_that_skips_the_batch_check_is_not_correct(snap_run):
    """The control: a stubbed batch check accepts the peer's forged
    nodes; they reach the store and the mirror, and the device verify,
    the store read-back and the re-hash all say so."""
    rc, line, lines = rehearse(
        "snap.statesync", seed=3_000_000_019, control="no-batch-check")
    assert rc == 0 and line["correct"] is False
    failed = {l.split()[1] for l in lines
              if l.startswith("check: ") and "FAILED" in l}
    assert {"device_verify_mismatches", "forged_values_stored"} <= failed


def test_timed_path_broken_underneath_is_not_correct(monkeypatch, snap_run):
    """Drives the rest of a run with the mirror's admit dropping every
    node (a part of the batch left out where it is produced)."""
    from khipu_tpu.storage.device_mirror import DeviceNodeMirror

    monkeypatch.setattr(DeviceNodeMirror, "admit", lambda self, items: None)
    rc, line, lines = rehearse("snap.statesync", seed=3_000_000_019)
    assert rc == 0 and line["correct"] is False
    assert any("stored_minus_resident" in l and "FAILED" in l for l in lines)


# ------------------------------------------ the resumed sync's checkpoint


@pytest.fixture(scope="module")
def small_source(tmp_path_factory):
    from benchmark.generators import snapshot

    root, nodes = snapshot.build_source(1200, seed=2_222_222_227)
    path = str(tmp_path_factory.mktemp("src") / "s.npz")
    snapshot.save_source(path, root, nodes)
    return root, nodes, snapshot.load_source(path)


def syncer(store, fetch, batch_size):
    from khipu_tpu.sync.fast_sync import FastSyncStateStorage, StateSyncer

    return StateSyncer(store, FastSyncStateStorage(store.app_state.source),
                       fetch, batch_size=batch_size)


def test_download_order_is_the_order_the_syncer_fetches_in(
        small_source, tmp_path):
    """The generator's breadth-first order, taken with its own reading of
    the nodes' RLP, is the order in which the program's syncer asks."""
    from khipu_tpu.storage.storages import Storages

    root, nodes, src = small_source
    assert src.nodes() == nodes and src.keys(0, 1) == [root]
    asked = []

    def fetch(hashes):
        asked.extend(hashes)
        return {h: nodes[h] for h in hashes}

    store = Storages(engine="kesque", data_dir=str(tmp_path / "a"))
    try:
        syncer(store, fetch, 7).start(root)
    finally:
        store.stop()
    assert asked == src.keys()


@pytest.mark.parametrize("remaining", [1, 150, 600, 1199])
def test_a_sync_resumed_from_the_generated_checkpoint_downloads_the_rest(
        small_source, tmp_path, remaining):
    """The checkpoint the driver writes is a state the syncer itself
    passes through: resumed from it, the syncer asks for exactly the
    nodes not yet downloaded, each once, and ends complete."""
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.fast_sync import (
        STATE_NODE, FastSyncStateStorage, SyncState)

    root, nodes, src = small_source
    done, pending_end = src.resume_point(remaining)
    assert done == len(src) - remaining and done < pending_end <= len(src)
    asked = []

    def fetch(hashes):
        asked.extend(hashes)
        return {h: nodes[h] for h in hashes}

    store = Storages(engine="kesque", data_dir=str(tmp_path / "b"))
    try:
        store.account_node_storage.update(
            [], {h: nodes[h] for h in src.keys(0, done)})
        FastSyncStateStorage(store.app_state.source).put_sync_state(SyncState(
            target_root=root, downloaded_nodes=done,
            pending=[(STATE_NODE, h) for h in src.keys(done, pending_end)]))
        state = syncer(store, fetch, 50).start(root)
        assert asked == src.keys(done)
        assert state.downloaded_nodes == len(src)
        assert all(store.account_node_storage.get(h) == v
                   for h, v in nodes.items())
    finally:
        store.stop()


def test_padded_rows_are_keccak_padded(small_source):
    import numpy as np

    from benchmark.reference.keccak import keccak256_batch

    _, nodes, src = small_source
    for nb in (1, 2, 3, 4):
        idx = np.nonzero(src.lens // 136 + 1 == nb)[0][:5]
        if not len(idx):
            continue
        rows = src.padded_rows(idx, 136 * nb)
        for i, row in zip(idx.tolist(), rows):
            enc = nodes[src.keys(i, i + 1)[0]]
            pad = bytearray(136 * nb - len(enc))
            pad[0] ^= 0x01
            pad[-1] ^= 0x80
            assert row.tobytes() == enc + bytes(pad)
    assert keccak256_batch([nodes[h] for h in src.keys(0, 8)]) == src.keys(0, 8)


def test_a_served_balance_altered_where_it_is_produced_is_not_correct(
        monkeypatch, sync_run):
    from benchmark.drivers import sync as sync_driver

    real = sync_driver.rpc

    def altered(port, method, *params):
        out = real(port, method, *params)
        if method == "eth_getBalance":
            return hex(int(out, 16) + 1)
        return out

    monkeypatch.setattr(sync_driver, "rpc", altered)
    rc, line, lines = rehearse("sync.dense", seed=2_147_483_777)
    assert rc == 0 and line["correct"] is False
    assert any("balance_mismatches" in l and "FAILED" in l for l in lines)


def test_no_result_line_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "sync.dense", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "nothing measured" in out.stderr


# -------------------------------------------------------- the manifest


NAME = manifest.NAME_RE
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def all_names():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[kind]:
            yield kind, e["name"]
    for w in BENCH["workloads"]:
        yield "traffic", w["traffic"]
    for c in BENCH["configs"]:
        for k in c["reduced"]:
            yield "reduced", k
    for name in manifest.listing()["metrics"]:
        yield "metric file", name


@pytest.mark.parametrize("kind,name", sorted(set(all_names())))
def test_every_name_is_made_of_the_allowed_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"],
    ids=lambda m: m["name"])
def test_every_metric_entry_is_well_formed(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in (
        "device_trace", "program_span", "program_counter", "host_clock")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric["name"] in E2E:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        assert set(metric) <= allowed | {"bound"}
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        # its reader exists and is found by name
        spec = manifest.metric_file(metric["name"])
        assert hasattr(manifest.load_module("readers", spec["reader"]), "read")
        # `moves` is an end-to-end metric that each of its cells reports
        moved = E2E[metric["moves"]]
        for cell in metric.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS), (metric["name"], cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_its_files_and_reports_enough(cell):
    c = manifest.cell(cell)
    assert c["chips"] in (1, 4) and len(c["why"]) <= 200
    assert hasattr(
        manifest.load_module("drivers", c["config_file"]["driver"]), "run")
    e2e = [m["name"] for m in manifest.metrics_for(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.metrics_for(cell, "per_layer")
    for key in c["config_entry"]["reduced"]:
        assert key in c["config_file"]["reduced"]
    assert c["config_entry"]["file"].startswith(tuple(BENCH["paths"]))


def test_manifest_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_a_cell_a_configuration_and_a_metric_are_added_as_files(tmp_path):
    """A temp copy gets a new configuration file, a new traffic file, a
    new metric file and new BENCHMARK.json entries; nothing that was
    there is edited; ``run.py --list`` shows them and the loader finds
    the new cell."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    conf = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "fullsync-postmerge.json")))
    conf["name"] = "fullsync-adaptive"
    conf["program"]["sync"]["adaptive_commit"] = True
    (root / "benchmark/configs/fullsync-adaptive.json").write_text(
        json.dumps(conf))
    traffic = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "dense.json")))
    traffic["name"] = "dense-zipf"
    (root / "benchmark/traffic/dense-zipf.json").write_text(json.dumps(traffic))
    (root / "benchmark/metrics/seal_pack_ms_per_window.sync.json").write_text(
        json.dumps({"reader": "span_ms",
                    "args": {"name": "seal.pack", "per": "window"}}))
    bench["configs"].append({
        "name": "fullsync-adaptive", "source": "as fullsync-postmerge",
        "file": "benchmark/configs/fullsync-adaptive.json",
        "reduced": ["accounts", "contracts"], "why": "the default controller"})
    bench["workloads"].append({
        "name": "sync.dense-zipf.adaptive", "config": "fullsync-adaptive",
        "traffic": "dense-zipf", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "seal_pack_ms_per_window.sync", "unit": "ms",
        "better": "lower", "source": "program_span",
        "layer": "collector stages (sync/replay.py)",
        "moves": "sync_blocks_per_s",
        "workloads": ["sync.dense-zipf.adaptive"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--list"],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, PYTHONPATH=REPO))
    assert out.returncode == 0, out.stderr
    listed = json.loads(out.stdout)
    assert "sync.dense-zipf.adaptive" in listed["cells"]
    assert "fullsync-adaptive" in listed["configs"]
    assert "dense-zipf" in listed["traffic"]
    assert "seal_pack_ms_per_window.sync" in listed["metrics"]
    cell = manifest.cell("sync.dense-zipf.adaptive", root=str(root))
    assert cell["config_file"]["program"]["sync"]["adaptive_commit"] is True
    assert [m["name"] for m in manifest.metrics_for(
        "sync.dense-zipf.adaptive", "per_layer", root=str(root))] == [
        "seal_pack_ms_per_window.sync"]
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


# -------------------------------------------------- the trace reduction


@pytest.fixture(scope="module")
def recorded():
    """A 0.1 s trace recorded on a v5e by benchmark/tools/record_trace.py
    (PR 23): three rounds of a Pallas Keccak call, a named 20 ms host
    pause, and a jitted scatter."""
    return xplane.load(os.path.join(HERE, "data", "small.xplane.pb"))


def test_reduction_of_a_recorded_tpu_trace(recorded):
    r = xplane.reduce(recorded)
    assert r["chips"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # three rounds, each with a 20 ms named pause the device sits out
    assert r["window_s"] > 0.06
    assert 100 * (1 - r["busy_s"] / r["window_s"]) > 90
    labels = [name for name, _ in r["device_ops"]]
    assert any(name.split("/")[-1].startswith("mosaic:") for name in labels)
    assert any(name.startswith("jit_scatter/") for name in labels)
    assert 0 < r["mosaic_s"] < r["busy_s"]
    programs = dict(r["programs"])
    assert {"jit_scatter", "jit_go"} <= set(programs)
    # the gaps are named by the annotation the host was inside
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.pause"] == pytest.approx(0.060, rel=0.25)
    assert xplane.clock_offset_ns(recorded) is not None


def test_gap_attribution_and_union():
    assert xplane.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]
    spans = [("outer", 0, 100), ("inner", 40, 60), ("elsewhere", 200, 300)]
    assert xplane._attribute(45, 55, spans) == {"inner": 10}  # innermost
    assert xplane._attribute(30, 70, spans) == {"outer": 20, "inner": 20}
    assert xplane._attribute(90, 130, spans) == {
        "outer": 10, "(no host span)": 30}
    text = ('%run.1 = u32[2,8,8,128] custom-call(u32[2,34,8,128] %x), '
            'custom_call_target="tpu_custom_call"')
    assert xplane.op_label(text) == "mosaic:run"
    assert xplane.op_label("%fusion.12 = u8[4] fusion(u8[4] %p)") == "fusion"


# ------------------------------------------------- the open-loop reader


def test_open_loop_charges_a_stall_to_every_request_behind_it():
    """A stub server answers at once, except that its 10th request
    stalls 0.4 s. With one sender at 50 requests/s, the ~20 requests due
    during the stall are sent late and are timed from when they were
    DUE: their latency is what is left of the stall, not the ~1 ms the
    server then takes; the generator's lateness says the same."""
    import numpy as np

    from benchmark.generators import accounts, rpcload

    stall_s, seen = 0.4, []
    n_accounts = 50
    others = [bytes([i]) * 20 for i in range(n_accounts)]
    extra = np.arange(n_accounts, dtype=np.int64)
    balance = {"0x" + a.hex(): accounts.PLAIN_BALANCE_BASE + int(x)
               for a, x in zip(others, extra)}

    class Stub(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_POST(self):
            req = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
            seen.append(time.perf_counter())
            if len(seen) == 10:
                time.sleep(stall_s)
            body = json.dumps({"jsonrpc": "2.0", "id": req["id"], "result":
                               hex(balance[req["params"][0]])}).encode()
            # one write: headers and body in two small packets would
            # meet Nagle and the client's delayed ACK (40 ms a request)
            self.wfile.write(
                b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
                b"Content-Length: %d\r\n\r\n" % len(body) + body)

        def log_message(self, *a):
            pass

    httpd = HTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    empty = np.zeros((0, 1), dtype=np.int64)
    data = {"others": others, "extra": extra, "senders": [b"\x01" * 20],
            "token": b"\x02" * 20, "roots": [],
            "picks": {"kind": empty, "sender": empty, "receiver": empty,
                      "amount": empty}}
    params = {"rate": 50, "mix": {"eth_getBalance": 1}, "zipf": 0.99,
              "senders": 1, "logs_span": 16}
    try:
        loop = rpcload.OpenLoop(httpd.server_address[1], params, data,
                                seed=4_000_000_007, head=0)
        t_open = time.perf_counter()
        loop.start(t_open, 1.5)
        time.sleep(1.6)
        out = loop.finish(time.perf_counter(), 0)
    finally:
        httpd.shutdown()
    assert out["failed"] == 0 and out["answered"] == out["due"] > 50
    assert all(c.ok for c in out["checks"])
    latency, lateness = out["latency_ms"], out["lateness_ms"]
    # the stalled request itself, and the one queued right behind it
    assert max(latency) >= 1000 * stall_s
    assert 0.6 * 1000 * stall_s < max(lateness) < 1000 * stall_s
    # service time apart from the stall is small: what is left is wait
    service = sorted(a - b for a, b in zip(latency, lateness))
    assert service[len(service) // 2] < 20
    # about stall_s * rate requests were held up behind it
    late = sum(x > 20 for x in lateness)
    assert 0.4 * stall_s * 50 <= late <= 2.5 * stall_s * 50
    assert out["p95_ms"] > 100  # the tail sees the stall; a median would not
