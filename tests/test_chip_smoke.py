"""chip_smoke.py on the CPU: every leg at a tiny size through the same
importable functions the chip run uses, plus the script's own refusal
to run without a TPU. Proves control flow and answers; says nothing of
the chip (that is what ``python chip_smoke.py`` through the chip tool
is for)."""

import os
import subprocess
import sys

import pytest

import chip_smoke
from khipu_tpu.observability.profiler import LEDGER
from khipu_tpu.observability.recorder import compile_log

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def nodes():
    return chip_smoke.make_nodes(2048, seed=0)


def test_script_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    lines = out.stdout.strip().splitlines()
    assert len(lines) == 1  # the stamp, and no result
    assert lines[0].startswith("chip_smoke: platform=cpu ")
    assert '"ok"' not in out.stdout


def test_result_line_is_exactly_the_chip_checks_contract():
    """The chip check refuses a last line with any key beyond ok/device
    and platform/kind/count (PR 21's first submission carried the leg
    detail in it); detail belongs on the line before."""
    import json

    out = json.loads(chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    ))
    assert out == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
    }}
    assert list(out) == ["ok", "device"]
    assert type(out["device"]["count"]) is int


def test_kernel_leg(nodes):
    raw, digests = nodes
    out = chip_smoke.leg_kernel(raw, digests, class_rows=64)
    assert out == {
        "impl": "jnp", "rows_576": 2048,
        "class_rows": {1: 64, 2: 64, 3: 64, 4: 64},
    }


def test_kernel_leg_catches_a_wrong_digest(nodes):
    raw, digests = nodes
    forged = digests.copy()
    forged[7, 0] ^= 1
    with pytest.raises(AssertionError, match="1 of 2048"):
        chip_smoke.leg_kernel(raw, forged, class_rows=16)


def test_snapshot_leg(nodes):
    raw, digests = nodes
    assert chip_smoke.leg_snapshot(raw, digests) == {
        "resident": 2048, "mismatches": 0, "forged_detected": 1,
    }
    forged = digests.copy()
    forged[100, 5] ^= 0x80
    with pytest.raises(AssertionError, match="1 mismatches"):
        chip_smoke.leg_snapshot(raw, forged)


def test_state_replay_device_work_and_serve_legs(tmp_path):
    """The node legs in order on one data dir, as ``main`` runs them:
    genesis on the persistent engine, bridge replay of the ERC-20 mix,
    the did-the-device-do-it audit, HTTP reads. On the CPU the device
    path is pinned (the probe would honestly pick host) and the fused
    program's backend is the jnp sponge."""
    LEDGER.reset()
    compile_log.reset()
    data_dir = str(tmp_path / "node")
    keys, senders, others, alloc = chip_smoke.make_alloc(600, senders=12)
    cfg = chip_smoke.smoke_config(
        data_dir, adaptive_commit=False, mirror_rows=4096
    )
    state = chip_smoke.leg_state(alloc, data_dir, cfg)
    assert state["accounts"] == 600 and state["trie_nodes"] > 600

    values, node = chip_smoke.leg_replay(
        cfg, state["spec"], keys, senders, others,
        blocks=8, txs_per_block=12, batch_blocks=4,
    )
    try:
        assert values["blocks"] == 8 and values["txs"] == 96
        assert values["roots_checked"] == 8 and values["windows"] == 2
        work = chip_smoke.leg_device_work(
            node, values["windows"], backend="jnp", adaptive=False
        )
        assert work["fused_dispatch_spans"] >= 2
        assert work["seal.upload.h2d.bytes"] > 0
        assert work["native_keccak"] and work["rlp_is_c"]
        # the audit is not decorative: asked for the kernel that did
        # not run, it fails
        with pytest.raises(AssertionError, match="pallas"):
            chip_smoke.leg_device_work(
                node, values["windows"], backend="pallas", adaptive=False
            )
        served = chip_smoke.leg_serve(node, work)
        assert served["head"] == 8
        assert served["token_balances_nonzero"] > 0
    finally:
        node.shutdown()
    LEDGER.disable()
    LEDGER.reset()


def test_multichip_leg_runs_on_four_devices_when_present():
    """The tests' virtual mesh has 8 devices, so the leg runs (tiny);
    that it refuses to build a mesh it was not given is covered by
    ``__graft_entry__._ensure_devices`` raising."""
    out = chip_smoke.leg_multichip(rows=64, session_keys=48)
    assert out["ran"] and out["devices"] == 4
    assert len(out["input_device_ids"]) == 4
    assert len(out["table_device_ids"]) == 4

    import __graft_entry__ as graft

    with pytest.raises(RuntimeError, match="need 64 devices"):
        graft._ensure_devices(64)


def test_compile_meter_merges_nested_trace_intervals():
    """jax reports an inner jit's trace inside its caller's; summing
    them counted the same seconds twice (356 s of "tracing" inside a
    186 s leg on the chip)."""
    union = chip_smoke.CompileMeter._union
    assert union([]) == 0.0
    assert union([(0.0, 10.0), (2.0, 3.0), (4.0, 9.0)]) == 10.0  # nested
    assert union([(5.0, 6.0), (0.0, 1.0)]) == 2.0  # disjoint, unsorted
    assert union([(0.0, 2.0), (1.0, 3.0), (10.0, 11.0)]) == 4.0  # overlap
