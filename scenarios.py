#!/usr/bin/env python
"""Scripted scenario gates: each sub-command builds a fixture chain,
drives one operator's scenario against the real planes and gates on
its invariants (exit 1 / AssertionError on a breach), printing one JSON
line per result.

  serve       JSON-RPC under mid-sync load, overload shed (docs/serving.md)
  serve-http  the same over the HTTP fleet and its router
  rebalance   elastic shard join + retire under a hard deadline
  reorg       replay, fork, reorg and crash recovery (docs/recovery.md)
  ingest      Kesque ingest and recovery (docs/kesque.md)
  getlogs     eth_getLogs against the bloom index
  gameday     seeded fault schedule over all of it (docs/gameday.md)

These are invariants, never rates: a time printed here is a CPU time
of this host. Speed is measured by ``benchmark/run.py`` on the chip.
"""

import argparse
import json
import sys
import time


def emit(metric, value, unit, **extra):
    # "vs_baseline" is a constant of the line format since the compare
    # gate went; consumers of the lines see what they always saw
    line = {
        "metric": metric,
        "value": value,
        "unit": unit,
        "vs_baseline": 0.0,
    }
    line.update(extra)
    print(json.dumps(line), flush=True)


def _quantile(vals, q):
    s = sorted(vals)
    if not s:
        return 0.0
    return s[min(len(s) - 1, int(q * len(s)))]


def _p50(vals):
    return _quantile(vals, 0.50)


def _p99(vals):
    return _quantile(vals, 0.99)


def _replay_keys(nsenders, seed_base=1):
    from khipu_tpu.base.crypto.secp256k1 import (
        privkey_to_pubkey,
        pubkey_to_address,
    )

    keys = [(i + seed_base).to_bytes(32, "big") for i in range(nsenders)]
    addrs = [pubkey_to_address(privkey_to_pubkey(k)) for k in keys]
    return keys, addrs


def _serve_setup(n_blocks, txs_per_block, window=2, depth=2):
    """Fixture chain + fresh target + serving plane wired the way
    ServiceBoard.start_serving does it, but with bench-scaled admission
    capacity (in-process dispatch is ~100x faster than a socket path,
    so the production limits would never saturate in-harness)."""
    import dataclasses

    from khipu_tpu.config import (
        ServingConfig,
        SyncConfig,
        TelemetryConfig,
        fixture_config,
    )
    from khipu_tpu.domain.block import Block as _Block
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.domain.transaction import Transaction, sign_transaction
    from khipu_tpu.jsonrpc import EthService, JsonRpcServer
    from khipu_tpu.observability.registry import MetricsRegistry
    from khipu_tpu.observability.telemetry import (
        ClusterTelemetry,
        Watchdog,
        decode_metrics,
        encode_metrics,
    )
    from khipu_tpu.serving import AdmissionController, ReadView, ServingPlane
    from khipu_tpu.serving.admission import (
        cluster_pressure,
        journal_pressure,
        pipeline_pressure,
        txpool_pressure,
    )
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.chain_builder import ChainBuilder
    from khipu_tpu.txpool import PendingTransactionsPool

    # short queue + short wait: an admitted request may absorb at most
    # ~4ms of queueing, keeping the admitted tail near the baseline
    # tail — excess beyond that sheds instead of waiting
    serve_cfg = ServingConfig(queue_timeout=0.004, max_queue=4)
    cfg = dataclasses.replace(
        fixture_config(chain_id=1),
        # parallel_tx ON (the production default): the serve bench's
        # import rides the conflict-aware scheduler, so tx passports
        # carry real schedule/execute lane stamps (vector-transfer for
        # this all-transfers fixture), not just the serial path
        sync=SyncConfig(
            parallel_tx=True, commit_window_blocks=window,
            pipeline_depth=depth,
        ),
        serving=serve_cfg,
    )
    nsenders = 8
    keys, addrs = _replay_keys(nsenders)
    receivers = [
        bytes.fromhex("%040x" % (0xFEED0000 + i)) for i in range(32)
    ]
    alloc = {a: 10**24 for a in addrs}
    genesis = GenesisSpec(alloc=alloc)
    # both branches share blocks 1..ancestor; the post-load fork switch
    # retracts the base suffix so >=1 serve-bench journey crosses a
    # reorg retraction (the passport acceptance), then adopts a longer
    # branch whose suffix re-mines DIFFERENT txs (value offset)
    ancestor = max(1, n_blocks - 2)

    def build(total, value_off, suffix_coinbase):
        builder = ChainBuilder(
            Blockchain(Storages(), cfg), cfg, genesis
        )
        blocks, nonces = [], [0] * nsenders
        for n in range(total):
            diverged = n >= ancestor
            txs = []
            for j in range(txs_per_block):
                i = j % nsenders
                txs.append(
                    sign_transaction(
                        Transaction(
                            nonces[i], 10**9, 21_000,
                            receivers[(j * 7 + n) % len(receivers)],
                            1_000 + n + (value_off if diverged else 0),
                        ),
                        keys[i], chain_id=1,
                    )
                )
                nonces[i] += 1
            blocks.append(builder.add_block(
                txs,
                coinbase=suffix_coinbase if diverged else b"\xaa" * 20,
                timestamp=10 * (n + 1),
            ))
        return blocks

    blocks = build(n_blocks, 0, b"\xaa" * 20)
    fork = build(n_blocks + 1, 10**6, b"\xbb" * 20)
    wire = [_Block.decode(b.encode()) for b in blocks]
    fork_wire = [_Block.decode(b.encode()) for b in fork]
    target = Blockchain(Storages(), cfg)
    target.load_genesis(genesis)

    # small pool so the write backlog the load phases build (no miner
    # drains it) organically trips txpool_pressure past shed_write_at —
    # the overload step then sheds with -32005 the way a saturated node
    # would, not via an injected signal. Sized so the baseline + normal
    # phases (~140 writes at the mixed profile's 10%) stay under the
    # 0.85 write threshold and the 4x step is what crosses it
    pool = PendingTransactionsPool(capacity=192)
    read_view = ReadView(target)

    # cluster telemetry over two in-process fake shards: each "shard"
    # is its own MetricsRegistry scraped through the telemetry codec —
    # the bench exercises merge + health + the cluster admission signal
    # without paying for real gRPC servers
    tel_cfg = TelemetryConfig(
        enabled=True, scrape_interval=0.5, staleness_s=5.0
    )
    shard_regs = {}
    for i, ep in enumerate(("bench-shard-a:0", "bench-shard-b:0")):
        reg = MetricsRegistry()
        reg.gauge("khipu_pipeline_in_flight").set(i)
        reg.counter("khipu_shard_requests_total").inc(10 + i)
        reg.histogram(
            "khipu_rpc_latency_seconds", buckets=(0.001, 0.01, 0.1)
        ).observe(0.005)
        shard_regs[ep] = reg

    class _Scrape:
        def __init__(self, reg):
            self.reg = reg

        def get_metrics(self):
            return decode_metrics(encode_metrics(self.reg))

        def close(self):
            pass

    telemetry = ClusterTelemetry(
        list(shard_regs), config=tel_cfg,
        client_factory=lambda ep: _Scrape(shard_regs[ep]),
    )
    watchdog = Watchdog(
        config=tel_cfg,
        journal_depth=lambda: target.storages.window_journal.depth,
        telemetry=telemetry,
    )

    admission = AdmissionController(
        serve_cfg,
        limits={"cheap": 4, "read": 4, "execute": 2, "write": 2},
        signals=[
            pipeline_pressure(),
            journal_pressure(target.storages, depth),
            txpool_pressure(pool),
            cluster_pressure(telemetry),
        ],
    )
    plane = ServingPlane(serve_cfg, read_view=read_view,
                         admission=admission)
    service = EthService(
        target, cfg, pool, read_view=read_view, serving=plane,
        telemetry=telemetry,
    )
    server = JsonRpcServer(service, serving=plane)
    return (cfg, target, wire, fork_wire, ancestor, genesis, addrs,
            receivers, plane, service, server, telemetry, watchdog)


def bench_serve(smoke=False):
    """``scenarios.py serve``: the serving-plane bench — mixed RPC load
    against a node MID-SYNC (the windowed pipelined replay importing
    blocks on another thread), with the loadgen's read-your-writes
    checker on. Three phases: (A) unloaded read-only baseline p99,
    (B) >=1000 mixed RPCs while the pipeline imports (the headline
    qps/p50/p99/shed line), (C) a 4x client step over the configured
    capacity — admission sheds -32005 while the p99 of ADMITTED
    requests stays bounded (vs collapsing for everyone, which is what
    the unbounded thread-per-request default does)."""
    import threading

    from khipu_tpu.observability.journey import JOURNEY
    from khipu_tpu.serving.loadgen import (
        MIXED,
        InProcessTransport,
        LoadGenerator,
    )
    from khipu_tpu.serving.replica import PrimaryFeed, ReplicaDriver
    from khipu_tpu.sync.replay import ReplayDriver

    n_blocks = 6 if smoke else 48
    (cfg, target, wire, fork_wire, ancestor, genesis, addrs, receivers,
     plane, service, server, telemetry,
     watchdog) = _serve_setup(n_blocks, txs_per_block=6)
    # the tx passport rides the whole bench: every import, pool, lane,
    # seal, durable, reorg, and replica-visibility edge is stamped
    JOURNEY.reset()
    JOURNEY.enable()
    # one read replica tails the primary's durable chain throughout —
    # its replica.visible stamps feed the ingress->replica_visible SLO
    replica = ReplicaDriver("r1", PrimaryFeed(target), cfg,
                            genesis).start()
    transport = InProcessTransport(server)
    nonce_addrs = ["0x" + a.hex() for a in addrs]
    # balances are checked on ACCUMULATE-ONLY addresses (receivers +
    # coinbase): monotone by construction, so any regression the
    # checker sees is a real torn/stale read
    balance_addrs = ["0x" + r.hex() for r in receivers]
    balance_addrs.append("0x" + (b"\xaa" * 20).hex())

    def gen(profile, clients, reqs, seed, key_base):
        return LoadGenerator(
            transport, profile, clients=clients, seed=seed,
            max_requests=reqs,
            nonce_addresses=nonce_addrs,
            balance_addresses=balance_addrs,
            client_keys=[
                (key_base + i).to_bytes(32, "big")
                for i in range(clients)
            ],
            chain_id=1,
        )

    # ALL phases run MID-SYNC: the pipelined replay imports the
    # fixture on its own thread, throttled so the import (and its
    # seal/collect window traffic) spans the whole load run. The
    # baseline too — the overload ratio must isolate what OVERLOAD
    # does to admitted requests, not what sharing a GIL with the
    # replay thread does to everything
    driver = ReplayDriver(target, cfg, read_view=plane.read_view)
    delay = 0.01 if smoke else 0.05

    def throttled():
        import time as _t

        for b in wire:
            yield b
            _t.sleep(delay)

    sync_done = threading.Event()

    def run_sync():
        try:
            driver.replay(throttled())
        finally:
            sync_done.set()

    sync_thread = threading.Thread(target=run_sync, daemon=True)
    sync_thread.start()

    # phase A: light-load baseline — SAME mixed profile as the loaded
    # phases (comparing a cheap-reads-only baseline against a mix that
    # includes eth_call would skew the overload ratio by method mix,
    # not by load)
    baseline = gen(MIXED, 2, 50 if smoke else 200, 11,
                   0x0A11_0000).run()
    p99_unloaded = baseline.p99()
    baseline_mid_sync = not sync_done.is_set()

    mixed = gen(MIXED, 4, 25 if smoke else 250, 22, 0x0B22_0000).run()
    mid_sync = not sync_done.is_set()  # the load really ran mid-import

    # phase C: 4x the client count over the same capacity
    overload = gen(MIXED, 16, 10 if smoke else 75, 33,
                   0x0C33_0000).run()
    overload_mid_sync = not sync_done.is_set()
    sync_thread.join(timeout=120)

    # ---- the tx passport acceptance. The primary switches to the
    # longer fork branch (load is done, so the RYW checker's monotone
    # assumption is not in play): the base suffix RETRACTS under live
    # journeys, then the replica mirrors the switch. After that, the
    # lineage plane must answer for every fixture tx: a complete,
    # monotonically ordered event list, >=1 journey crossing the
    # retraction, >=1 that rode the vectorized transfer lane
    from khipu_tpu.sync.reorg import ReorgManager

    reorg = ReorgManager(target, cfg, driver=driver,
                         read_view=plane.read_view)
    reorg.switch(ancestor, fork_wire[ancestor:])
    fork_tip = len(fork_wire)
    assert target.best_block_number == fork_tip
    deadline = time.perf_counter() + 60
    while (time.perf_counter() < deadline
           and replica.head_number() < fork_tip):
        time.sleep(0.02)
    assert replica.head_number() == fork_tip, replica.snapshot()
    replica.stop()

    all_hashes = [stx.hash for b in wire
                  for stx in b.body.transactions]
    complete = 0
    retract_crossing = 0
    for h in all_hashes:
        ex = JOURNEY.export(h)
        if ex is None:
            continue
        ts = [e["t"] for e in ex["events"]]
        edges = [e["edge"] for e in ex["events"]]
        if ts != sorted(ts):
            continue  # out-of-order passport: not complete
        if "ingress" in edges and "durable" in edges:
            complete += 1
        if "reorg.retract" in edges:
            retract_crossing += 1
    coverage = complete / len(all_hashes)
    vector_lane = sum(
        1 for j in JOURNEY.journeys()
        for (_t, e, _n, _tid, d) in j.events
        if e == "execute" and d and d.get("lane") == "vector-transfer"
    )
    assert coverage >= 0.99, (
        f"journey coverage {coverage:.4f} < 0.99 "
        f"({complete}/{len(all_hashes)} complete)"
    )
    assert retract_crossing >= 1, (
        "no journey crossed the reorg retraction"
    )
    assert vector_lane >= 1, "no journey rode the vector lane"
    # the RPC surface serves the same passport, ordered
    retracted_h = next(
        h for h in all_hashes
        if (j := JOURNEY.get(h)) is not None
        and any(e[1] == "reorg.retract" for e in j.events)
    )
    rpc_j = service.khipu_tx_journey("0x" + retracted_h.hex())
    rpc_edges = [e["edge"] for e in rpc_j["events"]]
    assert "reorg.retract" in rpc_edges, rpc_edges
    assert rpc_edges.index("ingress") < rpc_edges.index("durable"), (
        rpc_edges
    )

    durable_ms = JOURNEY.latencies_ms("durable")
    visible_ms = JOURNEY.latencies_ms("replica.visible")
    assert durable_ms, "no ingress->durable journey latencies"
    assert visible_ms, "no ingress->replica_visible journey latencies"
    emit(
        "tx_ingress_to_durable_p99_ms",
        round(_p99(durable_ms), 3), "ms",
        samples=len(durable_ms),
        p50_ms=round(_p50(durable_ms), 3),
        journey_coverage=round(coverage, 4),
        journeys_retracted=retract_crossing,
        vector_lane_executes=vector_lane,
        note="per-tx passport: first ingress stamp to the window's "
             "crash-survivable commit mark (throttled import — the "
             "number includes the deliberate window pacing)",
    )
    emit(
        "tx_ingress_to_replica_visible_p99_ms",
        round(_p99(visible_ms), 3), "ms",
        samples=len(visible_ms),
        p50_ms=round(_p50(visible_ms), 3),
        note="first ingress stamp to a replica tail passing the tx's "
             "block — the fleet's consistent-read promise, per tx",
    )

    violations = (
        len(mixed.violations) + len(overload.violations)
        + len(baseline.violations)
    )
    if smoke:
        # force one real -32005 through the whole stack (pressure pins
        # high -> write class sheds), so the exposition check below
        # covers the shed family too
        plane.admission.signals.append(lambda: 1.0)
        resp = transport.call("eth_sendRawTransaction", ["0x00"])
        assert resp.get("error", {}).get("code") == -32005, resp
        plane.admission.signals.pop()
        # exercise one ledger crossing so the lazily-registered
        # transfer families exist, then pin them to exactly one TYPE
        # line each alongside the serving families
        from khipu_tpu.observability.profiler import H2D, LEDGER

        was_on = LEDGER.enabled
        LEDGER.enable()
        LEDGER.record("bench.smoke", H2D, 1)
        if not was_on:
            LEDGER.disable()
        # cluster telemetry: scrape the fake shards, then pin the new
        # families in the DRIVER exposition and the one-TYPE-per-family
        # invariant in the MERGED exposition. A deliberate
        # journal-runaway trip (depth bound 0 vs the real journal is
        # wrong on purpose — the trip must fire deterministically)
        # populates khipu_watchdog_trips_total before the pin.
        telemetry.scrape_once()
        import dataclasses as _dc

        trip_dog = type(watchdog)(
            config=_dc.replace(watchdog.config, journal_runaway_depth=0),
            pipeline={}, journal_depth=lambda: 1, telemetry=telemetry,
        )
        tripped = trip_dog.check_once()
        assert "journal_runaway" in tripped, tripped
        text = service.khipu_metrics_text()
        lat = text.count("# TYPE khipu_rpc_latency_seconds histogram")
        shed = text.count("# TYPE khipu_rpc_shed_total counter")
        tb = text.count(
            "# TYPE khipu_device_transfer_bytes_total counter"
        )
        ts = text.count(
            "# TYPE khipu_device_transfer_seconds_total counter"
        )
        sh = text.count("# TYPE khipu_shard_health gauge")
        wd = text.count("# TYPE khipu_watchdog_trips_total counter")
        assert lat == 1, f"latency histogram TYPE lines: {lat}"
        assert shed == 1, f"shed counter TYPE lines: {shed}"
        assert tb == 1, f"transfer bytes TYPE lines: {tb}"
        assert ts == 1, f"transfer seconds TYPE lines: {ts}"
        assert sh == 1, f"shard health TYPE lines: {sh}"
        assert wd == 1, f"watchdog trips TYPE lines: {wd}"
        # ISSUE 13 families: the off-driver seal stage gauges, the
        # adaptive-commit controller, the async-copy fallback counter
        # and the mirror spill watermark must each expose exactly once
        # (importing the modules registers them; replay ran above)
        import khipu_tpu.ledger.schedule  # noqa: F401
        import khipu_tpu.storage.device_mirror  # noqa: F401
        import khipu_tpu.sync.adaptive  # noqa: F401
        import khipu_tpu.sync.prefetch  # noqa: F401
        import khipu_tpu.trie.fused  # noqa: F401

        text = service.khipu_metrics_text()
        for fam in (
            "khipu_pipeline_stage_seal_depth",
            "khipu_pipeline_stage_seal_busy_s",
            "khipu_adaptive_device_mode",
            "khipu_adaptive_flips_total",
            "khipu_adaptive_depth_hint",
            "khipu_adaptive_flap_suppressed_total",
            "khipu_fused_async_copy_fallbacks",
            "khipu_mirror_spilled_tiles",
            "khipu_mirror_unspilled_evictions",
            # ISSUE 14 families: pipelined sender recovery + the
            # conflict-aware scheduler's batch gauges
            "khipu_sender_prefetch_hits",
            "khipu_sender_prefetch_misses",
            "khipu_sender_prefetch_blocks",
            "khipu_sender_prefetch_evictions",
            "khipu_exec_batch_planned_blocks",
            "khipu_exec_batch_fast_txs",
            "khipu_exec_batch_call_txs",
            "khipu_exec_batch_residue_txs",
            "khipu_exec_batch_batches",
            "khipu_exec_batch_max_batch_width",
            "khipu_exec_batch_mispredictions",
            "khipu_exec_batch_fallbacks",
            "khipu_exec_batch_templates",
            "khipu_exec_batch_opaque_codes",
            # ISSUE 17 families: the trusted templated-call lane
            "khipu_exec_batch_vector_call_txs",
            "khipu_exec_batch_checked_call_txs",
            "khipu_exec_batch_trusted_templates",
            "khipu_exec_batch_effect_retirements",
        ):
            n = text.count(f"# TYPE {fam} gauge")
            assert n == 1, f"{fam} TYPE lines: {n}"
        # tx passport families: the commit-latency histogram (one TYPE
        # line covering both edge= children) and the journey board's
        # registry collector
        for fam, kind in (
            ("khipu_tx_commit_latency_seconds", "histogram"),
            ("khipu_tx_journey_enabled", "gauge"),
            ("khipu_tx_journeys_tracked", "gauge"),
            ("khipu_tx_journeys_pinned", "gauge"),
            ("khipu_tx_journey_events_total", "counter"),
            ("khipu_tx_journeys_evicted_total", "counter"),
        ):
            n = text.count(f"# TYPE {fam} {kind}")
            assert n == 1, f"{fam} TYPE lines: {n}"
        assert 'edge="durable"' in text, "durable histogram child missing"
        assert 'edge="replica_visible"' in text, (
            "replica_visible histogram child missing"
        )
        assert 'khipu_watchdog_trips_total{kind="journal_runaway"} 1' \
            in text, text
        ctext = service.khipu_cluster_metrics_text()
        ctypes = [
            line.split()[2] for line in ctext.splitlines()
            if line.startswith("# TYPE")
        ]
        assert len(ctypes) == len(set(ctypes)), (
            f"duplicate families in merged exposition: {ctypes}"
        )
        assert 'shard="bench-shard-a:0"' in ctext, ctext
        assert violations == 0, (
            mixed.violations + overload.violations
        )
        emit(
            "serve_smoke", mixed.requests + overload.requests,
            "requests",
            violations=violations,
            exposition_families_ok=True,
            transfer_families_ok=True,
            cluster_families_ok=True,
            watchdog_trip_ok=True,
            slo_methods=len(plane.slo.evaluate()["methods"]),
        )
        return

    assert mixed.requests >= 1000, mixed.requests
    assert violations == 0, (
        baseline.violations + mixed.violations + overload.violations
    )[:5]
    assert overload.shed > 0, "4x step produced no -32005 sheds"
    p99_admitted = overload.p99()
    # admitted requests must not collapse: overload p99 stays within
    # 5x the worse of (unloaded, mid-sync-normal-load) p99 — the whole
    # point of shedding excess instead of queueing it
    p99_floor = max(p99_unloaded, mixed.p99())
    assert p99_admitted <= 5 * p99_floor, (
        f"admitted p99 collapsed under overload: "
        f"{p99_admitted * 1e3:.3f}ms vs floor {p99_floor * 1e3:.3f}ms"
    )
    budget = plane.slo.evaluate()["errorBudget"]
    # shed attribution: which pressure signal (pipeline / journal /
    # txpool / cluster) got the blame for each pressure shed, plus the
    # live per-signal readout — the cluster signal reports even when
    # healthy (0.0), proving the plane is wired in
    telemetry.scrape_once()
    snap = plane.admission.snapshot()
    assert "cluster" in snap["pressureBySignal"], snap
    emit(
        "rpc_mid_sync_qps",
        round(mixed.qps, 1),
        "req/s",
        rpc_p50_ms=round(mixed.p50() * 1e3, 3),
        rpc_p99_ms=round(mixed.p99() * 1e3, 3),
        shed_rate=round(mixed.shed_rate, 4),
        requests=mixed.requests,
        mid_sync=mid_sync,
        baseline_mid_sync=baseline_mid_sync,
        p99_unloaded_ms=round(p99_unloaded * 1e3, 3),
        ryw_violations=violations,
        note="mixed profile, RYW checker on, windowed pipeline "
             "importing on a background thread",
    )
    emit(
        "rpc_overload_shed_rate",
        round(overload.shed_rate, 4),
        "fraction",
        clients_step="4x",
        shed=overload.shed,
        requests=overload.requests,
        mid_sync=overload_mid_sync,
        p99_admitted_ms=round(p99_admitted * 1e3, 3),
        p99_unloaded_ms=round(p99_unloaded * 1e3, 3),
        p99_admitted_vs_unloaded=round(
            p99_admitted / p99_unloaded if p99_unloaded else 0, 2
        ),
        error_budget_consumed=budget["budgetConsumed"],
        shed_by_signal=snap["shedBySignal"],
        pressure_by_signal=snap["pressureBySignal"],
        note="admitted p99 must stay bounded while excess load sheds "
             "with -32005 (SEDA-style staged admission)",
    )


def _fleet_setup(n_blocks, txs_per_block=4, sync_kwargs=None,
                 serving_kwargs=None):
    """Primary + fork branch + 2 read replicas + FleetRouter, wired
    for ``scenarios.py serve-http`` (and, with ``sync_kwargs``
    overriding the target's SyncConfig — e.g. a windowed pipeline so
    the collector stages are live — for ``scenarios.py gameday``).
    Fixture chains are always BUILT under the serial window=1 config,
    whatever the target runs.

    The fixture chain is shaped so the loadgen's monotone RYW checker
    stays SOUND across the mid-run reorg: blocks up to the fork
    ancestor move the checked senders/receivers, the diverged suffix
    (both branches) only touches a disjoint sender/receiver set. A
    reorg legitimately rewinds suffix state to the ancestor — but the
    checked addresses are identical at every height >= ancestor on
    both branches, so any regression the checker reports is a REAL
    stale read (a replica serving below a token floor), never reorg
    semantics."""
    import dataclasses

    from khipu_tpu.config import (
        ServingConfig,
        SyncConfig,
        TelemetryConfig,
        fixture_config,
    )
    from khipu_tpu.domain.block import Block as _Block
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.domain.transaction import Transaction, sign_transaction
    from khipu_tpu.jsonrpc import EthService, JsonRpcServer
    from khipu_tpu.observability.telemetry import ClusterTelemetry
    from khipu_tpu.serving import AdmissionController, ReadView, ServingPlane
    from khipu_tpu.serving.admission import (
        journal_pressure,
        pipeline_pressure,
        txpool_pressure,
    )
    from khipu_tpu.serving.fleet import FleetRouter
    from khipu_tpu.serving.replica import PrimaryFeed, ReplicaDriver
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.chain_builder import ChainBuilder
    from khipu_tpu.sync.reorg import ReorgManager
    from khipu_tpu.txpool import PendingTransactionsPool

    serve_cfg = ServingConfig(
        queue_timeout=0.004, max_queue=4, **(serving_kwargs or {})
    )
    build_cfg = dataclasses.replace(
        fixture_config(chain_id=1),
        sync=SyncConfig(parallel_tx=False, commit_window_blocks=1),
        serving=serve_cfg,
    )
    cfg = build_cfg if sync_kwargs is None else dataclasses.replace(
        build_cfg, sync=SyncConfig(**sync_kwargs),
    )
    nsenders = 8
    keys, addrs = _replay_keys(nsenders)
    checked_receivers = [
        bytes.fromhex("%040x" % (0xFEED0000 + i)) for i in range(16)
    ]
    suffix_receivers = [
        bytes.fromhex("%040x" % (0xD00D0000 + i)) for i in range(16)
    ]
    alloc = {a: 10**24 for a in addrs}
    genesis = GenesisSpec(alloc=alloc)
    ancestor = n_blocks - 2  # both branches share blocks 1..ancestor

    def build(total, value_off, suffix_coinbase):
        builder = ChainBuilder(
            Blockchain(Storages(), build_cfg), build_cfg, genesis
        )
        blocks, nonces = [], [0] * nsenders
        for n in range(total):
            diverged = n >= ancestor
            txs = []
            for j in range(txs_per_block):
                # checked half of the key/receiver space drives the
                # shared prefix; the disjoint half drives the suffix
                i = (4 + j % 4) if diverged else (j % 4)
                to_pool = (
                    suffix_receivers if diverged else checked_receivers
                )
                txs.append(sign_transaction(
                    Transaction(
                        nonces[i], 10**9, 21_000,
                        to_pool[(j * 7 + n) % len(to_pool)],
                        1_000 + n + (value_off if diverged else 0),
                    ),
                    keys[i], chain_id=1,
                ))
                nonces[i] += 1
            blocks.append(builder.add_block(
                txs,
                coinbase=suffix_coinbase if diverged else b"\xaa" * 20,
                timestamp=10 * (n + 1),
            ))
        return blocks

    base = build(n_blocks, 0, b"\xaa" * 20)
    fork = build(n_blocks + 2, 10**6, b"\xbb" * 20)
    wire = [_Block.decode(b.encode()) for b in base]
    fork_wire = [_Block.decode(b.encode()) for b in fork]

    target = Blockchain(Storages(), cfg)
    target.load_genesis(genesis)
    # tiny pool: the overload phases' write fraction fills it early,
    # pinning txpool_pressure at 1.0 — past shed_read_at, so a SINGLE
    # driver sheds its read classes too. That pressure isolation is
    # the fleet's whole value: replicas don't share the primary's
    # pressure signals, so reads keep flowing
    pool = PendingTransactionsPool(capacity=24)
    read_view = ReadView(target)
    # bench-scaled HARD: one driver's whole read-side capacity is 4
    # in-flight (2 cheap + 2 read). That is the denominator of the
    # fleet-vs-solo gate — the replicas run the production
    # DEFAULT_LIMITS, which is the capacity the fleet adds
    admission = AdmissionController(
        serve_cfg,
        limits={"cheap": 2, "read": 2, "execute": 2, "write": 2},
        signals=[
            pipeline_pressure(),
            journal_pressure(target.storages, 2),
            txpool_pressure(pool),
        ],
    )
    plane = ServingPlane(serve_cfg, read_view=read_view,
                         admission=admission)
    service = EthService(
        target, cfg, pool, read_view=read_view, serving=plane,
    )
    from khipu_tpu.sync.replay import ReplayDriver

    driver = ReplayDriver(target, cfg, read_view=read_view)
    reorg = ReorgManager(
        target, cfg, driver=driver, read_view=read_view
    )
    reorg.add_listener(service._filter_manager.note_reorg)
    server = JsonRpcServer(service, serving=plane)

    feed = PrimaryFeed(target)
    replicas = [
        ReplicaDriver(f"r{i}", feed, cfg, genesis).start()
        for i in (1, 2)
    ]
    # replicas ARE the scrape clients: a killed replica fails its
    # scrape and khipu_shard_health drops to 0.0 — the health signal
    # the router's pick-2 consumes
    by_name = {r.name: r for r in replicas}
    telemetry = ClusterTelemetry(
        list(by_name),
        config=TelemetryConfig(
            enabled=True, scrape_interval=0.2, staleness_s=5.0
        ),
        client_factory=lambda ep: by_name[ep],
    )
    router = FleetRouter(
        server, replicas, telemetry=telemetry, reorg_manager=reorg,
    )
    return (cfg, target, wire, fork_wire, ancestor, addrs,
            checked_receivers, plane, service, server, driver, reorg,
            replicas, telemetry, router, build_cfg, genesis)


def bench_serve_http(smoke=False):
    """``scenarios.py serve-http``: the replica-fleet bench over the
    REAL wire path — keep-alive HTTP into a FleetRouter fronting a
    primary plus two read replicas, with the read-your-writes checker
    (consistent-read tokens) on the whole time. Three phases: (A)
    unloaded floor over HTTP, (B) a 4x MIXED overload against the
    primary ALONE while a pinned ``primary_distress`` pressure signal
    models the node states PR 10/13 pin to 1.0 (failed scrapes,
    journal runaway) — past ``shed_read_at``, the single driver sheds
    its read classes along with writes and only cheap survives, (C)
    the SAME offered load and the SAME distress through the fleet,
    during which one replica is KILLED mid-phase and the primary
    REORGS under the load (the survivor must mirror the switch;
    tokens anchored to retracted blocks re-anchor to the fork
    ancestor). The gate: at equal offered load and an equal-or-better
    admitted p99, the fleet completes >=2x the requests the solo
    driver does — replicas do NOT share the primary's pressure
    signals, so primary distress cannot take the read plane down with
    it. That pressure isolation is the capacity a read-replica fleet
    actually adds (full mode; smoke pins mechanics + exposition
    instead)."""
    import threading

    from khipu_tpu.serving.loadgen import (
        MIXED,
        READ_ONLY,
        HttpTransport,
        LoadGenerator,
    )
    from khipu_tpu.serving.router import ReadToken

    n_blocks = 10 if smoke else 48
    (cfg, target, wire, fork_wire, ancestor, addrs, receivers, plane,
     service, server, driver, reorg, replicas, telemetry,
     router, _build_cfg, _genesis) = _fleet_setup(n_blocks)
    port = router.start_http()
    url = f"http://127.0.0.1:{port}/"
    nonce_addrs = ["0x" + a.hex() for a in addrs[:4]]
    balance_addrs = ["0x" + r.hex() for r in receivers]

    def gen(transport, profile, clients, reqs, seed, key_base):
        return LoadGenerator(
            transport, profile, clients=clients, seed=seed,
            max_requests=reqs,
            nonce_addresses=nonce_addrs,
            balance_addresses=balance_addrs,
            client_keys=[
                (key_base + i).to_bytes(32, "big")
                for i in range(clients)
            ],
            chain_id=1,
        )

    # background import throttled to span the load phases: replicas
    # tail the committed chain WHILE clients read through the router,
    # so token floors are live (a replica can genuinely be behind)
    delay = 0.01 if smoke else 0.03
    sync_done = threading.Event()

    def run_sync():
        import time as _t

        try:
            for b in wire:
                stats = driver.replay([b])
                _t.sleep(delay)
        finally:
            sync_done.set()

    sync_thread = threading.Thread(target=run_sync, daemon=True)
    sync_thread.start()

    # phase A: unloaded floor over the wire (keep-alive path)
    floor_t = HttpTransport(url)
    floor = gen(floor_t, READ_ONLY, 2, 30 if smoke else 150, 11,
                0x0A11_0000).run()
    p99_floor = floor.p99()

    # phase B (full mode): the 4x MIXED overload against the primary
    # alone, on its own HTTP front, under pinned primary distress.
    # The txpool alone cannot push pressure past shed_read_at — its
    # sheds self-limit at the write threshold (writes stop feeding the
    # pool, the fill freezes below 0.95: reads-survive-writes-shed is
    # the admission plane working). Distress models the states the
    # observability plane pins to 1.0 — a failed shard scrape, a
    # journal runaway — where a SINGLE driver has no choice but to
    # shed reads too
    over_clients = 8 if smoke else 32
    over_reqs = 25 if smoke else 40
    solo = None

    def primary_distress():
        return 1.0

    primary_distress.signal_name = "primary_distress"
    if not smoke:
        plane.admission.add_signal(primary_distress)
        solo_port = server.start()
        solo_t = HttpTransport(f"http://127.0.0.1:{solo_port}/")
        solo = gen(solo_t, MIXED, over_clients, over_reqs, 33,
                   0x0C33_0000).run()
        server.stop()

    # phase C: the SAME offered load and the SAME distress through
    # the fleet; one replica dies mid-phase (this is the
    # latency-gated window — failover must not cost the admitted tail
    # its budget)
    kill_timer = threading.Timer(
        0.3 if smoke else 1.0, replicas[0].kill
    )
    kill_timer.start()
    over_t = HttpTransport(url)
    overload = gen(over_t, MIXED, over_clients, over_reqs, 22,
                   0x0B22_0000).run()
    if primary_distress in plane.admission.signals:
        plane.admission.signals.remove(primary_distress)
    kill_timer.cancel()
    if replicas[0].alive():  # tiny smoke runs can beat the timer
        replicas[0].kill()
    sync_thread.join(timeout=120)

    # phase D: the primary switches to the longer fork branch UNDER
    # live token-bearing traffic. The switch (and each replica's
    # mirrored switch) re-executes the adopted suffix — a real CPU
    # burst, so this phase checks CONSISTENCY (zero RYW violations
    # across the retraction), not tail latency
    reorged = threading.Event()

    def run_reorg():
        reorg.switch(ancestor, fork_wire[ancestor:])
        reorged.set()

    reorg_thread = threading.Thread(target=run_reorg, daemon=True)
    ryw_t = HttpTransport(url)
    ryw_gen = gen(ryw_t, READ_ONLY, 2 if smoke else 4,
                  15 if smoke else 40, 44, 0x0D44_0000)
    reorg_thread.start()
    ryw = ryw_gen.run()
    reorg_thread.join(timeout=120)
    assert reorged.is_set(), "fork switch never ran"

    # the survivor must mirror the primary's switch and converge on
    # the adopted branch tip
    deadline = time.perf_counter() + 30
    fork_tip = len(fork_wire)
    while (time.perf_counter() < deadline
           and replicas[1].head_number() < fork_tip):
        time.sleep(0.02)
    assert replicas[1].head_number() == fork_tip, replicas[1].snapshot()
    assert replicas[1].switches_mirrored >= 1, replicas[1].snapshot()
    assert not replicas[0].alive()

    # a token anchored to a RETRACTED block must re-anchor, and an
    # unservable floor must redirect to the primary — both counted
    stale = ReadToken(1, ancestor + 1,
                      wire[ancestor].header.hash).encode()
    resp = over_t.call("eth_blockNumber", [], token=stale)
    assert "result" in resp, resp
    assert router.tokens_reanchored >= 1, router.snapshot()
    before = router.ryw_redirects
    future = ReadToken(1, fork_tip + 10_000, None).encode()
    resp = over_t.call("eth_blockNumber", [], token=future)
    assert resp["result"] == hex(fork_tip), resp
    assert router.ryw_redirects > before, router.snapshot()

    # dead replica = failed scrape = health 0.0 (what pick-2 consumes)
    telemetry.scrape_once()
    scores = telemetry.health_scores()
    assert scores[replicas[0].name].score == 0.0, scores
    assert scores[replicas[1].name].score > 0.0, scores
    # nothing below needs the survivor tailing; a caller that lives on
    # (a test) must not inherit its thread
    replicas[1].stop()

    violations = (
        len(floor.violations) + len(overload.violations)
        + len(ryw.violations)
    )
    if solo is not None:
        violations += len(solo.violations)
    assert violations == 0, (
        floor.violations + overload.violations + ryw.violations
        + (solo.violations if solo is not None else [])
    )[:5]
    overhead = overload.transport_overhead or {}

    if smoke:
        # exposition: every fleet family exactly once
        text = service.khipu_metrics_text()
        for fam, kind in (
            ("khipu_fleet_reads_per_sec", "gauge"),
            ("khipu_fleet_requests_total", "counter"),
            ("khipu_fleet_ryw_redirects_total", "counter"),
            ("khipu_fleet_tokens_reanchored_total", "counter"),
            ("khipu_replica_lag_blocks", "gauge"),
        ):
            n = text.count(f"# TYPE {fam} {kind}")
            assert n == 1, f"{fam} TYPE lines: {n}"
        router.stop_http()
        emit(
            "fleet_serve_smoke",
            floor.requests + overload.requests + ryw.requests,
            "requests",
            ryw_violations=violations,
            ryw_redirects=router.ryw_redirects,
            tokens_reanchored=router.tokens_reanchored,
            replica_kill_ok=True,
            switch_mirrored=replicas[1].switches_mirrored,
            transport_overhead_p50_ms=overhead.get("p50Ms"),
            reconnects=overhead.get("reconnects"),
            exposition_families_ok=True,
        )
        return

    # the capacity gate: equal offered load, equal-or-better admitted
    # p99 — the fleet must COMPLETE >=2x what the pressure-shedding
    # solo driver did (replicas don't share the primary's pressure
    # signals, so the saturated write plane can't shed the reads)
    fleet_qps = overload.ok / overload.seconds
    fleet_p99 = overload.p99()
    solo_qps = solo.ok / solo.seconds if solo.seconds else 0.0
    assert solo.shed > 0, "solo driver never shed under 4x overload"
    assert overload.ok >= 2 * solo.ok, (
        f"fleet completed {overload.ok}/{overload.requests} vs solo "
        f"{solo.ok}/{solo.requests} at equal offered load — "
        f"expected >=2x"
    )
    assert fleet_p99 <= max(solo.p99(), 5 * p99_floor), (
        f"fleet p99 {fleet_p99 * 1e3:.3f}ms worse than solo "
        f"{solo.p99() * 1e3:.3f}ms and 5x floor"
    )
    router.stop_http()
    max_lag = max(r.lag_blocks() for r in replicas[1:])
    emit(
        "fleet_reads_per_sec", round(router.reads_per_sec(), 1),
        "req/s",
        fleet_completed=overload.ok,
        solo_completed=solo.ok,
        fleet_vs_solo=round(overload.ok / solo.ok, 2) if solo.ok else 0,
        fleet_admitted_qps=round(fleet_qps, 1),
        solo_admitted_qps=round(solo_qps, 1),
        fleet_shed_rate=round(overload.shed_rate, 4),
        solo_shed_rate=round(solo.shed_rate, 4),
        fleet_p99_ms=round(fleet_p99 * 1e3, 3),
        solo_p99_ms=round(solo.p99() * 1e3, 3),
        p99_floor_ms=round(p99_floor * 1e3, 3),
        ryw_violations=violations,
        note="equal 4x MIXED overload over keep-alive HTTP under "
             "pinned primary distress; the fleet phase rode a replica "
             "kill, and the reorg-under-traffic phase held zero RYW "
             "violations with tokens on",
    )
    emit(
        "replica_lag_blocks", max_lag, "blocks",
        survivor_head=replicas[1].head_number(),
        switches_mirrored=replicas[1].switches_mirrored,
    )
    emit(
        "ryw_redirects_total", router.ryw_redirects, "redirects",
        tokens_reanchored=router.tokens_reanchored,
        reads_replica=router.reads_replica,
        reads_primary=router.reads_primary,
    )
    emit(
        "transport_overhead_ms", overhead.get("p50Ms", 0.0), "ms",
        p99_ms=overhead.get("p99Ms"),
        samples=overhead.get("samples"),
        reconnects=overhead.get("reconnects"),
        note="wall minus X-Khipu-Served-Ms on the persistent "
             "keep-alive connections",
    )


def bench_rebalance(smoke=False, deadline_s=120.0):
    """``scenarios.py rebalance``: elastic-membership smoke/bench — a
    3-shard in-process cluster takes a 4th shard through the full
    epoch-fenced join (plan / stream / cutover) and then retires an
    original. Emits ``shard_boot_to_serving_seconds`` (join call to
    the first content-verified read served BY the new endpoint) and
    ``rebalance_keys_per_sec``. Runs under a HARD deadline on a worker
    thread: a wedged cutover exits 1 instead of hanging the gate."""
    import threading

    from khipu_tpu.base.crypto.keccak import keccak256
    from khipu_tpu.cluster import Rebalancer, ShardedNodeClient
    from khipu_tpu.cluster.ring import _point

    class _Shard:
        def __init__(self):
            self.store = {}

        def get_node_data(self, hashes):
            return {
                h: self.store[h] for h in hashes if h in self.store
            }

        def put_node_data(self, nodes):
            self.store.update(nodes)
            return len(nodes)

        def stream_node_data(self, ranges, cursor, count):
            snap = dict(self.store)
            keys = sorted(
                k for k in snap
                if cursor < k
                and any(lo <= _point(k) < hi for lo, hi in ranges)
            )
            page = keys[:count]
            done = len(keys) <= count
            nxt = page[-1] if page else bytes(cursor)
            return done, nxt, [(k, snap[k]) for k in page]

        def ping(self, payload=b""):
            return payload

        def close(self):
            pass

    n_keys = 2_000 if smoke else 20_000
    shards = {ep: _Shard() for ep in ("s0", "s1", "s2", "s3")}
    client = ShardedNodeClient(
        ["s0", "s1", "s2"],
        channel_factory=lambda ep: shards[ep],
        sleep=lambda s: None,
    )
    rb = Rebalancer(client, batch=384)
    data = {}
    for i in range(n_keys):
        v = b"rebalance bench node %d" % i
        data[keccak256(v)] = v
    client.replicate(data)

    result = {}

    def drive():
        t0 = time.perf_counter()
        streamed = rb.join("s3")
        t_join = time.perf_counter() - t0
        # first verified read SERVED BY the new shard: pick a key the
        # new epoch assigns to it and fetch through the client
        served = None
        for h, v in data.items():
            if client.ring.replicas_for(h)[0] == "s3":
                got = client.fetch([h])
                assert got == {h: v}, "wrong bytes from joined shard"
                served = h
                break
        assert served is not None, "new shard owns no primaries"
        result["boot_to_serving_s"] = time.perf_counter() - t0
        result["join_s"] = t_join
        result["streamed"] = streamed
        rb.retire("s0")
        assert set(client.ring.members) == {"s1", "s2", "s3"}

    worker = threading.Thread(target=drive, daemon=True)
    worker.start()
    worker.join(timeout=deadline_s)
    if worker.is_alive() or "boot_to_serving_s" not in result:
        print(
            f"bench_rebalance: FAILED — join/retire did not complete "
            f"within {deadline_s}s (state={rb.status()})",
            file=sys.stderr,
        )
        sys.exit(1)
    keys_per_sec = (
        result["streamed"] / result["join_s"]
        if result["join_s"] > 0 else 0.0
    )
    emit(
        "shard_boot_to_serving_seconds",
        round(result["boot_to_serving_s"], 4),
        "seconds",
        keys_streamed=result["streamed"],
        epoch=client.ring.epoch,
        note="join() call to the first content-verified read served "
             "by the new shard (in-process transports)",
    )
    emit(
        "rebalance_keys_per_sec",
        round(keys_per_sec, 1),
        "keys/s",
        dataset_keys=n_keys,
        batch=rb.batch,
        completed=rb.completed,
        aborts=rb.aborts,
        moved_fraction=round(rb.last_moved_fraction, 4),
    )


def bench_reorg(smoke=False, deadline_s=120.0):
    """``scenarios.py reorg``: the fork-battle fixture — a node serving
    balance reads through a ReadView while a heavier branch displaces
    its tip. Two rounds: (1) the switch is KILLED mid-adopt at a
    ``reorg.*`` chaos seam and recovered in-process off the journaled
    intent (emits ``reorg_recover_seconds``); (2) a clean switch with
    a block filter attached (emits ``reorg_switch_blocks_per_sec``).
    The poller must never observe a balance outside the two legal
    chain states (old tip / fork point) — a torn read exits 1. Smoke
    additionally pins the ``khipu_reorg_*`` families to exactly one
    TYPE line each and trips the ``reorg_storm`` watchdog kind.
    Runs under a HARD deadline: a wedged switch exits 1, not hangs."""
    import dataclasses
    import threading

    from khipu_tpu.base.crypto.secp256k1 import (
        privkey_to_pubkey,
        pubkey_to_address,
    )
    from khipu_tpu.chaos import FaultPlan, FaultRule, InjectedDeath, active
    from khipu_tpu.config import SyncConfig, TelemetryConfig, fixture_config
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.domain.transaction import Transaction, sign_transaction
    from khipu_tpu.jsonrpc.filters import FilterManager
    from khipu_tpu.observability.registry import REGISTRY
    from khipu_tpu.observability.telemetry import Watchdog
    from khipu_tpu.serving.readview import ReadView
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.chain_builder import ChainBuilder
    from khipu_tpu.sync.journal import recover
    from khipu_tpu.sync.reorg import ReorgManager
    from khipu_tpu.sync.replay import ReplayDriver, ReplayStats
    from khipu_tpu.txpool import PendingTransactionsPool

    cfg = dataclasses.replace(
        fixture_config(chain_id=1),
        sync=SyncConfig(commit_window_blocks=1, parallel_tx=False),
    )
    keys = [(i + 1).to_bytes(32, "big") for i in range(4)]
    addrs = [pubkey_to_address(privkey_to_pubkey(k)) for k in keys]
    genesis = GenesisSpec(alloc={a: 1000 * 10**18 for a in addrs})
    miner_a, miner_b = b"\xaa" * 20, b"\xbb" * 20

    n_base = 8 if smoke else 24
    diverge = n_base - 3  # 3 orphaned blocks, 5 adopted
    n_fork = n_base + 2

    def build(n, diverged_suffix):
        builder = ChainBuilder(Blockchain(Storages(), cfg), cfg, genesis)
        blocks, nonces = [], [0, 0, 0, 0]
        for k in range(n):
            i = k % 4
            dv = diverged_suffix and k >= diverge
            blocks.append(builder.add_block(
                [sign_transaction(
                    Transaction(nonces[i], 10**9, 21_000,
                                addrs[(i + 1) % 4],
                                100 + k + (1000 if dv else 0)),
                    keys[i], chain_id=1,
                )],
                coinbase=miner_b if dv else miner_a,
                timestamp=10 * (k + 1),
            ))
            nonces[i] += 1
        return builder.blockchain, blocks

    base_bc, base = build(n_base, False)
    fork_bc, fork = build(n_fork, True)

    def fresh_node():
        bc = Blockchain(Storages(), cfg)
        bc.load_genesis(genesis)
        driver = ReplayDriver(bc, cfg)
        stats = ReplayStats()
        for b in base:
            driver._execute_and_insert(b, stats)
        return bc, driver

    def bal(bc, number):
        h = bc.get_header_by_number(number)
        acct = bc.get_account(miner_a, h.state_root)
        return 0 if acct is None else acct.balance

    old_val = bal(base_bc, n_base)
    anc_val = bal(base_bc, diverge)  # == new-chain value (fork suffix
    legal = {old_val, anc_val}       # is miner_b's)
    result = {}

    def drive():
        # ---- round 1: killed mid-adopt, recovered off the journal
        bc, driver = fresh_node()
        pool = PendingTransactionsPool()
        view = ReadView(bc)
        mgr = ReorgManager(bc, cfg, driver=driver, txpool=pool,
                           read_view=view)
        stop = threading.Event()
        violations = []

        def poll():
            while not stop.is_set():
                try:
                    _n, acct = view.get_account(miner_a)
                    v = 0 if acct is None else acct.balance
                    if v not in legal:
                        violations.append(v)
                except Exception as e:  # a reader crash IS a violation
                    violations.append(repr(e))
                    return

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        try:
            plan = FaultPlan(seed=42, rules=[
                FaultRule("reorg.adopt", "die", times=1, after=2)
            ])
            died = False
            try:
                with active(plan):
                    mgr.switch(diverge, fork[diverge:])
            except InjectedDeath:
                died = True
            assert died, "chaos seam reorg.adopt never fired"
            t0 = time.perf_counter()
            report = recover(bc, config=cfg, txpool=pool)
            result["recover_s"] = time.perf_counter() - t0
            assert report.reorgs_completed == 1, report.actions
        finally:
            stop.set()
            poller.join(timeout=10)
        assert not violations, violations[:5]
        ref = fork_bc.get_header_by_number(n_fork)
        assert bc.storages.app_state.best_block_number == n_fork
        assert bc.get_header_by_number(n_fork).state_root \
            == ref.state_root, "recovered tip diverges from fresh replay"
        assert bc.storages.window_journal.pending() == []
        adopted_txh = {
            tx.hash for b in fork[diverge:] for tx in b.body.transactions
        }
        for b in base[diverge:]:
            for tx in b.body.transactions:
                assert (tx.hash in adopted_txh
                        or pool.get(tx.hash) is not None), (
                    "orphaned tx neither re-mined nor pool-resident"
                )

        # ---- round 2: clean switch, block filter riding the listener
        bc2, driver2 = fresh_node()
        pool2 = PendingTransactionsPool()
        mgr2 = ReorgManager(bc2, cfg, driver=driver2, txpool=pool2)
        fm = FilterManager(bc2)
        fid = fm.new_block_filter()
        fm.changes(fid)  # advance the cursor to the old tip
        mgr2.add_listener(fm.note_reorg)
        t0 = time.perf_counter()
        done = mgr2.switch(diverge, fork[diverge:])
        result["switch_s"] = time.perf_counter() - t0
        result["adopted"] = done
        result["recycled"] = mgr2.recycled_txs
        assert fm.changes(fid) == [b.hash for b in fork[diverge:]], (
            "block filter missed the adopted branch"
        )
        result["mgr"] = mgr2

    worker = threading.Thread(target=drive, daemon=True)
    worker.start()
    worker.join(timeout=deadline_s)
    if worker.is_alive() or "switch_s" not in result:
        print(
            f"bench_reorg: FAILED — switch/recover did not complete "
            f"within {deadline_s}s",
            file=sys.stderr,
        )
        sys.exit(1)

    if smoke:
        # deterministic reorg_storm trip (injected clock + source),
        # then pin the khipu_reorg_* families to exactly one TYPE line
        # each and the storm kind in the same exposition
        count, clock = [0], [100.0]
        dog = Watchdog(
            config=TelemetryConfig(
                enabled=True, reorg_storm_count=3,
                reorg_storm_window_s=60.0,
            ),
            pipeline={}, clock=lambda: clock[0],
            reorg=lambda: count[0],
        )
        dog.check_once()
        tripped = []
        for _ in range(3):
            count[0] += 1
            clock[0] += 5.0
            tripped = dog.check_once()
        assert "reorg_storm" in tripped, tripped
        text = REGISTRY.prometheus_text()
        for fam, kind in (
            ("khipu_reorg_total", "counter"),
            ("khipu_reorg_refused_total", "counter"),
            ("khipu_reorg_depth", "gauge"),
            ("khipu_reorg_orphaned_blocks_total", "counter"),
            ("khipu_reorg_recycled_txs_total", "counter"),
        ):
            n = text.count(f"# TYPE {fam} {kind}")
            assert n == 1, f"{fam} TYPE lines: {n}"
        assert 'khipu_watchdog_trips_total{kind="reorg_storm"} 1' \
            in text, "reorg_storm trip missing from exposition"
        emit(
            "reorg_smoke", result["adopted"], "blocks",
            recover_s=round(result["recover_s"], 4),
            recycled_txs=result["recycled"],
            reorg_families_ok=True,
            storm_trip_ok=True,
        )
        return

    emit(
        "reorg_switch_blocks_per_sec",
        round(result["adopted"] / result["switch_s"], 1)
        if result["switch_s"] > 0 else 0.0,
        "blocks/s",
        depth=n_base - diverge,
        adopted=result["adopted"],
        recycled_txs=result["recycled"],
        note="journaled two-phase switch incl. fence, intent fsync, "
             "rollback, re-execution of the adopted branch and orphan "
             "recycling",
    )
    emit(
        "reorg_recover_seconds",
        round(result["recover_s"], 4),
        "seconds",
        killed_at="reorg.adopt",
        outcome="rolled_forward",
        note="in-process journal recovery after a mid-adopt death, "
             "serving reads throughout (zero torn reads tolerated)",
    )


def _gameday_run(smoke, seed, result):
    """The composed gameday scenario (docs/gameday.md), run on a
    worker thread under ``bench_gameday``'s hard deadline.

    One seeded timeline over a LIVE fleet (primary + 2 replicas +
    3-shard cluster) importing under 4x MIXED overload:

      e1.join            — a 4th shard joins mid-import
      e2.collector.die   — the persist stage worker dies (SIGKILL
                           model; the pipeline degrades to sync
                           commits and keeps going)
      e3.replica.die     — one replica's tail thread dies (failover)
      e4.shard.die       — shard s1 goes permanently unreachable
                           (every call raises; reads fail over to the
                           other replica of each key)
      e5.fork            — fork battle: a heavier branch displaces
                           the tip 2 blocks below it, retracting
                           served blocks, under live token traffic

    Events fire at BLOCK HEIGHTS (ScenarioEngine.step from the import
    loop), never wall-clock, so the composition replays identically
    for a seed. Gates: the full invariant set (chaos/invariants.py) —
    zero RYW violations, retraction visible on every replica, token
    floors honest, exactly-old-or-new ring epoch, final roots
    bit-exact vs a fresh serial replay — plus, in full mode, admitted
    p99 within 5x the unloaded floor."""
    import dataclasses
    import threading

    from khipu_tpu.base.crypto.keccak import keccak256
    from khipu_tpu.chaos import (
        FaultPlan,
        FaultRule,
        Scenario,
        ScenarioEngine,
        ScenarioEvent,
        active,
        check_admission_p99,
        check_epoch,
        check_retraction,
        check_roots_bit_exact,
        check_ryw,
        check_token_floor,
        fault_log,
        merge_plans,
        quiet_deaths,
        record_run,
    )
    from khipu_tpu.chaos.invariants import InvariantReport
    from khipu_tpu.chaos.scenario import clear_current_event
    from khipu_tpu.cluster import Rebalancer, ShardedNodeClient
    from khipu_tpu.config import TelemetryConfig
    from khipu_tpu.domain.block import Block as _Block
    from khipu_tpu.domain.blockchain import Blockchain
    from khipu_tpu.observability.telemetry import Watchdog
    from khipu_tpu.serving.loadgen import (
        MIXED,
        READ_ONLY,
        InProcessTransport,
        LoadGenerator,
    )
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.replay import PIPELINE_GAUGES, ReplayDriver

    from khipu_tpu.observability.journey import JOURNEY

    # tx passports ride the whole gameday: the fork battle's
    # retractions, the replica tails' visibility stamps and the
    # commit-latency histograms (with exemplar trace ids — the flight
    # recorder is on for the run) are all part of the postmortem
    JOURNEY.reset()
    JOURNEY.enable()
    n_blocks = 10 if smoke else 48
    (cfg, target, wire, fork_wire, ancestor, addrs, receivers, plane,
     service, server, driver, reorg, replicas, telemetry, router,
     build_cfg, genesis) = _fleet_setup(
        n_blocks,
        # windowed pipeline so the collector stages are LIVE targets
        sync_kwargs={"parallel_tx": False, "commit_window_blocks": 2,
                     "pipeline_depth": 2},
        # tight wait-or-redirect budget: a token-bearing read pays at
        # most 10ms waiting on a lagging replica before the router
        # redirects it to the primary — the operational posture for a
        # latency-gated fleet (docs/serving.md); the default 50ms
        # budget optimizes for replica offload instead and would
        # dominate the admitted tail under overload
        serving_kwargs={"ryw_wait_s": 0.01},
    )

    # ------------------------------------------------ shard cluster
    from khipu_tpu.cluster.ring import _point

    class _Shard:
        def __init__(self):
            self.store = {}

        def get_node_data(self, hashes):
            return {h: self.store[h] for h in hashes if h in self.store}

        def put_node_data(self, nodes):
            self.store.update(nodes)
            return len(nodes)

        def stream_node_data(self, ranges, cursor, count):
            snap = dict(self.store)
            keys = sorted(
                k for k in snap
                if cursor < k
                and any(lo <= _point(k) < hi for lo, hi in ranges)
            )
            page = keys[:count]
            done = len(keys) <= count
            nxt = page[-1] if page else bytes(cursor)
            return done, nxt, [(k, snap[k]) for k in page]

        def ping(self, payload=b""):
            return payload

        def close(self):
            pass

    shards = {ep: _Shard() for ep in ("s0", "s1", "s2", "s3")}
    cluster = ShardedNodeClient(
        ["s0", "s1", "s2"],
        channel_factory=lambda ep: shards[ep],
        sleep=lambda s: None,
    )
    rb = Rebalancer(cluster, batch=128)
    n_keys = 600 if smoke else 4000
    data = {}
    for i in range(n_keys):
        v = b"gameday node %d" % i
        data[keccak256(v)] = v
    cluster.replicate(data)
    cluster_keys = sorted(data)
    old_epoch = cluster.ring.epoch
    join_state = {}

    def run_join(_event):
        def work():
            try:
                join_state["streamed"] = rb.join("s3")
            except Exception as e:  # a shard death mid-stream rolls back
                join_state["error"] = f"{type(e).__name__}: {e}"
                rb.recover()

        t = threading.Thread(target=work, daemon=True, name="gd-join")
        t.start()
        join_state["thread"] = t

    # -------------------------------------------------- the timeline
    def h(frac):
        return max(1, int(n_blocks * frac))

    fork_event = ScenarioEvent(
        "e5.fork", n_blocks, "fork",
        params={"ancestor": ancestor},
    )
    scenario = Scenario(seed, [
        ScenarioEvent("e1.join", h(0.2), "join"),
        ScenarioEvent("e2.collector.die", h(0.4), "die",
                      "collector.persist"),
        ScenarioEvent("e3.replica.die", h(0.45), "die", "replica.tail"),
        ScenarioEvent("e4.shard.die", h(0.6), "raise", "cluster.call:s1",
                      {"times": None}),
        fork_event,
    ])
    # ambient background noise composed with the scenario through
    # merge_plans — per-(rule, site) RNG independence means arming the
    # scripted hazards cannot shift the ambient draws
    ambient = FaultPlan(seed=seed + 1, rules=[
        FaultRule("storage.node.get", "latency", prob=0.001,
                  latency_s=0.0002),
    ])
    plan = merge_plans(FaultPlan(seed=seed), ambient)

    reorged = {}

    def run_fork(event):
        # fork battle, synchronous on the import thread, under the
        # live overload/token traffic still running on worker threads
        reorg.switch(event.params["ancestor"], fork_wire[ancestor:])
        reorged["done"] = True

    engine = ScenarioEngine(
        scenario, plan, hooks={"join": run_join, "fork": run_fork},
    )
    result["schedule"] = scenario.schedule()

    # watchdog with an injectable journal-depth source: the smoke
    # trips it deterministically AFTER the scenario fired, pinning the
    # scenario correlation label on khipu_watchdog_trips_total
    depth_cell = {"depth": 0}
    wd = Watchdog(
        config=TelemetryConfig(enabled=True),
        journal_depth=lambda: depth_cell["depth"],
    )

    def gen(transport, profile, clients, reqs, seed_, key_base,
            rate=None, duration=0.0):
        return LoadGenerator(
            transport, profile, clients=clients, seed=seed_,
            max_requests=reqs, rate=rate, duration=duration,
            nonce_addresses=["0x" + a.hex() for a in addrs[:4]],
            balance_addresses=["0x" + r.hex() for r in receivers],
            client_keys=[
                (key_base + i).to_bytes(32, "big")
                for i in range(clients)
            ],
            chain_id=1,
        )

    transport = InProcessTransport(router)

    # phase A: unloaded floor (no faults installed) — the SAME mixed
    # profile the overload offers, so the 5x budget compares like with
    # like (a read-only floor would understate what an unloaded write
    # actually costs)
    floor = gen(transport, MIXED, 2, 30 if smoke else 150, 11,
                0x0A11_0000).run()
    p99_floor = floor.p99()

    # capacity probe (full mode): a short closed-loop MIXED saturation
    # run sizes the overload phase — the open loop then OFFERS 4x this
    # completed rate, so "4x overload" is a rate claim about offered
    # vs sustainable load, not a thread-count claim whose GIL
    # contention would corrupt the admitted tail it gates
    capacity_qps = None
    if not smoke:
        probe = gen(transport, MIXED, 6, 20, 17, 0x0E17_0000).run()
        capacity_qps = probe.ok / probe.seconds if probe.seconds else 0.0

    deaths_before = PIPELINE_GAUGES["collector_deaths"]
    slice_w = 4
    # throttle the import so the hazard timeline spans the overload
    # window (heights are the clock; the throttle only stretches them
    # across the load phase)
    delay = 0.01 if smoke else 0.25

    with quiet_deaths(), active(plan):
        # 4x MIXED overload riding the whole hazard timeline: smoke
        # keeps a small closed loop (mechanics only); full mode offers
        # an OPEN-loop 4x the probed capacity for the import's span
        if smoke:
            overload_gen = gen(transport, MIXED, 8, 25, 22, 0x0B22_0000)
        else:
            # 4 worker threads are a concurrency limit, not the load
            # claim — the OFFERED rate is the 4x; more workers would
            # only add GIL convoying to the admitted tail under test
            overload_gen = gen(
                transport, MIXED, 4, 0, 22, 0x0B22_0000,
                rate=4.0 * capacity_qps, duration=10.0,
            )
        over_box = {}

        def run_overload():
            over_box["report"] = overload_gen.run()

        over_t = threading.Thread(target=run_overload, daemon=True,
                                  name="gd-overload")
        over_t.start()

        # the import loop IS the milestone clock: scenario events fire
        # between window slices, keyed to committed height
        import time as _t

        i = 0
        while i < len(wire):
            engine.step(target.best_block_number)
            driver.replay(wire[i:i + slice_w])
            # deterministic cluster probe each milestone: content-
            # verified reads keep flowing through joins and deaths
            off = (i * 13) % len(cluster_keys)
            sample = cluster_keys[off:off + 8]
            got = cluster.fetch(sample)
            for k_, v_ in got.items():
                assert v_ == data[k_], "cluster served wrong bytes"
            i += slice_w
            _t.sleep(delay)
        wd.check_once()

        # fork battle (e5) fires here — import is complete, overload
        # may still be in flight, and a READ_ONLY token generation
        # runs THROUGH the retraction
        ryw_box = {}
        ryw_gen = gen(transport, READ_ONLY, 2 if smoke else 4,
                      15 if smoke else 40, 44, 0x0D44_0000)

        def run_ryw():
            ryw_box["report"] = ryw_gen.run()

        ryw_t = threading.Thread(target=run_ryw, daemon=True,
                                 name="gd-ryw")
        ryw_t.start()
        engine.step(target.best_block_number)
        assert reorged.get("done"), "fork battle never ran"
        ryw_t.join(timeout=120)
        over_t.join(timeout=120)

        # survivors converge on the adopted branch tip
        fork_tip = len(fork_wire)
        deadline = time.perf_counter() + 60
        while time.perf_counter() < deadline:
            alive = [r for r in replicas if r.alive()]
            if alive and all(
                r.head_number() == fork_tip for r in alive
            ):
                break
            _t.sleep(0.02)

        jt = join_state.get("thread")
        if jt is not None:
            jt.join(timeout=60)

    assert engine.done(), f"unfired events: {engine.remaining()}"
    overload = over_box["report"]
    ryw = ryw_box["report"]

    # the three seeded deaths all actually landed in THIS run
    kinds_fired = {(site, kind) for (site, _, kind, _) in plan.fired}
    assert ("collector.persist", "die") in kinds_fired, plan.fired
    assert ("replica.tail", "die") in kinds_fired, plan.fired
    assert ("cluster.call:s1", "raise") in kinds_fired, plan.fired
    assert PIPELINE_GAUGES["collector_deaths"] > deaths_before
    dead_replicas = [r for r in replicas if not r.alive()]
    live_replicas = [r for r in replicas if r.alive()]
    assert len(dead_replicas) == 1, [r.snapshot() for r in replicas]
    assert cluster.metrics["s1"].failures > 0, "shard death never hit"

    # ------------------------------------------------- the invariants
    report = InvariantReport()
    violations = (
        list(floor.violations) + list(overload.violations)
        + list(ryw.violations)
    )
    report.add(check_ryw(violations))
    retracted = [
        (n, wire[n - 1].header.hash)
        for n in range(ancestor + 1, len(wire) + 1)
    ]
    report.add(check_retraction(target, replicas, retracted))
    report.add(check_token_floor(router, retracted, ancestor))
    report.add(check_epoch(rb, old_epoch, old_epoch + 1))
    # every cluster key still content-verifiable through the ring,
    # one shard dead and one joined (or rolled back) notwithstanding
    all_back = {}
    for off in range(0, len(cluster_keys), 256):
        all_back.update(cluster.fetch(cluster_keys[off:off + 256]))
    cluster_ok = all_back == data
    from khipu_tpu.chaos.invariants import InvariantResult

    report.add(InvariantResult(
        "cluster_integrity", cluster_ok,
        "" if cluster_ok else
        f"{len(data) - len(all_back)} keys unreachable",
    ))
    # bit-exact final roots vs a FRESH serial replay of the canonical
    # (post-fork) chain
    ref_bc = Blockchain(Storages(), build_cfg)
    ref_bc.load_genesis(genesis)
    ref_driver = ReplayDriver(ref_bc, build_cfg)
    ref_driver.replay([_Block.decode(b.encode()) for b in fork_wire])
    report.add(check_roots_bit_exact(target, ref_bc))
    p99_ms = overload.p99() * 1e3
    floor_ms = p99_floor * 1e3
    if not smoke:
        # smoke gates on invariants only; full mode also holds the SLO
        report.add(check_admission_p99(p99_ms, floor_ms, budget=5.0))

    record_run(engine.events_by_kind, report, p99_ms)

    # deterministic watchdog trip AFTER the timeline completed: the
    # trip carries the last scenario event id as its correlation label
    depth_cell["depth"] = 99
    tripped = wd.check_once()
    assert "journal_runaway" in tripped, tripped
    snap = fault_log.snapshot()

    # per-tx passport readout: commit-latency tails plus the count of
    # journeys that crossed the fork battle's retraction — gated in
    # bench_gameday (a gameday whose passports miss the reorg would be
    # lying about what the timeline did)
    durable_ms = JOURNEY.latencies_ms("durable")
    visible_ms = JOURNEY.latencies_ms("replica.visible")
    retracted_journeys = sum(
        1 for j in JOURNEY.journeys()
        if any(e[1] == "reorg.retract" for e in j.events)
    )
    result.update({
        "tx_durable_ms": durable_ms,
        "tx_visible_ms": visible_ms,
        "retracted_journeys": retracted_journeys,
        "report": report,
        "p99_ms": p99_ms,
        "floor_ms": floor_ms,
        "overload": overload,
        "ryw": ryw,
        "floor": floor,
        "faults": snap,
        "events_fired": list(engine.fired),
        "survivor": live_replicas[0].snapshot() if live_replicas else None,
        "epoch": cluster.ring.epoch,
        "join": {k: v for k, v in join_state.items() if k != "thread"},
        "service": service,
        "router": router,
        "telemetry": telemetry,
        "watchdog": wd,
    })
    # a caller that lives on (a test) must not inherit the tail threads
    for r in live_replicas:
        r.stop()
    clear_current_event()


def bench_gameday(smoke=False, seed=0, deadline_s=None,
                  chrome_out=None):
    """``scenarios.py gameday``: one seeded scenario composing every
    failure mode the repo has proven in isolation — shard join +
    collector death + replica death + shard death + fork battle,
    under 4x overload — gated on the full invariant set and (full
    mode) the admitted-p99 SLO. ``--smoke`` runs the short
    deterministic timeline, gates on invariants only and pins the
    khipu_gameday_* exposition families. Runs under a HARD deadline
    on a worker thread: a wedged composition exits 1, never hangs the
    gate.

    The flight recorder is ON for the whole run and one merged chrome
    trace is dumped per run (``--chrome-out=`` or a tempdir default):
    every scenario event is a ``scenario.*`` instant in the same
    timeline as the replay/serving spans, so the postmortem view shows
    the hazard AND what the pipeline was doing when it landed."""
    import os
    import tempfile
    import threading

    from khipu_tpu.observability.trace import tracer

    deadline_s = deadline_s or (150.0 if smoke else 300.0)
    result = {}
    errbox = {}

    def drive():
        try:
            _gameday_run(smoke, seed, result)
        except BaseException as e:  # noqa: BLE001 - report, then gate
            import traceback

            errbox["error"] = e
            errbox["tb"] = traceback.format_exc()

    tracer.enable()
    worker = threading.Thread(target=drive, daemon=True)
    worker.start()
    worker.join(timeout=deadline_s)
    tracer.disable()
    trace_path = None
    try:
        from khipu_tpu.observability import export

        trace_path = chrome_out or os.path.join(
            tempfile.gettempdir(), f"gameday_trace_seed{seed}.json"
        )
        export.dump_chrome_trace(trace_path)
    except Exception as e:  # noqa: BLE001 - the trace is a postmortem
        print(f"bench_gameday: chrome trace not written: {e}",
              file=sys.stderr)
        trace_path = None
    if worker.is_alive():
        print(
            f"bench_gameday: FAILED — scenario did not complete within "
            f"{deadline_s}s (schedule={result.get('schedule')})",
            file=sys.stderr,
        )
        sys.exit(1)
    if "error" in errbox:
        print(errbox["tb"], file=sys.stderr)
        print("bench_gameday: FAILED — scenario raised", file=sys.stderr)
        sys.exit(1)

    report = result["report"]
    if not report.ok:
        for r in report.failures:
            print(f"bench_gameday: INVARIANT {r.name}: {r.detail}",
                  file=sys.stderr)
        sys.exit(1)

    # passport SLO lines, gated: the board must have witnessed durable
    # commits, replica visibility AND the fork battle's retractions
    durable_ms = result["tx_durable_ms"]
    visible_ms = result["tx_visible_ms"]
    retracted = result["retracted_journeys"]
    for name, ok in (
        ("tx durable latencies", bool(durable_ms)),
        ("tx replica-visible latencies", bool(visible_ms)),
        ("retraction-crossing journeys", retracted >= 1),
    ):
        if not ok:
            print(f"bench_gameday: FAILED — passport gate: no {name}",
                  file=sys.stderr)
            sys.exit(1)
    emit(
        "tx_ingress_to_durable_p99_ms",
        round(_p99(durable_ms), 3), "ms",
        samples=len(durable_ms),
        p50_ms=round(_p50(durable_ms), 3),
        retracted_journeys=retracted,
        note="per-tx passport across the whole gameday timeline "
             "(import deliberately throttled to stretch the hazard "
             "window — pacing is in the number)",
    )
    emit(
        "tx_ingress_to_replica_visible_p99_ms",
        round(_p99(visible_ms), 3), "ms",
        samples=len(visible_ms),
        p50_ms=round(_p50(visible_ms), 3),
    )

    if smoke:
        # exposition: every gameday family exactly once, plus the
        # watchdog correlation label stamped by the scenario
        service = result["service"]
        text = service.khipu_metrics_text()
        for fam, kind in (
            ("khipu_gameday_runs_total", "counter"),
            ("khipu_gameday_events_total", "counter"),
            ("khipu_gameday_invariant_checks_total", "counter"),
            ("khipu_gameday_invariant_failures_total", "counter"),
            ("khipu_gameday_last_p99_ms", "gauge"),
            ("khipu_tx_commit_latency_seconds", "histogram"),
            ("khipu_tx_journey_enabled", "gauge"),
            ("khipu_tx_journeys_tracked", "gauge"),
            ("khipu_tx_journeys_pinned", "gauge"),
            ("khipu_tx_journey_events_total", "counter"),
            ("khipu_tx_journeys_evicted_total", "counter"),
        ):
            n = text.count(f"# TYPE {fam} {kind}")
            assert n == 1, f"{fam} TYPE lines: {n}"
        # exemplar linkage: the flight recorder was ON for the run, so
        # commit-latency buckets carry the owning trace id
        assert ' # {trace_id="' in text, (
            "no exemplar on the commit-latency histogram"
        )
        assert 'khipu_watchdog_trips_total{kind="journal_runaway"' \
            in text, "watchdog trip family missing"
        assert 'scenario="e5.fork"' in text, (
            "scenario correlation label missing from watchdog trips"
        )
        for name, ok in report.summary().items():
            assert ok, name
        emit(
            "gameday_p99_ms", round(result["p99_ms"], 3), "ms",
            smoke=True,
            seed=seed,
            invariants={n: bool(v) for n, v in report.summary().items()},
            events_fired=[e for e, _ in result["events_fired"]],
            faults_fired=result["faults"]["fired"],
            ryw_violations=0,
            epoch=result["epoch"],
            exposition_families_ok=True,
            scenario_label_ok=True,
            chrome_trace=trace_path,
        )
        return

    emit(
        "gameday_p99_ms", round(result["p99_ms"], 3), "ms",
        seed=seed,
        p99_floor_ms=round(result["floor_ms"], 3),
        p99_budget="5.0x floor",
        invariants={n: bool(v) for n, v in report.summary().items()},
        events_fired=[e for e, _ in result["events_fired"]],
        faults_fired=result["faults"]["fired"],
        faults_by_kind=result["faults"]["byKind"],
        overload_completed=result["overload"].ok,
        overload_shed=result["overload"].shed,
        ryw_violations=0,
        epoch=result["epoch"],
        join=result["join"],
        survivor=result["survivor"],
        chrome_trace=trace_path,
        note="one seeded timeline: shard join + collector death + "
             "replica death + shard death + fork battle under 4x "
             "MIXED overload; gated on RYW + retraction + token "
             "floors + exactly-old-or-new epoch + bit-exact roots + "
             "admitted p99 <= 5x floor (docs/gameday.md)",
    )


def bench_ingest(smoke=False, deadline_s=180.0):
    """``scenarios.py ingest``: the Kesque storage-engine gate — three
    first-class metrics, all gated:

    * ``persist_bytes_per_sec`` — bulk ``append_batch`` throughput of
      the segment log on window-sized batches, with the sqlite
      engine's per-batch throughput on the same data as the delta.
    * ``snapshot_ingest_seconds`` — parallel segment-streamed ingest
      (sync/fast_sync.py ``segment_snapshot_ingest``) of a REAL state
      trie, against the per-node baseline: the actual ``StateSyncer``
      downloading the same trie node-by-node (serial child-discovery
      walk, per-node verify + parse, batch-of-100 saves into a fresh
      sqlite store). GATE: the segment path must be ≥ 3× faster. The
      post-ingest reachability walk (same verification crash recovery
      runs) is reported separately as ``verify_walk_seconds`` and must
      find the streamed trie complete.
    * ``ingest_read_amplification`` — disk bytes fetched per value
      byte served under random point reads of the ingested store
      (positional frame reads: expected ≈ 1.0x, gated < 1.5x).

    Smoke additionally pins every ``khipu_kesque_*`` registry family
    to exactly one TYPE line in the Prometheus exposition. Runs under
    a HARD deadline on a worker thread: a wedged ingest exits 1."""
    import os
    import shutil
    import tempfile
    import threading

    from khipu_tpu.base.crypto.keccak import keccak256
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.observability.registry import REGISTRY
    from khipu_tpu.storage.compactor import verify_reachable
    from khipu_tpu.storage.datasource import MemoryKeyValueDataSource
    from khipu_tpu.storage.kesque import KesqueEngine
    from khipu_tpu.storage.sqlite_engine import SqliteNodeDataSource
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.fast_sync import (
        FastSyncStateStorage,
        StateSyncer,
        segment_snapshot_ingest,
    )

    n_records = 4_000 if smoke else 24_000
    batch = 2_000  # window-sized bulk append
    dataset = {}
    for i in range(n_records):
        v = (b"kesque ingest record %08d " % i) * 6  # ~180 B/node
        dataset[keccak256(v)] = v
    total_bytes = sum(len(v) for v in dataset.values())
    items = list(dataset.items())
    tmp = tempfile.mkdtemp(prefix="bench_ingest_")
    result = {}

    def drive():
        runs = 3  # best-of: stores are rebuilt fresh per run, the
        # minimum is reported (single-shot numbers at this scale are
        # dominated by filesystem and allocator noise)

        # ---- persist throughput: window-sized bulk appends
        def kes_persist(i):
            eng = KesqueEngine(os.path.join(tmp, f"kes_persist{i}"))
            st = eng.store("account")
            t0 = time.perf_counter()
            for s in range(0, len(items), batch):
                st.append_batch([], dict(items[s : s + batch]))
            st.flush()
            secs = time.perf_counter() - t0
            eng.stop()
            return secs

        def sq_persist(i):
            d = os.path.join(tmp, f"sq_persist{i}")
            os.makedirs(d, exist_ok=True)
            sq = SqliteNodeDataSource(d, "account")
            t0 = time.perf_counter()
            for s in range(0, len(items), batch):
                sq.update([], dict(items[s : s + batch]))
            fl = getattr(sq, "flush", None)
            if fl:
                fl()
            secs = time.perf_counter() - t0
            sq.stop()
            return secs

        result["kes_persist_s"] = min(kes_persist(i) for i in range(runs))
        result["sq_persist_s"] = min(sq_persist(i) for i in range(runs))

        # ---- a REAL state trie: genesis alloc of n accounts builds
        # the account MPT the two ingest paths race over (large enough
        # that per-node walk cost, not fixed setup, dominates both)
        n_accounts = 2_400 if smoke else 8_000
        cfg = fixture_config(chain_id=1)
        alloc = {
            keccak256(b"bench ingest acct %08d" % i)[:20]: 10**18 + i
            for i in range(n_accounts)
        }
        src_bc = Blockchain(Storages(), cfg)
        src_bc.load_genesis(GenesisSpec(alloc=alloc))
        root = src_bc.get_header_by_number(0).state_root
        src_nodes = {}
        for k in src_bc.storages.account_node_storage.source.keys():
            src_nodes[bytes(k)] = src_bc.storages.account_node_storage.get(k)
        result["trie_nodes"] = len(src_nodes)
        # the segment-ship source: the same trie in a kesque log,
        # rolled into several segments so the worker pool has real
        # per-segment parallelism (production logs are many segments)
        trie_src = KesqueEngine(
            os.path.join(tmp, "kes_trie"), segment_bytes=128 << 10
        )
        trie_src.store("account").append_batch([], src_nodes)

        # ---- per-node baseline: the actual StateSyncer (serial
        # child-discovery walk, per-node verify + parse, batch saves)
        def baseline_run(i):
            base_target = Storages(
                engine="sqlite",
                data_dir=os.path.join(tmp, f"sq_ingest{i}"),
            )
            syncer = StateSyncer(
                base_target,
                FastSyncStateStorage(MemoryKeyValueDataSource()),
                lambda hashes: {
                    h: src_nodes[h] for h in hashes if h in src_nodes
                },
            )
            t0 = time.perf_counter()
            state = syncer.start(root)
            secs = time.perf_counter() - t0
            assert state.downloaded_nodes == len(src_nodes)
            base_target.stop()
            return secs

        result["baseline_ingest_s"] = min(
            baseline_run(i) for i in range(runs)
        )

        # ---- segment streaming: the manifest IS the work list — no
        # discovery walk, megabyte chunks, bulk appends
        dst = None

        def segment_run(i):
            nonlocal dst
            if dst is not None:
                dst.stop()
            dst = Storages(engine="kesque",
                           data_dir=os.path.join(tmp, f"kes_dst{i}"))
            t0 = time.perf_counter()
            report = segment_snapshot_ingest(
                dst,
                lambda: trie_src.list_segments(["account"]),
                trie_src.read_chunk,
                workers=4,
            )
            secs = time.perf_counter() - t0
            assert report.records == len(src_nodes), (
                f"ingested {report.records}/{len(src_nodes)}"
            )
            assert report.corrupt_frames == 0
            return secs

        result["segment_ingest_s"] = min(
            segment_run(i) for i in range(runs)
        )
        # completeness: the same hash-verified reachability walk crash
        # recovery runs (timed separately — it is verification, not
        # movement; receipt-time content addressing already verified
        # every shipped record)
        t0 = time.perf_counter()
        walk = verify_reachable(
            dst.account_node_storage, dst.storage_node_storage,
            dst.evmcode_storage, root, verify_hashes=True,
        )
        result["verify_walk_s"] = time.perf_counter() - t0
        assert walk.missing == 0 and walk.corrupt == 0, (
            f"streamed trie incomplete: {walk.missing} missing "
            f"{walk.corrupt} corrupt"
        )

        # ---- read amplification under serving point reads
        st = dst.kesque_engine.store("account")
        trie_keys = sorted(src_nodes)
        for k in trie_keys[::3]:
            assert st.get(k) is not None
        result["read_amp"] = dst.kesque_engine.read_amplification()
        result["reads"] = len(trie_keys[::3])
        dst.stop()
        trie_src.stop()

    worker = threading.Thread(target=drive, daemon=True)
    worker.start()
    worker.join(timeout=deadline_s)
    try:
        if worker.is_alive() or "read_amp" not in result:
            print(
                f"bench_ingest: FAILED — did not complete within "
                f"{deadline_s}s (have {sorted(result)})",
                file=sys.stderr,
            )
            sys.exit(1)
        kes_bps = (
            total_bytes / result["kes_persist_s"]
            if result["kes_persist_s"] > 0 else 0.0
        )
        sq_bps = (
            total_bytes / result["sq_persist_s"]
            if result["sq_persist_s"] > 0 else 0.0
        )
        speedup = (
            result["baseline_ingest_s"] / result["segment_ingest_s"]
            if result["segment_ingest_s"] > 0 else 0.0
        )
        emit(
            "persist_bytes_per_sec",
            round(kes_bps),
            "bytes/s",
            sqlite_bytes_per_sec=round(sq_bps),
            vs_sqlite_ratio=round(kes_bps / sq_bps, 2) if sq_bps else 0,
            records=n_records,
            batch=batch,
            note="window-sized bulk append_batch into the segment log "
                 "vs the same batches into the sqlite engine",
        )
        emit(
            "snapshot_ingest_seconds",
            round(result["segment_ingest_s"], 4),
            "seconds",
            baseline_per_node_seconds=round(
                result["baseline_ingest_s"], 4
            ),
            speedup=round(speedup, 2),
            trie_nodes=result["trie_nodes"],
            verify_walk_seconds=round(result["verify_walk_s"], 4),
            workers=4,
            note="parallel segment streaming of a real account trie "
                 "vs the actual StateSyncer per-node download",
        )
        emit(
            "ingest_read_amplification",
            round(result["read_amp"], 4),
            "x",
            reads=result["reads"],
            note="disk bytes per value byte under random point reads "
                 "of the ingested store (frame header + tag overhead)",
        )
        if speedup < 3.0:
            print(
                f"bench_ingest: FAILED — segment ingest speedup "
                f"{speedup:.2f}x < 3.0x gate",
                file=sys.stderr,
            )
            sys.exit(1)
        if result["read_amp"] >= 1.5:
            print(
                f"bench_ingest: FAILED — read amplification "
                f"{result['read_amp']:.3f}x >= 1.5x gate",
                file=sys.stderr,
            )
            sys.exit(1)
        if smoke:
            text = REGISTRY.prometheus_text()
            for fam, kind in (
                ("khipu_kesque_segments", "gauge"),
                ("khipu_kesque_live_bytes", "gauge"),
                ("khipu_kesque_garbage_bytes", "gauge"),
                ("khipu_kesque_index_entries", "gauge"),
                ("khipu_kesque_appended_bytes_total", "counter"),
                ("khipu_kesque_reclaimed_bytes_total", "counter"),
                ("khipu_kesque_torn_bytes_total", "counter"),
                ("khipu_kesque_compactions_total", "counter"),
                ("khipu_kesque_read_amplification", "gauge"),
            ):
                n = text.count(f"# TYPE {fam} {kind}")
                assert n == 1, f"{fam} TYPE lines: {n}"
            emit(
                "ingest_smoke", n_records, "records",
                kesque_families_ok=True,
                speedup=round(speedup, 2),
            )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_getlogs(smoke=False):
    """``scenarios.py getlogs``: the indexing fixture — a chain whose
    every block carries LOG1-emitting contract calls, scanned by
    repeated full-range address+topic ``eth_getLogs`` queries through
    the RPC service (the workload an indexer backfilling an event
    table offers a node). The metric is blocks SCANNED per second;
    every scan's hit count is verified against the fixture shape, so a
    filter regression fails the bench rather than speeding it up."""
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.domain.transaction import (
        Transaction,
        contract_address,
        sign_transaction,
    )
    from khipu_tpu.jsonrpc import EthService
    from khipu_tpu.storage.storages import Storages
    from khipu_tpu.sync.chain_builder import ChainBuilder

    cfg = fixture_config(chain_id=1)
    n_blocks = 12 if smoke else 64
    calls_per_block = 6
    keys, addrs = _replay_keys(4)
    alloc = {a: 10**24 for a in addrs}
    # runtime: PUSH32 <data> MSTORE, LOG1 topic 0x..42 with 32B data
    topic = (0x42).to_bytes(32, "big")
    runtime = (
        bytes([0x7F]) + b"\xab" * 32 + bytes.fromhex("600052")
        + bytes([0x7F]) + topic + bytes.fromhex("60206000a100")
    )
    init = bytes(
        [0x60, len(runtime), 0x60, 12, 0x60, 0x00, 0x39,
         0x60, len(runtime), 0x60, 0x00, 0xF3]
    ) + runtime
    bc = Blockchain(Storages(), cfg)
    builder = ChainBuilder(bc, cfg, GenesisSpec(alloc=alloc))
    nonces = [0] * len(keys)
    builder.add_block(
        [sign_transaction(
            Transaction(0, 10**9, 300_000, None, 0, init), keys[0],
            chain_id=1,
        )],
        coinbase=b"\xaa" * 20,
    )
    nonces[0] += 1
    caddr = contract_address(addrs[0], 0)
    for _n in range(n_blocks):
        txs = []
        for j in range(calls_per_block):
            i = j % len(keys)
            txs.append(sign_transaction(
                Transaction(nonces[i], 10**9, 100_000, caddr, 0),
                keys[i], chain_id=1,
            ))
            nonces[i] += 1
        builder.add_block(txs, coinbase=b"\xaa" * 20)
    svc = EthService(bc, cfg)
    head = bc.best_block_number
    query = {
        "fromBlock": "0x0", "toBlock": "latest",
        "address": "0x" + caddr.hex(),
        "topics": ["0x" + topic.hex()],
    }
    expected = n_blocks * calls_per_block
    assert len(svc.eth_getLogs(query)) == expected  # warm + verify
    rounds = 3 if smoke else 10
    t0 = time.perf_counter()
    for _ in range(rounds):
        hits = svc.eth_getLogs(query)
        assert len(hits) == expected, (len(hits), expected)
    secs = time.perf_counter() - t0
    blocks_scanned = rounds * (head + 1)
    emit(
        "getlogs_blocks_per_sec",
        round(blocks_scanned / secs, 1) if secs else 0.0,
        "blocks/s",
        logs_matched=expected,
        blocks=head,
        rounds=rounds,
        calls_per_block=calls_per_block,
        note="repeated full-range address+topic eth_getLogs scans "
             "over a chain whose every block logs (receipt re-derive "
             "+ filter path; the indexer-backfill shape)",
    )


MODES = {
    "serve": bench_serve,
    "serve-http": bench_serve_http,
    "rebalance": bench_rebalance,
    "reorg": bench_reorg,
    "ingest": bench_ingest,
    "getlogs": bench_getlogs,
    "gameday": bench_gameday,
}


def parse_args(argv):
    """argv -> (gate function, its keyword arguments). No mode or an
    unknown one is argparse's usage error (exit 2)."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = parser.add_subparsers(dest="mode", required=True)
    modes = {mode: sub.add_parser(mode) for mode in MODES}
    for p in modes.values():
        p.add_argument("--smoke", action="store_true")
    modes["gameday"].add_argument("--seed", type=int, default=0)
    modes["gameday"].add_argument("--chrome-out", default=None)
    kwargs = vars(parser.parse_args(argv))
    return MODES[kwargs.pop("mode")], kwargs


def main() -> None:
    fn, kwargs = parse_args(sys.argv[1:])
    from khipu_tpu import device

    device.place_compile_cache()
    fn(**kwargs)


if __name__ == "__main__":
    main()
