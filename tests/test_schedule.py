"""Conflict-aware scheduler tests (ledger/schedule.py, batch_exec.py,
sync/prefetch.py — ISSUE 14 execute-stage rebuild).

External oracles: the sequential fold (ChainBuilder builds every
fixture chain serially, so its headers ARE the serial roots/receipts/
gas), the optimistic-parallel path, and exact conflict-pair checks
re-derived from the documented footprint algebra — never from the
planner's own code.
"""

import dataclasses
import random

import pytest

from khipu_tpu.base.crypto.secp256k1 import (
    privkey_to_pubkey,
    pubkey_to_address,
)
from khipu_tpu.config import SyncConfig, fixture_config
from khipu_tpu.domain.account import EMPTY_CODE_HASH
from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
from khipu_tpu.domain.transaction import (
    Transaction,
    contract_address,
    sign_transaction,
)
from khipu_tpu.ledger.schedule import (
    CALL,
    FAST,
    LEARNER,
    TemplateLearner,
    plan_block,
    reset_templates,
)
from khipu_tpu.ledger.world import (
    ON_ACCOUNT,
    ON_ADDRESS,
    ON_CODE,
    ON_STORAGE,
)
from khipu_tpu.storage.storages import Storages
from khipu_tpu.sync.chain_builder import ChainBuilder
from khipu_tpu.sync.replay import ReplayDriver

CFG = fixture_config(chain_id=1)
NKEYS = 12
KEYS = [(i + 71).to_bytes(32, "big") for i in range(NKEYS)]
ADDRS = [pubkey_to_address(privkey_to_pubkey(k)) for k in KEYS]
MINER = b"\xaa" * 20
GWEI = 10**9
ETH = 10**18
ALLOC = {a: 1000 * ETH for a in ADDRS}


def _cfg(parallel=True, scheduled=True):
    return dataclasses.replace(
        CFG, sync=SyncConfig(parallel_tx=parallel, scheduled_tx=scheduled)
    )


def _fresh(cfg, alloc=None):
    bc = Blockchain(Storages(), cfg)
    bc.load_genesis(GenesisSpec(alloc=alloc or ALLOC))
    return bc


def tx(i, nonce, to, value, gas=21_000, payload=b""):
    return sign_transaction(
        Transaction(nonce, GWEI, gas, to, value, payload),
        KEYS[i], chain_id=1,
    )


# --------------------------------------------------- plan disjointness


class _STX:
    """Planner-shaped stand-in: plan_block only reads ``.tx``."""

    def __init__(self, t):
        self.tx = t


def _conflicts(p, q):
    """The documented conflict relation, re-derived independently of
    the planner: read meets write/delta, write meets anything, slots
    intersect. D∩D and code∩code are NOT conflicts."""
    return bool(
        (p.acct_r & (q.acct_w | q.acct_d))
        or (q.acct_r & (p.acct_w | p.acct_d))
        or (p.acct_w & (q.acct_r | q.acct_w | q.acct_d))
        or (q.acct_w & (p.acct_r | p.acct_w | p.acct_d))
        or (p.slots & q.slots)
    )


class TestPlanDisjointness:
    def _random_block(self, rng, learner, token, token_hash):
        """A planner-hostile tx mix: few senders (hot chains), shared
        recipients, coinbase touches, creations, precompile targets,
        zero-value transfers, and template calls to ``token``."""
        pool = ADDRS[:6]
        txs, senders = [], []
        for j in range(rng.randrange(8, 30)):
            sender = rng.choice(pool)
            r = rng.random()
            if r < 0.05:
                t = Transaction(j, GWEI, 53_000, None, 0, b"\x00")
            elif r < 0.10:
                t = Transaction(j, GWEI, 21_000, MINER, 5)
            elif r < 0.15:
                t = Transaction(
                    j, GWEI, 21_000, (0x07).to_bytes(20, "big"), 5
                )
            elif r < 0.25:
                t = Transaction(j, GWEI, 21_000, rng.choice(pool), 0)
            elif r < 0.55:
                payload = rng.randrange(1, 9).to_bytes(32, "big")
                t = Transaction(j, GWEI, 90_000, token, 0, payload)
            else:
                t = Transaction(
                    j, GWEI, 21_000,
                    rng.choice(pool + ADDRS[6:10]), rng.randrange(1, 99),
                )
            txs.append(_STX(t))
            senders.append(sender)
        return txs, senders

    def test_batches_pairwise_disjoint_over_seeds(self):
        """Property: within every planned batch, all predicted pairs
        are conflict-free under the independently-derived relation,
        residues are singleton barriers, and the plan is a permutation
        of the block."""
        token = b"\x70" * 20
        token_hash = b"\x71" * 32
        learner = TemplateLearner()
        # teach one template (balance[arg0]-style) via the public API
        learner.observe(
            token_hash, ADDRS[0], token,
            (5).to_bytes(32, "big"),
            reads={ON_ACCOUNT: {ADDRS[0], token}, ON_ADDRESS: set(),
                   ON_STORAGE: {(token, 5)}, ON_CODE: {token}},
            written={ON_ACCOUNT: {ADDRS[0]}, ON_ADDRESS: set(),
                     ON_STORAGE: {(token, 5)}, ON_CODE: set()},
        )

        def code_hash_of(addr):
            return token_hash if addr == token else EMPTY_CODE_HASH

        for seed in range(40):
            rng = random.Random(seed)
            txs, senders = self._random_block(
                rng, learner, token, token_hash
            )
            plan = plan_block(txs, senders, MINER, code_hash_of, learner)
            seen = []
            for step in plan.steps:
                seen.extend(step.indices)
                if step.kind == "residue":
                    assert len(step.indices) == 1
                    assert step.indices[0] not in plan.predicted
                    continue
                assert step.indices == sorted(step.indices)
                preds = [plan.predicted[i] for i in step.indices]
                for a in range(len(preds)):
                    for b in range(a + 1, len(preds)):
                        assert not _conflicts(preds[a], preds[b]), (
                            f"seed {seed}: batch {step.indices} txs "
                            f"{step.indices[a]},{step.indices[b]} conflict"
                        )
            assert sorted(seen) == list(range(len(txs))), (
                f"seed {seed}: plan is not a permutation of the block"
            )
            assert plan.n_fast + plan.n_call + plan.n_residue == len(txs)

    def test_conflicting_pairs_keep_index_order(self):
        """Two transfers from ONE sender must land in increasing
        batches (read-of-sender meets delta-on-sender)."""
        txs = [
            _STX(Transaction(0, GWEI, 21_000, ADDRS[5], 1)),
            _STX(Transaction(1, GWEI, 21_000, ADDRS[6], 1)),
        ]
        plan = plan_block(
            txs, [ADDRS[0], ADDRS[0]], MINER,
            lambda a: EMPTY_CODE_HASH, TemplateLearner(),
        )
        batch_of = {}
        for pos, step in enumerate(plan.steps):
            for i in step.indices:
                batch_of[i] = pos
        assert batch_of[0] < batch_of[1]
        assert plan.conflicted == 1

    def test_pure_credit_overlap_shares_a_batch(self):
        """Two different senders paying the SAME recipient commute
        (D∩D) and must share the widest batch."""
        txs = [
            _STX(Transaction(0, GWEI, 21_000, ADDRS[7], 1)),
            _STX(Transaction(0, GWEI, 21_000, ADDRS[7], 2)),
        ]
        plan = plan_block(
            txs, [ADDRS[0], ADDRS[1]], MINER,
            lambda a: EMPTY_CODE_HASH, TemplateLearner(),
        )
        assert plan.max_width == 2 and plan.conflicted == 0


# ------------------------------------------------- 120-seed oracle sweep


# the conflict-storm token from the contended bench: writes
# balance[CALLER] and balance[arg0] — learnable as ("caller",)/("arg",0)
_TOKEN_RUNTIME = bytes([
    0x60, 0x00, 0x35, 0x60, 0x20, 0x35, 0x33, 0x54, 0x81, 0x90, 0x03,
    0x33, 0x55, 0x81, 0x54, 0x01, 0x90, 0x55, 0x00,
])


def _init_code(runtime):
    return (
        bytes([0x60 + len(runtime) - 1]) + runtime
        + bytes([0x60, 0x00, 0x52])
        + bytes([0x60, len(runtime), 0x60, 32 - len(runtime), 0xF3])
    )


class TestScheduledOracleSweep:
    def _random_chain(self, seed, n_tx_blocks=4, txs_per_block=12):
        """Deploy the token, then ``n_tx_blocks`` blocks of a seeded
        adversarial tx mix: transfers (hot + disjoint), template calls,
        zero-value touches, coinbase payments, creations. Multi-block
        on purpose (ISSUE 17): the token calls must live long enough to
        cross TRUST_AFTER confirmations so the later blocks' calls run
        through the TRUSTED vectorized batch lane, not just checked."""
        rng = random.Random(seed)
        cfg = _cfg(parallel=False)
        builder = ChainBuilder(
            Blockchain(Storages(), cfg), cfg, GenesisSpec(alloc=ALLOC)
        )
        token = contract_address(ADDRS[0], 0)
        blocks = [builder.add_block(
            [tx(0, 0, None, 0, gas=500_000,
                payload=_init_code(_TOKEN_RUNTIME))],
            coinbase=MINER,
        )]
        nonces = [1] + [0] * (NKEYS - 1)
        for _ in range(n_tx_blocks):
            txs = []
            for _ in range(txs_per_block):
                i = rng.randrange(NKEYS)
                r = rng.random()
                if r < 0.30:
                    # hot transfers: few recipients, frequent sender
                    # reuse
                    txs.append(tx(i, nonces[i], rng.choice(ADDRS[:4]),
                                  1 + rng.randrange(50)))
                elif r < 0.55:
                    payload = (
                        ADDRS[rng.randrange(NKEYS)].rjust(32, b"\x00")
                        + (1 + rng.randrange(3)).to_bytes(32, "big")
                    )
                    txs.append(tx(i, nonces[i], token, 0, gas=200_000,
                                  payload=payload))
                elif r < 0.65:
                    txs.append(tx(i, nonces[i], rng.choice(ADDRS), 0,
                                  gas=30_000))
                elif r < 0.72:
                    txs.append(tx(i, nonces[i], MINER, 7))
                elif r < 0.78:
                    txs.append(tx(i, nonces[i], None, 0, gas=60_000,
                                  payload=b"\x00"))
                else:
                    txs.append(tx(
                        i, nonces[i],
                        bytes.fromhex(
                            "%040x" % (0xE0000000 + rng.randrange(8))),
                        1 + rng.randrange(9),
                    ))
                nonces[i] += 1
            blocks.append(builder.add_block(txs, coinbase=MINER))
        return blocks

    @pytest.mark.parametrize("bank", range(4))
    def test_scheduled_bit_exact_vs_serial_and_optimistic(self, bank):
        """120 seeds (4 banks x 30): the scheduled path must land on
        the EXACT chain the serial fold built (roots + receipts root +
        gas all live in the sealed header; the replay validates
        against it and raises on any divergence), and so must the
        optimistic path. Templates reset between seeds — every seed
        re-learns from its own residue, and the 4-block chains carry
        the token past TRUST_AFTER so the trusted vectorized call lane
        executes real traffic inside the sweep."""
        from khipu_tpu.ledger.schedule import EXEC_GAUGES

        total_fast = total_residue = 0
        vector_before = EXEC_GAUGES["vector_call_txs"]
        for seed in range(bank * 30, bank * 30 + 30):
            blocks = self._random_chain(seed)
            reset_templates()
            for cfg in (_cfg(scheduled=True), _cfg(scheduled=False)):
                bc = _fresh(cfg)
                stats = ReplayDriver(bc, cfg).replay(blocks)
                assert (
                    bc.get_header_by_number(len(blocks)).hash
                    == blocks[-1].hash
                ), f"seed {seed} diverged (scheduled="\
                   f"{cfg.sync.scheduled_tx})"
                if cfg.sync.scheduled_tx:
                    total_fast += stats.fast_path_txs
                    total_residue += stats.residue_txs
        # the sweep must actually exercise both executors AND the
        # trusted templated-call lane (not just checked calls)
        assert total_fast > 0 and total_residue > 0
        assert EXEC_GAUGES["vector_call_txs"] > vector_before

    def test_template_call_batches_after_learning(self):
        """Same-shaped token calls: the first call runs residue (and
        teaches the learner), a later block's call is CALL-predicted —
        the learner's effect is visible in the stats, not just gauges.
        Blocks carry >=2 txs (single-tx blocks take the sequential
        path) and are BUILT serially, so all learning happens in the
        replay under test."""
        cfg = _cfg()
        seq = _cfg(parallel=False)
        builder = ChainBuilder(
            Blockchain(Storages(), seq), seq, GenesisSpec(alloc=ALLOC)
        )
        token = contract_address(ADDRS[0], 0)
        payload = ADDRS[9].rjust(32, b"\x00") + (1).to_bytes(32, "big")
        blocks = [
            builder.add_block(
                [tx(0, 0, None, 0, gas=500_000,
                    payload=_init_code(_TOKEN_RUNTIME)),
                 tx(4, 0, ADDRS[10], 3)],
                coinbase=MINER,
            ),
            builder.add_block(
                [tx(1, 0, token, 0, gas=200_000, payload=payload),
                 tx(5, 0, ADDRS[10], 3)],
                coinbase=MINER,
            ),
            builder.add_block(
                [tx(2, 0, token, 0, gas=200_000, payload=payload),
                 tx(3, 0, ADDRS[8], 5)],
                coinbase=MINER,
            ),
        ]
        reset_templates()
        bc = _fresh(cfg)
        stats = ReplayDriver(bc, cfg).replay(blocks)
        assert bc.get_header_by_number(3).hash == blocks[-1].hash
        # block 2's call learned the template; block 3's call took the
        # scheduled CALL lane (parallel) instead of the residue
        assert stats.residue_txs == 2  # deploy + learning call
        assert stats.fast_path_txs == 3  # the plain transfers
        assert stats.parallel_txs == 4  # transfers + template call
        code_hash = bc.get_world_state(
            blocks[0].header.state_root
        ).get_code_hash(token)
        verdict = LEARNER.lookup(code_hash)
        assert verdict is not None and verdict != "opaque"
        assert ("caller",) in verdict.rules and ("arg", 0) in verdict.rules


# --------------------------------------------------- misprediction path


class TestMispredictionFallback:
    # SSTORE(arg0 XOR arg1, 1): with arg1=0 the learner derives
    # ("arg", 0); a later call with arg1 != 0 lands on a DIFFERENT
    # slot than predicted -> footprint check fails -> whole-block
    # fallback to the optimistic oracle
    XOR_RUNTIME = bytes([
        0x60, 0x01, 0x60, 0x00, 0x35, 0x60, 0x20, 0x35, 0x18, 0x55,
        0x00,
    ])

    def test_misprediction_falls_back_bit_exact(self):
        cfg = _cfg()
        seq = _cfg(parallel=False)
        builder = ChainBuilder(
            Blockchain(Storages(), seq), seq, GenesisSpec(alloc=ALLOC)
        )
        xor = contract_address(ADDRS[0], 0)

        def call(i, nonce, a0, a1):
            return tx(
                i, nonce, xor, 0, gas=100_000,
                payload=a0.to_bytes(32, "big") + a1.to_bytes(32, "big"),
            )

        blocks = [
            builder.add_block(
                [tx(0, 0, None, 0, gas=500_000,
                    payload=_init_code(self.XOR_RUNTIME)),
                 tx(4, 0, ADDRS[10], 3)],
                coinbase=MINER,
            ),
            # learning call: arg1=0 -> slot == arg0 -> ("arg", 0)
            builder.add_block(
                [call(1, 0, 5, 0), tx(5, 0, ADDRS[10], 3)],
                coinbase=MINER,
            ),
            # poisoned call: slot is 5^7=2, prediction says 5
            builder.add_block(
                [call(2, 0, 5, 7), tx(3, 0, ADDRS[8], 9)],
                coinbase=MINER,
            ),
        ]
        reset_templates()
        bc = _fresh(cfg)
        stats = ReplayDriver(bc, cfg).replay(blocks)
        # correctness never depended on the prediction
        assert bc.get_header_by_number(3).hash == blocks[-1].hash
        assert stats.mispredictions >= 1
        # the poisoned code hash is demoted: re-running the same chain
        # routes its calls straight to the residue, no second fallback
        code_hash = bc.get_world_state(
            blocks[0].header.state_root
        ).get_code_hash(xor)
        assert LEARNER.lookup(code_hash) == "opaque"
        bc2 = _fresh(cfg)
        stats2 = ReplayDriver(bc2, cfg).replay(blocks)
        assert bc2.get_header_by_number(3).hash == blocks[-1].hash
        assert stats2.mispredictions == 0


# ------------------------------------------- mapping-slot templates


# ERC-20 transfer(to, amount) with real keccak mapping slots: balances
# at keccak(pad32(holder) ++ pad32(0)); calldata is the raw two words
# (arg0 = recipient, arg1 = amount). Straight-line + whitelisted, so
# the purity scan passes and the learner can trust it after
# confirmation (ISSUE 17)
_ERC20_RUNTIME = bytes([
    0x33, 0x60, 0x00, 0x52,              # mem[0:32] = caller
    0x60, 0x00, 0x60, 0x20, 0x52,        # mem[32:64] = 0 (base slot)
    0x60, 0x40, 0x60, 0x00, 0x20,        # sender slot = SHA3(0, 64)
    0x80, 0x54,                          # sender balance
    0x60, 0x20, 0x35, 0x90, 0x03,        # bal - amount
    0x90, 0x55,                          # debit sender
    0x60, 0x00, 0x35, 0x60, 0x00, 0x52,  # mem[0:32] = recipient
    0x60, 0x40, 0x60, 0x00, 0x20,        # recipient slot = SHA3(0, 64)
    0x80, 0x54,                          # recipient balance
    0x60, 0x20, 0x35, 0x01,              # bal + amount
    0x90, 0x55,                          # credit recipient
    0x00,                                # STOP
])


def _codecopy_init(runtime):
    """Constructor for runtimes wider than one PUSH word."""
    return bytes([
        0x60, len(runtime), 0x60, 0x0C, 0x60, 0x00, 0x39,  # CODECOPY
        0x60, len(runtime), 0x60, 0x00, 0xF3,              # RETURN
    ]) + runtime


class TestMappingTemplates:
    def _erc20_chain(self, n_call_blocks):
        """Deploy the ERC-20, then ``n_call_blocks`` blocks of two
        disjoint transfer(to, amount) calls each plus a filler
        transfer (single-tx blocks take the sequential path and would
        teach nothing)."""
        seq = _cfg(parallel=False)
        builder = ChainBuilder(
            Blockchain(Storages(), seq), seq, GenesisSpec(alloc=ALLOC)
        )
        token = contract_address(ADDRS[0], 0)

        def call(i, nonce, rcpt, amount):
            return tx(
                i, nonce, token, 0, gas=200_000,
                payload=rcpt.rjust(32, b"\x00")
                + amount.to_bytes(32, "big"),
            )

        blocks = [builder.add_block(
            [tx(0, 0, None, 0, gas=500_000,
                payload=_codecopy_init(_ERC20_RUNTIME)),
             tx(4, 0, ADDRS[10], 3)],
            coinbase=MINER,
        )]
        nonces = [1] + [0] * (NKEYS - 1)
        nonces[4] = 1
        holders = [
            bytes.fromhex("%040x" % (0xE20E2000 + i)) for i in range(8)
        ]
        for n in range(n_call_blocks):
            s1, s2, filler = 1 + (n % 3), 5 + (n % 3), 8 + (n % 4)
            txs = [
                call(s1, nonces[s1], holders[n % 8], 100 + 7 * n),
                call(s2, nonces[s2], holders[(n + 3) % 8], 5 + n),
                tx(filler, nonces[filler], ADDRS[11], 2 + n),
            ]
            for i in (s1, s2, filler):
                nonces[i] += 1
            blocks.append(builder.add_block(txs, coinbase=MINER))
        return blocks, token

    def test_mapping_rules_promote_after_one_observation(self):
        """One observed call is enough to derive BOTH mapping-form
        write rules — debit keccak(caller || 0), credit
        keccak(arg0 || 0) — with the arg-delta effect shapes. No
        second observation, no confirmation required for the template
        (trust comes later; the template itself must exist now)."""
        from khipu_tpu.ledger.schedule import TRUST_AFTER

        blocks, token = self._erc20_chain(1)
        reset_templates()
        cfg = _cfg()
        bc = _fresh(cfg)
        ReplayDriver(bc, cfg).replay(blocks)
        assert bc.get_header_by_number(len(blocks)).hash == blocks[-1].hash
        code_hash = bc.get_world_state(
            blocks[0].header.state_root
        ).get_code_hash(token)
        verdict = LEARNER.lookup(code_hash)
        assert verdict is not None and verdict != "opaque"
        assert ("map_caller", 0) in verdict.rules
        assert ("map_arg", 0, 0) in verdict.rules
        assert ("map_caller", 0) in verdict.write_rules
        assert ("map_arg", 0, 0) in verdict.write_rules
        # the purity scan accepted the runtime, but one observation is
        # NOT trust: effects only exist after checked confirmations,
        # and the vectorized lane further needs TRUST_AFTER of them
        assert verdict.scan is not None
        assert verdict.effects is None
        assert verdict.confirmations < TRUST_AFTER

    def test_trusted_mapping_calls_run_vectorized_bit_exact(self):
        """Past TRUST_AFTER checked confirmations the mapping calls
        execute in the trusted vectorized batch lane — visible in the
        vector_call_txs gauge — and the replay still lands on the
        serial fold's exact headers."""
        from khipu_tpu.ledger.schedule import EXEC_GAUGES, TRUST_AFTER

        blocks, token = self._erc20_chain(6)
        reset_templates()
        cfg = _cfg()
        bc = _fresh(cfg)
        before = EXEC_GAUGES["vector_call_txs"]
        stats = ReplayDriver(bc, cfg).replay(blocks)
        assert bc.get_header_by_number(len(blocks)).hash == blocks[-1].hash
        assert stats.mispredictions == 0
        # blocks 2..1+TRUST_AFTER run checked; the remaining call
        # blocks (2 calls each) run trusted
        expect_vector = 2 * (6 - 1 - TRUST_AFTER)
        assert EXEC_GAUGES["vector_call_txs"] - before >= expect_vector
        code_hash = bc.get_world_state(
            blocks[0].header.state_root
        ).get_code_hash(token)
        verdict = LEARNER.lookup(code_hash)
        assert verdict.confirmations >= TRUST_AFTER
        assert verdict.vectorizable
        # learned effects: debit is old - arg1, credit is old + arg1
        by_rule = dict(zip(verdict.write_rules, verdict.effects))
        assert by_rule[("map_caller", 0)][0] == ("old_sub_arg", 1)
        assert by_rule[("map_arg", 0, 0)][0] == ("old_add_arg", 1)

    # poisoned mapping: SSTORE(keccak(pad32(caller) ++ pad32(arg1)),
    # arg0) — with arg1=0 the learner derives ("map_caller", 0); a
    # later call with arg1 != 0 writes a DIFFERENT mapping bucket than
    # predicted -> footprint escape -> fallback + permanent demotion
    POISON_RUNTIME = bytes([
        0x33, 0x60, 0x00, 0x52,        # mem[0:32] = caller
        0x60, 0x20, 0x35,              # arg1 (base slot, attacker's)
        0x60, 0x20, 0x52,              # mem[32:64] = arg1
        0x60, 0x40, 0x60, 0x00, 0x20,  # slot = SHA3(0, 64)
        0x60, 0x00, 0x35,              # arg0 (value)
        0x90, 0x55,                    # SSTORE(slot, arg0)
        0x00,
    ])

    def test_poisoned_mapping_slot_demotes_bit_exact(self):
        """The mapping analog of the XOR misprediction test: the
        derived ("map_caller", 0) rule is a lie the learner cannot see
        from one observation. The poisoned call must fall back
        whole-block (bit-exact), demote the code hash to opaque, and a
        re-run must take the residue path with no second fallback."""
        cfg = _cfg()
        seq = _cfg(parallel=False)
        builder = ChainBuilder(
            Blockchain(Storages(), seq), seq, GenesisSpec(alloc=ALLOC)
        )
        poison = contract_address(ADDRS[0], 0)

        def call(i, nonce, a0, a1):
            return tx(
                i, nonce, poison, 0, gas=100_000,
                payload=a0.to_bytes(32, "big") + a1.to_bytes(32, "big"),
            )

        blocks = [
            builder.add_block(
                [tx(0, 0, None, 0, gas=500_000,
                    payload=_init_code(self.POISON_RUNTIME)),
                 tx(4, 0, ADDRS[10], 3)],
                coinbase=MINER,
            ),
            # learning call: arg1=0 -> slot == keccak(caller || 0)
            builder.add_block(
                [call(1, 0, 0x99, 0), tx(5, 0, ADDRS[10], 3)],
                coinbase=MINER,
            ),
            # poisoned call: arg1=3 writes keccak(caller || 3), the
            # prediction says keccak(caller || 0)
            builder.add_block(
                [call(2, 0, 7, 3), tx(3, 0, ADDRS[8], 9)],
                coinbase=MINER,
            ),
        ]
        reset_templates()
        bc = _fresh(cfg)
        stats = ReplayDriver(bc, cfg).replay(blocks)
        assert bc.get_header_by_number(3).hash == blocks[-1].hash
        assert stats.mispredictions >= 1
        code_hash = bc.get_world_state(
            blocks[0].header.state_root
        ).get_code_hash(poison)
        assert LEARNER.lookup(code_hash) == "opaque"
        bc2 = _fresh(cfg)
        stats2 = ReplayDriver(bc2, cfg).replay(blocks)
        assert bc2.get_header_by_number(3).hash == blocks[-1].hash
        assert stats2.mispredictions == 0

    def test_demotion_is_permanent(self):
        """Opaque is forever: once demoted, no stream of perfectly
        consistent observations may resurrect the template — the
        promote/demote protocol must not oscillate."""
        from khipu_tpu.native.keccak import keccak256_batch

        token = b"\x70" * 20
        code_hash = b"\x73" * 32
        learner = TemplateLearner()
        sender = ADDRS[1]
        slot = int.from_bytes(keccak256_batch(
            [sender.rjust(32, b"\x00") + b"\x00" * 32]
        )[0], "big")
        footprint = dict(
            reads={ON_ACCOUNT: {sender, token}, ON_ADDRESS: set(),
                   ON_STORAGE: {(token, slot)}, ON_CODE: {token}},
            written={ON_ACCOUNT: {sender}, ON_ADDRESS: set(),
                     ON_STORAGE: {(token, slot)}, ON_CODE: set()},
        )
        payload = (5).to_bytes(32, "big")
        learner.observe(code_hash, sender, token, payload, **footprint)
        assert learner.lookup(code_hash) != "opaque"
        learner.demote(code_hash)
        assert learner.lookup(code_hash) == "opaque"
        for _ in range(5):
            learner.observe(code_hash, sender, token, payload,
                            **footprint)
            assert learner.lookup(code_hash) == "opaque"

    def test_concurrent_observation_determinism(self):
        """Racing observers must converge on the SAME template a
        serial pass derives, for every interleaving — the learner is
        shared across executor threads and a rule set that depended on
        arrival order would make replay nondeterministic."""
        import threading

        from khipu_tpu.native.keccak import keccak256_batch

        token = b"\x70" * 20
        code_hash = b"\x74" * 32

        def observation(i):
            sender = ADDRS[i]
            rcpt = ADDRS[(i + 5) % NKEYS]
            amount = 3 + i
            pre = [sender.rjust(32, b"\x00") + b"\x00" * 32,
                   rcpt.rjust(32, b"\x00") + b"\x00" * 32]
            ss, rs = [
                int.from_bytes(k, "big") for k in keccak256_batch(pre)
            ]
            payload = (rcpt.rjust(32, b"\x00")
                       + amount.to_bytes(32, "big"))
            return sender, payload, dict(
                reads={ON_ACCOUNT: {sender, token}, ON_ADDRESS: set(),
                       ON_STORAGE: {(token, ss), (token, rs)},
                       ON_CODE: {token}},
                written={ON_ACCOUNT: {sender}, ON_ADDRESS: set(),
                         ON_STORAGE: {(token, ss), (token, rs)},
                         ON_CODE: set()},
            )

        obs = [observation(i) for i in range(NKEYS)]
        serial = TemplateLearner()
        for sender, payload, fp in obs:
            serial.observe(code_hash, sender, token, payload, **fp)
        ref = serial.lookup(code_hash)
        assert ref != "opaque" and ("map_caller", 0) in ref.rules
        for trial in range(8):
            rng = random.Random(trial)
            learner = TemplateLearner()
            order = list(obs)
            rng.shuffle(order)
            threads = [
                threading.Thread(
                    target=lambda o=o: learner.observe(
                        code_hash, o[0], token, o[1], **o[2]
                    )
                )
                for o in order
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            got = learner.lookup(code_hash)
            assert got != "opaque", f"trial {trial} went opaque"
            assert got.rules == ref.rules, f"trial {trial} diverged"
            assert got.write_rules == ref.write_rules


# ------------------------------------------------ sender prefetch cache


class TestSenderPrefetch:
    def _wire_blocks(self, n_blocks=3, txs_per_block=4):
        from khipu_tpu.domain.block import Block

        cfg = _cfg(parallel=False)
        builder = ChainBuilder(
            Blockchain(Storages(), cfg), cfg, GenesisSpec(alloc=ALLOC)
        )
        nonces = [0] * NKEYS
        blocks = []
        for n in range(n_blocks):
            txs = []
            for j in range(txs_per_block):
                i = (n * txs_per_block + j) % NKEYS
                txs.append(tx(i, nonces[i], ADDRS[(i + 5) % NKEYS], 1 + n))
                nonces[i] += 1
            blocks.append(builder.add_block(txs, coinbase=MINER))
        # wire round-trip: decode drops every per-object sender memo
        return [Block.decode(b.encode()) for b in blocks]

    def test_cache_hit_on_reimport(self):
        from khipu_tpu.sync.prefetch import (
            PREFETCH_GAUGES,
            flush_sender_cache,
            recover_block_senders,
            sender_cache_len,
        )

        flush_sender_cache()
        blocks = self._wire_blocks(n_blocks=1)
        stxs = blocks[0].body.transactions
        h0, m0 = PREFETCH_GAUGES["hits"], PREFETCH_GAUGES["misses"]
        recover_block_senders(stxs)
        assert PREFETCH_GAUGES["misses"] == m0 + len(stxs)
        assert PREFETCH_GAUGES["hits"] == h0
        first = [s.sender for s in stxs]
        assert all(a in ADDRS for a in first)
        assert sender_cache_len() == len(stxs)

        # the re-import: fresh decode, same wire bytes — all hits
        from khipu_tpu.domain.block import Block

        again = Block.decode(blocks[0].encode()).body.transactions
        assert all("sender" not in s.__dict__ for s in again)
        recover_block_senders(again)
        assert PREFETCH_GAUGES["hits"] == h0 + len(stxs)
        assert PREFETCH_GAUGES["misses"] == m0 + len(stxs)
        assert [s.sender for s in again] == first
        flush_sender_cache()
        assert sender_cache_len() == 0

    def test_lru_eviction_bounds_the_cache(self):
        from khipu_tpu.sync.prefetch import (
            PREFETCH_GAUGES,
            flush_sender_cache,
            recover_block_senders,
            sender_cache_len,
        )

        flush_sender_cache()
        blocks = self._wire_blocks(n_blocks=1, txs_per_block=6)
        e0 = PREFETCH_GAUGES["evictions"]
        recover_block_senders(
            blocks[0].body.transactions, cache_entries=2
        )
        assert sender_cache_len() == 2
        assert PREFETCH_GAUGES["evictions"] == e0 + 4
        flush_sender_cache()

    def test_prefetcher_fills_memos_in_order(self):
        from khipu_tpu.sync.prefetch import SenderPrefetcher

        blocks = self._wire_blocks()
        pf = SenderPrefetcher(blocks, depth=2)
        out = list(pf)
        pf.close()  # idempotent after natural drain
        assert [b.header.number for b in out] == [
            b.header.number for b in blocks
        ]
        for b in out:
            assert all(
                "sender" in s.__dict__ for s in b.body.transactions
            )

    def test_prefetcher_propagates_source_errors_in_position(self):
        from khipu_tpu.sync.prefetch import SenderPrefetcher

        blocks = self._wire_blocks()

        def source():
            yield blocks[0]
            raise RuntimeError("wire hiccup")

        pf = SenderPrefetcher(source(), depth=2)
        it = iter(pf)
        assert next(it).header.number == blocks[0].header.number
        with pytest.raises(RuntimeError, match="wire hiccup"):
            next(it)
        pf.close()


# --------------------------------------------------- process-wide pool


class TestExecPool:
    def test_pool_is_shared_and_resizable(self):
        from khipu_tpu.ledger.ledger import _exec_pool, shutdown_exec_pool

        a = _exec_pool(4)
        assert _exec_pool(4) is a  # same width -> same pool
        b = _exec_pool(2)
        assert b is not a  # width change rebuilds
        assert _exec_pool(2) is b
        shutdown_exec_pool()
        c = _exec_pool(2)
        assert c is not b  # shutdown releases; next call rebuilds
        assert c.submit(lambda: 41 + 1).result() == 42
        shutdown_exec_pool()


# ------------------------------------------- segment-local recovery


class TestSegmentRollback:
    """A misprediction rolls the merged world back to the last residue
    barrier and re-runs that segment serially (ISSUE 35). Blocks are
    built by the sequential executor and executed with validate=True:
    gas, receipts root, bloom and state root are held to its headers."""

    XOR_RUNTIME = TestMispredictionFallback.XOR_RUNTIME
    BASE = 0x50000  # above the literal-slot rule's ceiling

    @staticmethod
    def _execute(blocks, cfg):
        from khipu_tpu.ledger.ledger import execute_block

        reset_templates()
        bc = _fresh(cfg)
        parent = bc.get_header_by_number(0)
        stats = []
        for block in blocks:
            result = execute_block(
                block, parent.state_root, bc.get_world_state, cfg)
            bc.save_block(block, result.receipts, block.header.difficulty,
                          result.world)
            stats.append(result.stats)
            parent = block.header
        assert bc.get_header_by_number(len(blocks)).hash == blocks[-1].hash
        return stats, bc

    def _xor_call(self, xor):
        def call(i, nonce, a0, a1):
            return tx(
                i, nonce, xor, 0, gas=100_000,
                payload=a0.to_bytes(32, "big") + a1.to_bytes(32, "big"),
            )
        return call

    def test_an_escape_into_a_slot_a_later_tx_already_used_needs_the_rollback(
            self):
        """The contaminating order. h and i are predicted on one slot, so
        i waits for batch 1; j (a higher index) is predicted elsewhere
        and runs in batch 0, BEFORE i. i's real write lands on j's slot:
        serially j's SSTORE is a no-op (800 gas) on what i stored, in
        the attempt it was the first store (20,000). Keeping the
        attempt's prefix would keep j's wrong gas; only running the
        segment again from the checkpoint, in index order, is exact."""
        from khipu_tpu.domain.transaction import recover_senders

        seq = _cfg(parallel=False)
        builder = ChainBuilder(
            Blockchain(Storages(), seq), seq, GenesisSpec(alloc=ALLOC)
        )
        xor = contract_address(ADDRS[0], 0)
        call = self._xor_call(xor)
        B = self.BASE
        blocks = [
            builder.add_block(
                [tx(0, 0, None, 0, gas=500_000,
                    payload=_init_code(self.XOR_RUNTIME)),
                 tx(4, 0, ADDRS[10], 3)],
                coinbase=MINER,
            ),
            # learning call: arg1=0 -> slot == arg0 -> ("arg", 0)
            builder.add_block(
                [call(1, 0, B + 9, 0), tx(5, 0, ADDRS[10], 3)],
                coinbase=MINER,
            ),
            builder.add_block(
                [call(2, 0, B, 0),      # h: slot B
                 call(3, 0, B, 7),      # i: predicted B, writes B ^ 7
                 call(6, 0, B ^ 7, 0),  # j: slot B ^ 7
                 tx(7, 0, ADDRS[8], 9)],
                coinbase=MINER,
            ),
        ]
        cfg = _cfg()
        stats, bc = self._execute(blocks[:2], cfg)
        # the plan the executor will make of block 3: j before i
        txs = list(blocks[2].body.transactions)
        recover_senders(txs)
        plan = plan_block(
            txs, [t.sender for t in txs], MINER,
            bc.get_world_state(blocks[1].header.state_root).get_code_hash,
        )
        assert [s.indices for s in plan.steps] == [[0, 2, 3], [1]]
        stats, bc = self._execute(blocks, cfg)
        escaped = stats[2]
        assert not escaped.fallback and escaped.mispredicted_txs == 1
        assert (escaped.reruns, escaped.rerun_txs) == (1, 4)
        assert escaped.lane_txs["residue"] == 4
        h, i, j = (r.cumulative_gas_used for r in bc.get_receipts(3)[:3])
        # h and i each stored first (20,000); j found i's value (800)
        assert h > 40_000 and i - h > 40_000 and j - i < 25_000

    def test_a_rolled_back_segment_with_vector_calls_and_transfers(self):
        """The escaping call sits in batch 1, so batch 0's trusted
        (vectorised) token calls and plain transfers have already
        changed the merged world when it escapes: the rollback undoes
        them, and the serial re-run books all of them under residue."""
        from khipu_tpu.ledger.schedule import EXEC_GAUGES

        seq = _cfg(parallel=False)
        builder = ChainBuilder(
            Blockchain(Storages(), seq), seq, GenesisSpec(alloc=ALLOC)
        )
        token = contract_address(ADDRS[0], 0)
        xor = contract_address(ADDRS[1], 0)
        call = self._xor_call(xor)
        B = self.BASE
        holders = [
            bytes.fromhex("%040x" % (0xE20E2000 + i)) for i in range(4)
        ]

        def send(i, nonce, rcpt, amount):
            return tx(
                i, nonce, token, 0, gas=200_000,
                payload=rcpt.rjust(32, b"\x00")
                + amount.to_bytes(32, "big"),
            )

        blocks = [
            builder.add_block(
                [tx(0, 0, None, 0, gas=500_000,
                    payload=_codecopy_init(_ERC20_RUNTIME)),
                 tx(1, 0, None, 0, gas=500_000,
                    payload=_init_code(self.XOR_RUNTIME))],
                coinbase=MINER,
            ),
            # observed in the residue: one template each
            builder.add_block(
                [send(2, 0, holders[0], 100), call(3, 0, B + 9, 0)],
                coinbase=MINER,
            ),
            # two checked token calls: TRUST_AFTER confirmations
            builder.add_block(
                [send(2, 1, holders[1], 7), send(4, 0, holders[2], 8)],
                coinbase=MINER,
            ),
            builder.add_block(
                [send(2, 2, holders[3], 5),   # trusted: vector
                 call(5, 0, B, 0),            # checked, stands
                 send(4, 1, holders[0], 6),   # trusted: vector
                 tx(6, 0, ADDRS[10], 3),      # plain: vector
                 call(7, 0, B, 7),            # batch 1: escapes
                 tx(8, 0, ADDRS[11], 4)],
                coinbase=MINER,
            ),
        ]
        vector_calls = EXEC_GAUGES["vector_call_txs"]
        stats, bc = self._execute(blocks, _cfg())
        assert stats[2].lane_txs["checked"] == 2
        escaped = stats[3]
        assert not escaped.fallback and escaped.mispredicted_txs == 1
        assert (escaped.reruns, escaped.rerun_txs) == (1, 6)
        assert escaped.lane_txs["residue"] == 6
        assert escaped.lane_txs["vector"] == 0 and escaped.fast_path_txs == 0
        assert escaped.lane_seconds["vector"] > 0  # the attempt's, kept
        # the attempt did vectorise the two trusted calls before it
        # was rolled back
        assert EXEC_GAUGES["vector_call_txs"] == vector_calls + 2
        erc20_hash = bc.get_world_state(
            blocks[0].header.state_root).get_code_hash(token)
        xor_hash = bc.get_world_state(
            blocks[0].header.state_root).get_code_hash(xor)
        assert LEARNER.lookup(xor_hash) == "opaque"
        assert LEARNER.lookup(erc20_hash) != "opaque"
