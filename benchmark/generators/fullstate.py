"""The serving side of a whole fast sync: the peers a node downloads a
state from, each a process of its own.

A peer is the program's own serving stack (``PeerManager`` with
``HostService`` over a ``Blockchain`` on Kesque) in a child process that
never imports JAX: a deployment's peers are other machines and must not
take the node's GIL, and the chip belongs to the node. Each child copies
the seed's genesis data dir (``generators/state.py``'s state, as
``load_genesis`` persisted it: the account trie, every storage trie, the
code), lays a chain of empty post-Merge blocks over it (no reward, so
the state root never moves and any pivot's root is the genesis state's),
listens for RLPx on loopback and says where on its first line of stdout.
Peers stand at different heights of the one chain (``heights``), so the
median the node takes its pivot from is of numbers that differ.

A peer forges a seeded share of its ``NodeData`` answers: hash ``h`` is
forged by the one peer it is dealt to, the first time that peer is asked
for it (one bit of the blob's middle byte flipped), and answered
honestly ever after and by every other peer; dealt over ``peers`` peers,
1 answer in ``forge_one_in`` is forged when requests are spread evenly.
Requests of fewer than ``FORGE_MIN_REQUEST`` hashes are never forged, so
that no batch comes back without one good node (the syncer takes that
for a dead peer set and gives up). Each peer keeps what it forged and
counts what it served; ``report`` on its stdin prints both.

``python fullstate.py genesis '<json>'`` builds a seed's genesis data
dir, exactly as ``drivers/sync_state.py`` does, for a seed that
``sync.deep`` has not left in the cache yet.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from typing import Dict, List, Optional

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

STATE_KEYS = ("accounts", "funded_senders", "token_contracts", "token_slots")
KINDS = ("state", "storage", "code")     # the program's three node stores
FORGE_MIN_REQUEST = 8
BLOCK_SECONDS = 12


# ------------------------------------------------------------ the chain


def empty_chain(genesis, blocks: int) -> List:
    """Headers 1..``blocks`` over ``genesis``: no transactions, no
    ommers, difficulty 0 and no reward, so every state root is the
    genesis state's."""
    from khipu_tpu.domain.block_header import BlockHeader

    out, parent = [], genesis
    for n in range(1, blocks + 1):
        parent = BlockHeader(
            parent_hash=parent.hash, ommers_hash=genesis.ommers_hash,
            beneficiary=genesis.beneficiary, state_root=genesis.state_root,
            transactions_root=genesis.transactions_root,
            receipts_root=genesis.receipts_root,
            logs_bloom=genesis.logs_bloom, difficulty=0, number=n,
            gas_limit=genesis.gas_limit, gas_used=0,
            unix_timestamp=genesis.unix_timestamp + BLOCK_SECONDS * n)
        out.append(parent)
    return out


def heights(chain_blocks: int, peers: int, step: int = 4) -> List[int]:
    """Peer i's best block: ``step`` blocks apart, the tallest at
    ``chain_blocks``."""
    return [chain_blocks - step * i for i in range(peers)]


def pivot_number(bests: List[int], offset: int) -> int:
    """The upstream's rule (FastSyncService.scala:184-273): the median of
    the peers' best numbers less the offset."""
    return max(1, sorted(bests)[len(bests) // 2] - offset)


# -------------------------------------------------------------- forgery


def dealt_to(h: bytes, seed: int, peers: int, forge_one_in: int) -> int:
    """The peer that forges ``h`` on its first request, or -1."""
    if not forge_one_in:
        return -1
    x = int.from_bytes(h[:8], "big") ^ (seed * 0x9E3779B97F4A7C15 & (2**64 - 1))
    share = max(1, forge_one_in // peers)
    return (x // share) % peers if x % share == 0 else -1


def forge(value: bytes) -> bytes:
    """One flipped bit in the middle byte; its own inverse."""
    pos = len(value) // 2
    return value[:pos] + bytes([value[pos] ^ 0x40]) + value[pos + 1:]


# ------------------------------------------------------- a peer (child)


def serve(a: Dict) -> int:
    """``python fullstate.py serve '<json>'``: one peer, until stdin
    closes (so no peer outlives the run that started it)."""
    from khipu_tpu.base.crypto.secp256k1 import privkey_to_pubkey
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.block import Block
    from khipu_tpu.domain.blockchain import Blockchain
    from khipu_tpu.network.host_service import HostService
    from khipu_tpu.network.messages import ETH_OFFSET, GET_NODE_DATA, Status
    from khipu_tpu.network.peer import PeerManager
    from khipu_tpu.storage.storages import Storages

    index, seed, peers = int(a["index"]), int(a["seed"]), int(a["peers"])
    one_in = int(a["forge_one_in"])
    shutil.copytree(a["genesis_dir"], a["dir"])
    # every node is asked for once: a read cache would only take memory
    storages = Storages(engine="kesque", data_dir=a["dir"], cache_size=1024)
    chain = Blockchain(storages, fixture_config(chain_id=1))
    genesis = chain.get_header_by_number(0)
    td = chain.get_total_difficulty(0) or 0
    for header in empty_chain(genesis, int(a["best"])):
        chain.save_block(Block(header), [], td)

    def status() -> Status:
        best = chain.best_block_number
        return Status(63, 1, td, chain.get_hash_by_number(best), genesis.hash)

    key = (0xFA57 << 64 | (seed % 2**40) << 8 | index).to_bytes(32, "big")
    manager = PeerManager(key, f"khipu-tpu/bench-peer{index}", status)
    HostService(chain).install(manager)
    honest = manager.handlers[ETH_OFFSET + GET_NODE_DATA]
    forged: Dict[bytes, bytes] = {}
    served = {"requests": 0, "blobs": 0}

    def node_data(body):
        code, blobs = honest(body)
        served["requests"] += 1
        served["blobs"] += len(blobs)
        if len(blobs) == len(body) >= FORGE_MIN_REQUEST:
            for i, h in enumerate(body):
                if (dealt_to(h, seed, peers, one_in) == index
                        and h not in forged):
                    forged[h] = blobs[i] = forge(blobs[i])
        return code, blobs

    manager.handlers[ETH_OFFSET + GET_NODE_DATA] = node_data
    port = manager.listen("127.0.0.1", 0)
    sources = (storages.account_node_storage, storages.storage_node_storage,
               storages.evmcode_storage)
    print(json.dumps({
        "port": port, "pub": privkey_to_pubkey(key).hex(),
        "best": chain.best_block_number, "genesis": genesis.encode().hex(),
        "nodes": {k: s.source.count for k, s in zip(KINDS, sources)},
    }), flush=True)
    for line in sys.stdin:
        if line.strip() == "report":
            print(json.dumps(dict(served, forged=[
                [h.hex(), v.hex()] for h, v in forged.items()])), flush=True)
    manager.stop()
    os._exit(0)  # the copy is thrown away: no index checkpoint, no flush


class Peers:
    """The peers of one run: started together, asked together, stopped
    together."""

    def __init__(self, procs: List[subprocess.Popen], hellos: List[Dict]):
        self.procs, self.hellos = procs, hellos

    @classmethod
    def start(cls, genesis_dir: str, run_dir: str, seed: int,
              bests: List[int], forge_one_in: int) -> "Peers":
        procs: List[subprocess.Popen] = []
        try:
            for i, best in enumerate(bests):
                procs.append(subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__), "serve",
                     json.dumps({
                         "index": i, "seed": seed, "peers": len(bests),
                         "best": best, "forge_one_in": forge_one_in,
                         "genesis_dir": genesis_dir,
                         "dir": os.path.join(run_dir, f"peer{i}")})],
                    env=dict(os.environ, JAX_PLATFORMS="cpu"),
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True))
            self = cls(procs, [])
            self.hellos = self._read_all()
            return self
        except BaseException:  # no child outlives a set-up that failed
            cls(procs, []).stop()
            raise

    def _read_all(self) -> List[Dict]:
        out = []
        for i, p in enumerate(self.procs):
            line = p.stdout.readline()
            if not line:
                raise RuntimeError(f"peer {i} exited {p.wait()}")
            out.append(json.loads(line))
        return out

    def report(self) -> List[Dict]:
        """Per peer: ``requests`` and ``blobs`` served so far, and
        ``forged`` as {claimed hash: the forged blob sent for it}."""
        for p in self.procs:
            p.stdin.write("report\n")
            p.stdin.flush()
        rows = self._read_all()
        for row in rows:
            row["forged"] = {bytes.fromhex(h): bytes.fromhex(v)
                             for h, v in row["forged"]}
        return rows

    def stop(self) -> None:
        for p in self.procs:
            if p.stdin and not p.stdin.closed:
                p.stdin.close()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


# ------------------------------------------------- the genesis (child)


def build_genesis(a: Dict) -> int:
    """``python fullstate.py genesis '<json>'``: the seed's state into a
    Kesque data dir through ``load_genesis``, then the ``ok`` file: what
    ``drivers/sync_state.py`` builds in line for ``sync.deep``."""
    from benchmark.generators import state as gen_state
    from khipu_tpu.config import fixture_config
    from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
    from khipu_tpu.storage.storages import Storages

    state = gen_state.make_state(a["sizes"], int(a["seed"]))
    shutil.rmtree(a["dir"], ignore_errors=True)
    os.makedirs(a["dir"])
    storages = Storages(engine="kesque", data_dir=a["dir"])
    Blockchain(storages, fixture_config(chain_id=1)).load_genesis(
        GenesisSpec(alloc=state["alloc"], gas_limit=int(a["gas_limit"])))
    storages.stop()  # flushes and closes: the peers open copies
    open(a["ok"], "w").close()
    return 0


def start_genesis_builder(sizes: Dict, seed: int, gas_limit: int,
                          genesis_dir: str, ok_file: str
                          ) -> Optional[subprocess.Popen]:
    """None if the seed's genesis is in the cache, else the child that
    builds it (JAX held to the CPU there)."""
    if os.path.exists(ok_file):
        return None
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "genesis", json.dumps({
            "sizes": {k: sizes[k] for k in STATE_KEYS}, "seed": seed,
            "gas_limit": gas_limit, "dir": genesis_dir, "ok": ok_file})],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), stdout=subprocess.DEVNULL)


def main(argv) -> int:
    command = {"serve": serve, "genesis": build_genesis}[argv[1]]
    return command(json.loads(argv[2]))


if __name__ == "__main__":
    sys.exit(main(sys.argv))
