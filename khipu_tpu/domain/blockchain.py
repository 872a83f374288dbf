"""Blockchain facade: chain DB over the typed storages.

Parity: domain/Blockchain.scala:170-379 (getWorldState:301,
getAccount:336, saveNewBlock:362 — world.persist + header/body/
receipts/td/blocknum/tx index + best number, removeBlock:322) and
blockchain/data/GenesisDataLoader.scala:70 (alloc -> state trie ->
stored genesis, with the stored-vs-computed hash check).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional, Union

from khipu_tpu.base.crypto.keccak import keccak256
from khipu_tpu.base.rlp import rlp_encode
from khipu_tpu.config import KhipuConfig
from khipu_tpu.domain.account import EMPTY_CODE_HASH, Account, address_key
from khipu_tpu.domain.block import Block, BlockBody
from khipu_tpu.domain.block_header import EMPTY_OMMERS_HASH, BlockHeader
from khipu_tpu.domain.receipt import Receipt, decode_receipts, encode_receipts
from khipu_tpu.ledger.bloom import EMPTY_BLOOM
from khipu_tpu.evm.dataword import to_minimal_bytes
from khipu_tpu.ledger.world import BlockWorldState, TrieStorage
from khipu_tpu.observability.registry import REGISTRY
from khipu_tpu.observability.trace import span
from khipu_tpu.storage.storages import Storages
from khipu_tpu.trie.bulk import bulk_build, device_hasher, host_hasher
from khipu_tpu.trie.mpt import EMPTY_TRIE_HASH, MerklePatriciaTrie

RECEIPT_LOGS = REGISTRY.counter(
    "khipu_receipt_logs_total",
    help="log entries in the receipts save_block stored "
         "(domain/blockchain.py)",
)


@dataclass(frozen=True)
class GenesisAccount:
    """One ``alloc`` entry in geth's genesis shape: a pre-deployed
    contract (or any account that is more than a balance)."""

    balance: int = 0
    nonce: Optional[int] = None  # None: the chain's account_start_nonce
    code: bytes = b""
    storage: Mapping[int, int] = field(default_factory=dict)  # slot -> value


@dataclass(frozen=True)
class GenesisSpec:
    """Genesis parameters + alloc (GenesisDataLoader's JSON shape)."""

    # address -> wei, or a full account record
    alloc: Dict[bytes, Union[int, GenesisAccount]] = field(
        default_factory=dict)
    difficulty: int = 0x020000
    gas_limit: int = 8_000_000
    timestamp: int = 0
    extra_data: bytes = b""
    nonce: bytes = b"\x00" * 8
    mix_hash: bytes = b"\x00" * 32
    coinbase: bytes = b"\x00" * 20


class Blockchain:
    def __init__(self, storages: Storages, config: KhipuConfig):
        self.storages = storages
        self.config = config

    # ------------------------------------------------------------ worlds

    def get_world_state(self, state_root: bytes) -> BlockWorldState:
        """Fresh world at a state root (getWorldState:301)."""
        return BlockWorldState(
            MerklePatriciaTrie(
                self.storages.account_node_storage, root_hash=state_root
            ),
            self.storages.storage_node_storage,
            self.storages.evmcode_storage,
            get_block_hash=self.get_hash_by_number,
            account_start_nonce=self.config.blockchain.account_start_nonce,
        )

    def get_account(
        self, address: bytes, state_root: bytes
    ) -> Optional[Account]:
        trie = MerklePatriciaTrie(
            self.storages.account_node_storage, root_hash=state_root
        )
        raw = trie.get(address_key(address))
        return Account.decode(raw) if raw is not None else None

    # ------------------------------------------------------------ blocks

    def get_hash_by_number(self, number: int) -> Optional[bytes]:
        return self.storages.block_numbers.hash_of(number)

    def get_header_by_number(self, number: int) -> Optional[BlockHeader]:
        raw = self.storages.block_header_storage.get(number)
        return BlockHeader.decode(raw) if raw is not None else None

    def get_header_by_hash(self, block_hash: bytes) -> Optional[BlockHeader]:
        """Hash-verified lookup through the hash->number index (a stale
        index entry after a reorg must not alias another header)."""
        n = self.storages.block_numbers.number_of(block_hash)
        if n is None:
            return None
        header = self.get_header_by_number(n)
        if header is not None and header.hash == block_hash:
            return header
        return None

    def get_block_by_number(self, number: int) -> Optional[Block]:
        header = self.get_header_by_number(number)
        if header is None:
            return None
        raw = self.storages.block_body_storage.get(number)
        body = BlockBody.decode(raw) if raw is not None else BlockBody()
        return Block(header, body)

    def get_receipts(self, number: int) -> Optional[List[Receipt]]:
        raw = self.storages.receipts_storage.get(number)
        return decode_receipts(raw) if raw is not None else None

    def get_total_difficulty(self, number: int) -> Optional[int]:
        return self.storages.total_difficulty_storage.get_td(number)

    @property
    def best_block_number(self) -> int:
        return self.storages.best_block_number

    def save_block(
        self,
        block: Block,
        receipts: List[Receipt],
        total_difficulty: int,
        world: Optional[BlockWorldState] = None,
        hasher=None,
    ) -> None:
        """saveNewBlock:362: world.persist + all block storages +
        best-number advance. ``hasher`` routes the trie commit through
        the batched device path; the root equality check below gates it
        against the header either way."""
        s = self.storages
        if world is not None:
            root = world.persist(
                s.account_node_storage,
                s.storage_node_storage,
                s.evmcode_storage,
                hasher=hasher,
            )
            if root != block.header.state_root:
                raise ValueError(
                    f"persisted root {root.hex()} != header state root "
                    f"{block.header.state_root.hex()}"
                )
        n = block.number
        s.block_header_storage.put(n, block.header.encode())
        s.block_body_storage.put(n, block.body.encode())
        s.receipts_storage.put(n, encode_receipts(receipts))
        RECEIPT_LOGS.inc(sum(len(r.logs) for r in receipts))
        s.total_difficulty_storage.put_td(n, total_difficulty)
        s.block_numbers.put(block.hash, n)
        for i, tx in enumerate(block.body.transactions):
            s.transaction_storage.put(tx.hash, n, i)
        s.app_state.best_block_number = n

    def remove_block(self, block_hash: bytes) -> None:
        """removeBlock:322 (reorg orphaning)."""
        s = self.storages
        n = s.block_numbers.number_of(block_hash)
        if n is None:
            return
        block = self.get_block_by_number(n)
        if block is not None and block.hash == block_hash:
            for tx in block.body.transactions:
                s.transaction_storage.source.remove(tx.hash)
            s.block_header_storage.source.remove(n)
            s.block_body_storage.source.remove(n)
            s.receipts_storage.source.remove(n)
            s.total_difficulty_storage.source.remove(n)
        s.block_numbers.remove(block_hash)

    # ----------------------------------------------------------- genesis

    def load_genesis(
        self, spec: GenesisSpec, on_device: bool = False
    ) -> Block:
        """Build + persist the genesis state and block
        (GenesisDataLoader.scala:70). The alloc trie, and the storage
        trie of every alloc entry that has storage, go through the
        level-synchronous bulk build — the TPU path when on_device."""
        start_nonce = self.config.blockchain.account_start_nonce
        hasher = device_hasher if on_device else host_hasher
        with span("genesis.load", accounts=len(spec.alloc)) as sp:
            # a contract's storage trie is built as BlockWorldState
            # stores it: key keccak(pad32(slot)), value RLP of the
            # trimmed integer, zero values absent
            records: Dict[bytes, Account] = {}
            storage_nodes: Dict[bytes, bytes] = {}
            codes: Dict[bytes, bytes] = {}
            slots = 0
            with span("genesis.storage_tries"):
                for addr, entry in spec.alloc.items():
                    if isinstance(entry, int):
                        continue
                    cells = [
                        (TrieStorage.key_bytes(slot),
                         rlp_encode(to_minimal_bytes(value)))
                        for slot, value in entry.storage.items() if value
                    ]
                    # no cells: the empty trie's root and no nodes
                    storage_root, trie_nodes = bulk_build(
                        cells, hasher=hasher)
                    slots += len(cells)
                    storage_nodes.update(trie_nodes)
                    code_hash = EMPTY_CODE_HASH
                    if entry.code:
                        code_hash = keccak256(entry.code)
                        codes[code_hash] = bytes(entry.code)
                    records[addr] = Account(
                        nonce=start_nonce if entry.nonce is None
                        else entry.nonce,
                        balance=entry.balance,
                        storage_root=storage_root,
                        code_hash=code_hash,
                    )
            with span("genesis.account_trie"):
                pairs = [
                    (
                        address_key(addr),
                        (Account(nonce=start_nonce, balance=entry)
                         if isinstance(entry, int)
                         else records[addr]).encode(),
                    )
                    for addr, entry in spec.alloc.items()
                ]
                state_root, nodes = bulk_build(pairs, hasher=hasher)
            with span("genesis.store"):
                s = self.storages
                if storage_nodes:
                    s.storage_node_storage.update([], storage_nodes)
                for code_hash, code in codes.items():
                    s.evmcode_storage.put(code_hash, code)
                s.account_node_storage.update([], nodes)
            sp.set_tag("contracts", len(records))
            sp.set_tag("slots", slots)
            sp.set_tag("nodes", len(nodes) + len(storage_nodes))

        header = BlockHeader(
            parent_hash=b"\x00" * 32,
            ommers_hash=EMPTY_OMMERS_HASH,
            beneficiary=spec.coinbase,
            state_root=state_root,
            transactions_root=EMPTY_TRIE_HASH,
            receipts_root=EMPTY_TRIE_HASH,
            logs_bloom=EMPTY_BLOOM,
            difficulty=spec.difficulty,
            number=0,
            gas_limit=spec.gas_limit,
            gas_used=0,
            unix_timestamp=spec.timestamp,
            extra_data=spec.extra_data,
            mix_hash=spec.mix_hash,
            nonce=spec.nonce,
        )
        genesis = Block(header, BlockBody())

        existing = self.get_header_by_number(0)
        if existing is not None and existing.hash != header.hash:
            raise ValueError(
                "stored genesis hash differs from computed genesis"
            )
        self.save_block(genesis, [], header.difficulty)
        return genesis
