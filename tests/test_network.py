"""Network stack tests over loopback: ECIES, RLPx handshake/framing,
snappy, full peer connections serving chain data, Kademlia discovery
(parity targets SURVEY §2.7 RLPx stack, HostService, discovery)."""

import time

import pytest

from khipu_tpu.base.crypto.keccak import keccak256
from khipu_tpu.base.crypto.secp256k1 import (
    privkey_to_pubkey,
    pubkey_to_address,
)
from khipu_tpu.config import fixture_config
from khipu_tpu.domain.blockchain import Blockchain, GenesisSpec
from khipu_tpu.domain.transaction import Transaction, sign_transaction
from khipu_tpu.network import snappy_codec
from khipu_tpu.network.ecies import EciesError, decrypt, encrypt
from khipu_tpu.network.rlpx import (
    AuthHandshake,
    FrameCodec,
    _IncrementalKeccak,
)
from khipu_tpu.storage.storages import Storages
from khipu_tpu.sync.chain_builder import ChainBuilder

PRIV_A = (11).to_bytes(32, "big")
PRIV_B = (22).to_bytes(32, "big")
PUB_A = privkey_to_pubkey(PRIV_A)
PUB_B = privkey_to_pubkey(PRIV_B)


class TestEcies:
    def test_roundtrip(self):
        msg = b"rlpx auth payload" * 3
        ct = encrypt(PUB_B, msg, shared_mac_data=b"\x01\x02")
        assert decrypt(PRIV_B, ct, shared_mac_data=b"\x01\x02") == msg

    def test_tamper_and_wrong_key_rejected(self):
        ct = encrypt(PUB_B, b"secret")
        bad = ct[:-1] + bytes([ct[-1] ^ 1])
        with pytest.raises(EciesError):
            decrypt(PRIV_B, bad)
        with pytest.raises(EciesError):
            decrypt(PRIV_A, ct)
        with pytest.raises(EciesError):
            decrypt(PRIV_B, ct, shared_mac_data=b"x")


class TestSnappy:
    def test_roundtrip(self):
        for payload in (b"", b"a", b"hello" * 100, bytes(range(256)) * 7):
            assert snappy_codec.decompress(
                snappy_codec.compress(payload)
            ) == payload

    def test_decodes_copy_tags(self):
        # hand-built stream: literal "abcd" + 1-byte-offset copy of 4
        # back-referencing "abcd" => "abcdabcd"
        stream = bytes([8]) + bytes([(4 - 1) << 2]) + b"abcd" + bytes(
            [(0 << 5) | ((4 - 4) << 2) | 1, 4]
        )
        assert snappy_codec.decompress(stream) == b"abcdabcd"

    def test_overlapping_copy(self):
        # literal "ab" + copy(offset=2, len=6) => "abababab"
        stream = bytes([8, (2 - 1) << 2]) + b"ab" + bytes(
            [((6 - 4) << 2) | 1, 2]
        )
        assert snappy_codec.decompress(stream) == b"abababab"

    def test_bad_streams_rejected(self):
        with pytest.raises(snappy_codec.SnappyError):
            snappy_codec.decompress(b"")
        with pytest.raises(snappy_codec.SnappyError):
            # declared 100 bytes, provides none
            snappy_codec.decompress(bytes([100]))
        with pytest.raises(snappy_codec.SnappyError):
            # copy before any output
            snappy_codec.decompress(bytes([4, 0b101, 1]))


class TestIncrementalKeccak:
    def test_matches_oneshot_and_continues(self):
        k = _IncrementalKeccak()
        k.update(b"hello ")
        k.update(b"world")
        assert k.digest() == keccak256(b"hello world")
        # stream continues after digest snapshot
        k.update(b"!")
        assert k.digest() == keccak256(b"hello world!")

    def test_block_boundaries(self):
        k = _IncrementalKeccak()
        blob = bytes(range(256)) * 3  # > 5 rate blocks
        for i in range(0, len(blob), 37):
            k.update(blob[i : i + 37])
        assert k.digest() == keccak256(blob)


    def test_the_native_running_sponge_is_the_pure_one(self):
        """``rlpx._mac_stream`` hands the frame MAC the native sponge:
        same digests as the pure one after every update, however the
        stream is cut, and the stream goes on after each read."""
        import random

        from khipu_tpu.native import keccak as native_keccak
        from khipu_tpu.network import rlpx

        if not native_keccak.available():
            pytest.skip("no native library on this host")
        assert isinstance(rlpx._mac_stream(),
                          native_keccak.RunningKeccak256)
        rng = random.Random(44)
        for _ in range(40):
            native, pure, whole = (
                native_keccak.RunningKeccak256(), _IncrementalKeccak(), b"")
            for _ in range(rng.randint(1, 12)):
                chunk = rng.randbytes(
                    rng.choice([0, 1, 16, 135, 136, 137, 272, 300, 1000]))
                native.update(chunk)
                pure.update(chunk)
                whole += chunk
                assert native.digest() == pure.digest() == keccak256(whole)


class TestRlpxHandshake:
    def _pair(self):
        initiator = AuthHandshake(PRIV_A)
        responder = AuthHandshake(PRIV_B)
        auth = initiator.create_auth(PUB_B)
        remote_pub = responder.handle_auth(auth)
        assert remote_pub == PUB_A
        ack, resp_secrets = responder.create_ack(remote_pub)
        init_secrets = initiator.handle_ack(ack)
        return init_secrets, resp_secrets

    def test_secrets_agree(self):
        a, b = self._pair()
        assert a.aes == b.aes
        assert a.mac == b.mac
        assert a.egress_mac.digest() == b.ingress_mac.digest()
        assert a.ingress_mac.digest() == b.egress_mac.digest()

    def test_frames_roundtrip_both_directions(self):
        a, b = self._pair()
        ca, cb = FrameCodec(a), FrameCodec(b)
        for i, msg in enumerate(
            [b"\x80", b"ping", b"x" * 15, b"y" * 16, b"z" * 1000]
        ):
            wire = ca.write_frame(msg)
            size = cb.read_header(wire[:32])
            assert cb.read_frame(size, wire[32:]) == msg
            back = cb.write_frame(msg + b"-reply")
            size = ca.read_header(back[:32])
            assert ca.read_frame(size, back[32:]) == msg + b"-reply"

    def test_tampered_frame_rejected(self):
        a, b = self._pair()
        ca, cb = FrameCodec(a), FrameCodec(b)
        wire = bytearray(ca.write_frame(b"payload"))
        wire[40] ^= 1  # flip a ciphertext byte
        size = cb.read_header(bytes(wire[:32]))
        with pytest.raises(ValueError, match="MAC"):
            cb.read_frame(size, bytes(wire[32:]))


CFG = fixture_config(chain_id=1)
KEYS = [(i + 1).to_bytes(32, "big") for i in range(3)]
ADDRS = [pubkey_to_address(privkey_to_pubkey(k)) for k in KEYS]


def make_chain(n_blocks=3):
    bc = Blockchain(Storages(), CFG)
    builder = ChainBuilder(
        bc, CFG, GenesisSpec(alloc={a: 10**21 for a in ADDRS})
    )
    for n in range(n_blocks):
        builder.add_block(
            [sign_transaction(
                Transaction(n, 10**9, 21000, ADDRS[1], 5), KEYS[0], chain_id=1
            )],
            coinbase=b"\xaa" * 20,
        )
    return bc


class TestPeerStack:
    def test_full_stack_serves_chain_data(self):
        from khipu_tpu.network.host_service import HostService
        from khipu_tpu.network.messages import (
            BLOCK_BODIES,
            BLOCK_HEADERS,
            ETH_OFFSET,
            GET_BLOCK_BODIES,
            GET_BLOCK_HEADERS,
            GET_NODE_DATA,
            NODE_DATA,
            GetBlockHeaders,
            Status,
            decode_headers,
        )
        from khipu_tpu.network.peer import PeerManager

        bc = make_chain()

        def status():
            best = bc.best_block_number
            return Status(
                63, 1,
                bc.get_total_difficulty(best) or 0,
                bc.get_header_by_number(best).hash,
                bc.get_header_by_number(0).hash,
            )

        server = PeerManager(PRIV_B, "khipu-tpu/server", status)
        HostService(bc).install(server)
        port = server.listen()

        client = PeerManager(PRIV_A, "khipu-tpu/client", status)
        try:
            peer = client.connect("127.0.0.1", port, PUB_B)
            assert peer.hello.client_id == "khipu-tpu/server"
            assert peer.status.total_difficulty == status().total_difficulty
            assert peer.snappy  # p2p v5 both sides

            # headers by number range
            body = peer.request(
                ETH_OFFSET + GET_BLOCK_HEADERS,
                GetBlockHeaders(1, max_headers=3).body(),
                ETH_OFFSET + BLOCK_HEADERS,
            )
            headers = decode_headers(body)
            assert [h.number for h in headers] == [1, 2, 3]
            assert headers[2].hash == bc.get_header_by_number(3).hash

            # bodies by hash
            bodies = peer.request(
                ETH_OFFSET + GET_BLOCK_BODIES,
                [headers[0].hash],
                ETH_OFFSET + BLOCK_BODIES,
            )
            assert len(bodies) == 1

            # node data by hash (fast-sync supplier path)
            root = bc.get_header_by_number(3).state_root
            nodes = peer.request(
                ETH_OFFSET + GET_NODE_DATA, [root], ETH_OFFSET + NODE_DATA
            )
            assert len(nodes) == 1
            assert keccak256(nodes[0]) == root
        finally:
            client.stop()
            server.stop()

    def test_genesis_mismatch_rejected(self):
        from khipu_tpu.network.messages import Status
        from khipu_tpu.network.peer import PeerError, PeerManager

        bc = make_chain(1)

        def status_a():
            return Status(63, 1, 1, b"\x01" * 32, b"\xaa" * 32)

        def status_b():
            return Status(63, 1, 1, b"\x01" * 32, b"\xbb" * 32)

        server = PeerManager(PRIV_B, "s", status_b)
        port = server.listen()
        client = PeerManager(PRIV_A, "c", status_a)
        try:
            with pytest.raises(PeerError, match="genesis"):
                client.connect("127.0.0.1", port, PUB_B)
        finally:
            client.stop()
            server.stop()


class TestDiscovery:
    def test_three_node_bootstrap(self):
        from khipu_tpu.network.discovery import DiscoveryService

        a = DiscoveryService((31).to_bytes(32, "big"))
        b = DiscoveryService((32).to_bytes(32, "big"))
        c = DiscoveryService((33).to_bytes(32, "big"))
        for s in (a, b, c):
            s.start()
        try:
            # b and c know each other; a bootstraps from b only
            b.table.add(c.record)
            found = a.bootstrap([b.record], timeout=2.0)
            assert found >= 2  # learned b via pong and c via neighbours
            pubs = {
                r.pubkey
                for bucket in a.table.buckets
                for r in bucket
            }
            assert b.pubkey in pubs and c.pubkey in pubs
        finally:
            for s in (a, b, c):
                s.stop()

    def test_packet_codec_and_tamper(self):
        from khipu_tpu.network.discovery import (
            decode_packet,
            encode_packet,
        )

        packet = encode_packet(PRIV_A, 1, [b"x"])
        pub, ptype, body = decode_packet(packet)
        assert pub == PUB_A and ptype == 1 and body == [b"x"]
        bad = packet[:40] + bytes([packet[40] ^ 1]) + packet[41:]
        with pytest.raises(ValueError):
            decode_packet(bad)

    def test_routing_table_eviction(self):
        from khipu_tpu.network.discovery import (
            K_BUCKET,
            KRoutingTable,
            NodeRecord,
        )

        table = KRoutingTable(PUB_A)
        for i in range(3 * K_BUCKET):
            table.add(
                NodeRecord(
                    privkey_to_pubkey((100 + i).to_bytes(32, "big")),
                    "127.0.0.1", 30000 + i, 30000 + i,
                )
            )
        assert all(len(b) <= K_BUCKET for b in table.buckets)
        closest = table.closest(keccak256(PUB_A), k=5)
        assert len(closest) == 5


class TestSnappyCompressor:
    """The C greedy compressor (rlp_ext.snappy_compress) must round-trip
    through our spec decompressor and actually compress; the all-literal
    fallback stays valid."""

    def test_roundtrip_and_ratio(self):
        import random

        from khipu_tpu.network.snappy_codec import (
            _compress_literal,
            compress,
            decompress,
        )

        rng = random.Random(9)
        cases = [
            b"", b"a", b"ab" * 3, b"x" * 100, b"hello world " * 500,
            rng.randbytes(1000),
            bytes(70000),
            (b"hdr" + bytes(40)) * 2000,
            rng.randbytes(200) * 300,
        ]
        for c in cases:
            assert decompress(compress(c), max_len=1 << 26) == c
            assert decompress(_compress_literal(c), max_len=1 << 26) == c
        for _ in range(100):
            blob = bytes(
                rng.choice(b"abcd") for _ in range(rng.randint(0, 3000))
            )
            assert decompress(compress(blob), max_len=1 << 26) == blob
        big = (b"repetitive-node-payload" + bytes(32)) * 5000
        z = compress(big)
        assert decompress(z, max_len=1 << 26) == big
        from khipu_tpu.native.build import load_rlp_ext

        if load_rlp_ext() is not None:
            assert len(z) < len(big) // 5, "compressor not compressing"

    def test_expansion_worst_case_no_overflow(self):
        """Regression: greedy emission can EXPAND (short literal runs +
        4-byte copies); the C buffer must use the snappy worst-case
        bound, not a per-64KiB slack — this shape overflowed a 4-bytes-
        per-region capacity and segfaulted."""
        import random

        from khipu_tpu.network.snappy_codec import compress, decompress

        rng = random.Random(1)
        parts = []
        for i in range(8000):
            parts.append(rng.randbytes(59))
            parts.append(i.to_bytes(2, "big"))
            parts.append(b"MARK")
        blob = b"".join(parts)
        assert decompress(compress(blob), max_len=1 << 26) == blob
