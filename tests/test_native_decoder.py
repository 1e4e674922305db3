"""Native C++ decoder: parity with the Python oracle + robustness."""

import numpy as np
import pytest

from deepflow_tpu.decode import columnar, native
from deepflow_tpu.utils import native_build
from deepflow_tpu.replay.generator import SyntheticAgent
from deepflow_tpu.wire.codec import pack_pb_records

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason=f"native decoder unavailable: {native.build_error()}")


def test_binary_name_is_derived_from_source_flags_and_cpu(tmp_path):
    """The .so is named by a hash of what it depends on: a change to
    the source, the compiler flags or the host CPU gives another name,
    so a binary built elsewhere is never picked up."""
    key = native.build_key()
    assert native._LIB.path().endswith(f"_native_decoder-{key}.so")
    assert native_build.build_key(native._SRC,
                                  cpu=native_build.cpu_flags()) == key
    other_src = tmp_path / "decoder.cc"
    other_src.write_bytes(open(native._SRC, "rb").read() + b"\n")
    assert native_build.build_key(str(other_src)) != key
    assert native_build.build_key(
        native._SRC, native_build.CXXFLAGS + ("-DX",)) != key
    assert native._LIB.key(cpu="x86_64:sse2") != key
    assert key in native._LIB.path(key)


def test_stale_binary_from_another_build_is_not_loaded(tmp_path,
                                                       monkeypatch):
    """Binaries under other keys (another source or flags) and under
    the old unkeyed name, however fresh their timestamps, are never
    loaded: this host builds and loads its own keyed binary."""
    monkeypatch.setenv("DEEPFLOW_TPU_NATIVE_DIR", str(tmp_path))
    stale = [tmp_path / "_native_decoder.so",
             tmp_path / f"_native_decoder-{native._LIB.key(cpu='arm')}.so",
             tmp_path / "_native_decoder-{}.so".format(
                 native_build.build_key(native._SRC, flags=("-O0",)))]
    for p in stale:
        p.write_bytes(b"not an ELF")
    fresh = native_build.NativeLib(native._SRC, "_native_decoder",
                                   native._bind, libs=("-lpthread",))
    monkeypatch.setattr(native, "_LIB", fresh)
    assert native.available(), native.build_error()
    assert fresh.path() not in {str(p) for p in stale}
    assert fresh.path().startswith(str(tmp_path))
    assert all(p.read_bytes() == b"not an ELF" for p in stale)
    got, bad = native.decode_l4_payload(
        pack_pb_records(SyntheticAgent().l4_batch(5)[1]))
    assert bad == 0 and len(got["ip_src"]) == 5


def test_parity_with_python_decoder():
    agent = SyntheticAgent()
    _, records = agent.l4_batch(500)
    want = columnar.decode_l4_records(records)
    got, bad = native.decode_l4_payload(pack_pb_records(records))
    assert bad == 0
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def test_capacity_chunking():
    agent = SyntheticAgent()
    _, records = agent.l4_batch(300)
    got, bad = native.decode_l4_payload(pack_pb_records(records),
                                        capacity=64)
    assert bad == 0
    assert len(got["ip_src"]) == 300
    want = columnar.decode_l4_records(records)
    np.testing.assert_array_equal(got["byte_tx"], want["byte_tx"])


def test_bad_records_skipped():
    agent = SyntheticAgent()
    _, records = agent.l4_batch(10)
    records[3] = b"\xff\xff\xff garbage"
    got, bad = native.decode_l4_payload(pack_pb_records(records))
    assert bad == 1
    assert len(got["ip_src"]) == 9


def test_truncated_payload():
    agent = SyntheticAgent()
    _, records = agent.l4_batch(5)
    payload = pack_pb_records(records)
    got, bad = native.decode_l4_payload(payload[:-7])
    assert bad == 1
    assert len(got["ip_src"]) == 4


def test_empty_payload():
    got, bad = native.decode_l4_payload(b"")
    assert bad == 0 and len(got["ip_src"]) == 0


def test_v6_fold_agrees_across_paths():
    """Capture, the Python wire decoder, and the C++ decoder must all
    produce the SAME class-E-confined u32 for one v6 address."""
    import struct

    import numpy as np

    from deepflow_tpu.agent.packet import decode_packets
    from deepflow_tpu.store.dict_store import fold_ipv6

    src16 = bytes(range(100, 116))
    dst16 = bytes(range(116, 132))
    tcp = struct.pack(">HHIIBBHHH", 443, 55000, 7, 0, 0x50, 0x10,
                      8192, 0, 0)
    ip6 = struct.pack(">IHBB", 0x60000000, len(tcp), 6, 64) \
        + src16 + dst16
    frame = b"\x02" * 6 + b"\x04" * 6 + b"\x86\xdd" + ip6 + tcp
    cap = decode_packets([frame])
    assert cap["ip_src"][0] == fold_ipv6(src16)

    from deepflow_tpu.decode import native
    from deepflow_tpu.decode.columnar import decode_l4_records
    from deepflow_tpu.wire.codec import pack_pb_records
    from deepflow_tpu.wire.gen import flow_log_pb2

    d = flow_log_pb2.TaggedFlow()
    d.flow.flow_key.ip6_src = src16
    d.flow.flow_key.ip6_dst = dst16
    d.flow.flow_key.port_src = 443
    d.flow.flow_key.port_dst = 55000
    rec = d.SerializeToString()
    py = decode_l4_records([rec])
    assert py["ip_src"][0] == fold_ipv6(src16)
    assert py["ip_dst"][0] == fold_ipv6(dst16)
    if native.available():
        payload = pack_pb_records([rec])
        n32 = len(native.L4_COLS32)
        n64 = len(native.L4_COLS64)
        buf32 = np.empty((n32, 8), np.uint32)
        buf64 = np.empty((n64, 8), np.uint64)
        rows, bad, _ = native.decode_l4_into(payload, buf32, buf64)
        assert rows == 1
        names32 = [n for n, _ in native.L4_COLS32]
        assert buf32[names32.index("ip_src"), 0] == fold_ipv6(src16)
        assert buf32[names32.index("ip_dst"), 0] == fold_ipv6(dst16)


def test_round3_column_goldens():
    """New round-3 columns: tunnel MACs, acl_gids, derived status /
    retrans_syn[ack] / l7_error — exact values through BOTH decoders
    (the reference derivations: l4_flow_log.go :857 getStatus, :960
    handshake retrans, :926 l7_error)."""
    from deepflow_tpu.wire.gen import flow_log_pb2

    def rec(close_type, proto, syn=0, synack=0, gids=(),
            cli_err=0, srv_err=0):
        m = flow_log_pb2.TaggedFlow()
        f = m.flow
        f.flow_key.proto = proto
        f.flow_key.ip_src = 1
        f.flow_key.ip_dst = 2
        f.close_type = close_type
        f.start_time = 1_000_000_000
        f.end_time = 2_000_000_000
        t = f.tunnel
        t.tx_mac0, t.tx_mac1 = 0x0000AABB, 0xCCDDEEFF
        t.rx_mac0, t.rx_mac1 = 0x00001122, 0x33445566
        f.acl_gids.extend(gids)
        if syn or synack or cli_err or srv_err:
            f.has_perf_stats = 1
            f.perf_stats.tcp.syn_count = syn
            f.perf_stats.tcp.synack_count = synack
            f.perf_stats.l7.err_client_count = cli_err
            f.perf_stats.l7.err_server_count = srv_err
        return m.SerializeToString()

    records = [
        rec(1, 6, syn=3, synack=2, gids=(7, 9)),   # FIN -> status 0
        rec(3, 6),                                 # TCP timeout -> 3
        rec(3, 17),                                # UDP timeout -> 0
        rec(2, 6, cli_err=2, srv_err=5),           # RST -> 3
    ]
    want = columnar.decode_l4_records(records)
    got, bad = native.decode_l4_payload(pack_pb_records(records))
    assert bad == 0
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    assert got["status"].tolist() == [0, 3, 0, 3]
    assert got["retrans_syn"].tolist() == [2, 0, 0, 0]
    assert got["retrans_synack"].tolist() == [1, 0, 0, 0]
    assert got["acl_gids"].tolist() == [7, 0, 0, 0]
    assert got["l7_error"].tolist() == [0, 0, 0, 7]
    assert got["tunnel_tx_mac"].tolist() == [0x0000AABBCCDDEEFF] * 4
    assert got["tunnel_rx_mac"].tolist() == [0x0000112233445566] * 4


def test_fuzz_hostile_payloads_never_crash():
    """Deterministic fuzz: random bytes, truncated/corrupted real
    records, and pathological length prefixes must never crash the C++
    walker or overrun buffers (bad counts rise instead), across both
    ST and MT paths, matching the python oracle's row count."""
    rng = np.random.default_rng(0xFADE)
    agent = SyntheticAgent()
    _, real = agent.l4_batch(64)
    payloads = []
    # pure garbage
    for n in (0, 1, 3, 4, 5, 64, 4096):
        payloads.append(rng.bytes(n))
    # length prefix pointing past the end
    payloads.append((1 << 20).to_bytes(4, "little") + b"x" * 32)
    # real records with random corruption
    for _ in range(20):
        recs = list(real)
        for _ in range(8):
            i = int(rng.integers(0, len(recs)))
            b = bytearray(recs[i])
            if len(b):
                j = int(rng.integers(0, len(b)))
                b[j] = int(rng.integers(0, 256))
            recs[i] = bytes(b)
        payloads.append(pack_pb_records(recs))
    # truncations of a valid payload
    whole = pack_pb_records(real)
    for cut in (1, 7, len(whole) // 3, len(whole) - 1):
        payloads.append(whole[:cut])

    for payload in payloads:
        for threads in (1, 4):
            got, bad = native.decode_l4_payload(payload,
                                                n_threads=threads)
            rows = len(got["ip_src"])
            assert rows + bad >= 0           # no crash is the real assert
            # oracle agreement on well-formed-record COUNT: the python
            # decoder skips exactly the records the walker rejects,
            # except byte-corrupted ones that remain valid protobuf
            # with unknown fields — so only assert bounds
            assert rows <= 64


def test_pipelined_decoder_matches_serial():
    """PipelinedDecoder (feeder-thread overlap) yields byte-identical
    column data to serial decode_l4_into across a payload stream long
    enough to cycle every ring slot several times, and consuming slowly
    never lets the feeder overwrite a buffer still held."""
    agent = SyntheticAgent()
    base = agent.l4_columns(512)
    recs = [agent.l4_record(base, i) for i in range(512)]
    payloads = [pack_pb_records(recs[i::8]) for i in range(8)] * 3
    n32, n64 = len(native.L4_COLS32), len(native.L4_COLS64)
    want = []
    b32 = np.empty((n32, 64), np.uint32)
    b64 = np.empty((n64, 64), np.uint64)
    for p in payloads:
        rows, bad, _ = native.decode_l4_into(p, b32, b64)
        assert bad == 0
        want.append((rows, b32[:, :rows].copy(), b64[:, :rows].copy()))

    dec = native.PipelinedDecoder(capacity=64, n_bufs=3)
    got_n = 0
    import time as _t
    for (rows, g32, g64), (wr, w32, w64) in zip(
            dec.stream(iter(payloads)), want):
        _t.sleep(0.002)      # slow consumer: feeder runs ahead, must
        assert rows == wr    # still respect the ring discipline
        np.testing.assert_array_equal(g32[:, :rows], w32)
        np.testing.assert_array_equal(g64[:, :rows], w64)
        got_n += 1
    assert got_n == len(payloads)


def test_pipelined_decoder_propagates_feeder_errors():
    dec = native.PipelinedDecoder(capacity=64)

    def gen():
        yield b"\x00\x01ok-this-will-decode-to-nothing"
        raise RuntimeError("payload source exploded")

    with pytest.raises(RuntimeError, match="payload source exploded"):
        for _ in dec.stream(gen()):
            pass


def test_pipelined_decoder_reusable_after_abort_and_error():
    """An early consumer break or a feeder error must not poison the
    NEXT stream on the same decoder (per-call queues + stop flag)."""
    agent = SyntheticAgent()
    base = agent.l4_columns(128)
    recs = [agent.l4_record(base, i) for i in range(128)]
    payloads = [pack_pb_records(recs[i::4]) for i in range(4)]
    dec = native.PipelinedDecoder(capacity=128, n_bufs=2)
    # 1) abort mid-stream
    for n, _ in enumerate(dec.stream(iter(payloads))):
        if n == 1:
            break
    # 2) feeder error mid-stream
    def gen():
        yield payloads[0]
        raise RuntimeError("boom")
    with pytest.raises(RuntimeError):
        for _ in dec.stream(gen()):
            pass
    # 3) a fresh stream still yields every payload with correct counts
    got = [rows for rows, _, _ in dec.stream(iter(payloads))]
    assert got == [32, 32, 32, 32]
