"""The l4 flow-record pool a traffic mix describes, and its wire bytes.

Imports neither JAX nor the program under test, so the load generator can
run in a process that never touches the chip.

- `Agent` copies the synthetic agent's column generation
  (`deepflow_tpu/replay/generator.py`, `SyntheticAgent.l4_columns` and
  `l4_columns_pooled`) draw for draw, so a seed gives the same records.
- `encode_records` writes the same bytes as `SyntheticAgent.l4_record`
  (one TaggedFlow protobuf per row, proto3 field order, zero scalars
  omitted, every sub-message on the path present), vectorized over rows.
- `Pool` holds a mix's records as one contiguous buffer of
  length-prefixed records, so any run of consecutive records is a valid
  frame payload (`| pb_len u32 LE | pb |`, as `wire/codec.py` packs).
"""

from __future__ import annotations

import struct
from typing import Dict, Sequence, Tuple

import numpy as np

MESSAGE_FRAME_SIZE_MAX = 512_000      # BaseHeader frame_size limit
HEADER_LEN = 5 + 14                   # BaseHeader + FlowHeader
TAGGEDFLOW = 4                        # message type id
FLOW_HEADER_VERSION = 20220117

_BASE = struct.Struct(">IB")
_FLOW = struct.Struct("<IQH")


def frame_header(payload_len: int, sequence: int, vtap_id: int) -> bytes:
    """BaseHeader + FlowHeader of one TAGGEDFLOW frame."""
    return (_BASE.pack(HEADER_LEN + payload_len, TAGGEDFLOW)
            + _FLOW.pack(FLOW_HEADER_VERSION, sequence, vtap_id))


class Agent:
    """Column generation of the synthetic agent, copied draw for draw."""

    def __init__(self, seed: int, vtap_id: int = 7, n_hosts: int = 4096,
                 n_services: int = 64, zipf_a: float = 1.25) -> None:
        self.vtap_id = vtap_id
        self.n_hosts = n_hosts
        self.n_services = n_services
        self.zipf_a = zipf_a
        self.rng = np.random.default_rng(seed)
        base = int.from_bytes(b"\x0a\x00\x00\x00", "big")
        self.client_ips = (base + self.rng.choice(
            1 << 20, n_hosts, replace=False)).astype(np.uint32)
        sbase = int.from_bytes(b"\xac\x10\x00\x00", "big")
        self.server_ips = (sbase + self.rng.choice(
            1 << 16, n_services, replace=False)).astype(np.uint32)
        self.server_ports = self.rng.choice(
            np.array([80, 443, 3306, 6379, 8080, 9092, 5432, 53], np.uint32),
            n_services)

    def l4_columns(self, n: int) -> Dict[str, np.ndarray]:
        r = self.rng
        svc = (r.zipf(self.zipf_a, n) - 1).clip(max=self.n_services - 1)
        cli = r.integers(0, self.n_hosts, n)
        return {
            "ip_src": self.client_ips[cli],
            "ip_dst": self.server_ips[svc],
            "port_src": r.integers(1024, 65536, n).astype(np.uint32),
            "port_dst": self.server_ports[svc].astype(np.uint32),
            "proto": np.where(r.random(n) < 0.9, 6, 17).astype(np.uint32),
            "vtap_id": np.full(n, self.vtap_id, np.uint32),
            "tap_side": r.integers(0, 3, n).astype(np.uint32),
            "byte_tx": r.lognormal(6.0, 1.5, n).astype(np.uint64),
            "byte_rx": r.lognormal(7.0, 1.5, n).astype(np.uint64),
            "packet_tx": r.integers(1, 64, n).astype(np.uint64),
            "packet_rx": r.integers(1, 64, n).astype(np.uint64),
            "l3_epc_id": r.integers(-2, 100, n).astype(np.int32),
            "start_time": (np.uint64(1_700_000_000_000_000_000)
                           + np.arange(n, dtype=np.uint64) * np.uint64(1000)),
            "duration": r.integers(10_000, 10_000_000_000, n).astype(np.uint64),
            "close_type": r.integers(0, 8, n).astype(np.uint32),
            "flow_id": np.arange(n, dtype=np.uint64) + np.uint64(1),
            "rtt": r.integers(100, 200_000, n).astype(np.uint32),
            "retrans": (r.random(n) < 0.02).astype(np.uint32)
            * r.integers(1, 5, n).astype(np.uint32),
            "mac_src": r.integers(0, 1 << 48, n).astype(np.uint64),
            "mac_dst": r.integers(0, 1 << 48, n).astype(np.uint64),
            "vlan": r.integers(0, 4096, n).astype(np.uint32),
            "tcp_flags_bit_0": r.integers(0, 256, n).astype(np.uint32),
            "tcp_flags_bit_1": r.integers(0, 256, n).astype(np.uint32),
            "syn_seq": r.integers(0, 1 << 32, n).astype(np.uint32),
            "synack_seq": r.integers(0, 1 << 32, n).astype(np.uint32),
            "l3_byte_tx": r.integers(0, 1 << 20, n).astype(np.uint32),
            "l3_byte_rx": r.integers(0, 1 << 20, n).astype(np.uint32),
            "total_packet_tx": r.integers(1, 128, n).astype(np.uint32),
            "total_packet_rx": r.integers(1, 128, n).astype(np.uint32),
            "rtt_client": r.integers(50, 100_000, n).astype(np.uint32),
            "rtt_server": r.integers(50, 100_000, n).astype(np.uint32),
            "retrans_tx": (r.random(n) < 0.02).astype(np.uint32),
            "retrans_rx": (r.random(n) < 0.02).astype(np.uint32),
            "l7_request": r.integers(0, 16, n).astype(np.uint32),
            "l7_response": r.integers(0, 16, n).astype(np.uint32),
            "direction_score": r.integers(0, 256, n).astype(np.uint32),
            "gprocess_id_0": r.integers(0, 1 << 16, n).astype(np.uint32),
            "gprocess_id_1": r.integers(0, 1 << 16, n).astype(np.uint32),
        }

    def l4_columns_pooled(self, n: int, pool: int) -> Dict[str, np.ndarray]:
        r = self.rng
        base = self.l4_columns(pool)
        pick = (r.zipf(self.zipf_a, n) - 1).clip(max=pool - 1)
        cols = {k: v[pick] for k, v in base.items()}
        cols["flow_id"] = np.arange(n, dtype=np.uint64) + np.uint64(1)
        cols["start_time"] = (np.uint64(1_700_000_000_000_000_000)
                              + np.arange(n, dtype=np.uint64) * np.uint64(1000))
        return cols


def mix_columns(mix: dict, seed: int) -> Dict[str, np.ndarray]:
    """The mix's record pool in send order: `fresh_share` of it fresh
    5-tuples (random source ports, Zipf services), the rest drawn with
    Zipf weights from a pool of `heavy_pool` flows, shuffled together."""
    n = int(mix["pool_records"])
    heavy = int(round(n * (1.0 - float(mix["fresh_share"]))))
    agent = Agent(seed, zipf_a=float(mix["zipf_a"]))
    parts = []
    if n - heavy:
        parts.append(agent.l4_columns(n - heavy))
    if heavy:
        parts.append(agent.l4_columns_pooled(heavy, pool=int(mix["heavy_pool"])))
    order = np.random.default_rng(seed).permutation(n)
    cols = {k: np.concatenate([p[k] for p in parts])[order] for k in parts[0]}
    cols["flow_id"] = np.arange(n, dtype=np.uint64) + np.uint64(1)
    return cols


# -- vectorized protobuf writer ---------------------------------------------

def _tag(field: int, wire_type: int) -> bytes:
    v, out = (field << 3) | wire_type, bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _varint_len(v: np.ndarray) -> np.ndarray:
    n = np.ones(v.shape, np.int64)
    top = int(v.max(initial=0))
    k = 1
    while top >> (7 * k):
        n += v >= np.uint64(1 << (7 * k))
        k += 1
    return n


class _Scalar:
    """A varint field; proto3 omits it where the value is 0."""

    def __init__(self, field: int, values: np.ndarray) -> None:
        v = np.asarray(values)
        if v.dtype.kind == "i":
            # int32/int64 negatives go out as 64-bit two's complement
            v = v.astype(np.int64).view(np.uint64)
        self.values = v.astype(np.uint64)
        self.tag = _tag(field, 0)
        self.present = self.values != 0
        self.vlen = _varint_len(self.values)
        self.length = np.where(self.present, len(self.tag) + self.vlen, 0)


class _Message:
    """A length-delimited sub-message, present on every row."""

    def __init__(self, field: int, parts: Sequence) -> None:
        self.tag = _tag(field, 2)
        self.parts = list(parts)
        self.body = sum(p.length for p in self.parts)
        self.blen = _varint_len(self.body.astype(np.uint64))
        self.length = len(self.tag) + self.blen + self.body


def _segments(part, out: list) -> None:
    """Flatten a field tree into (bytes (n, w), lengths (n,)) segments in
    wire order: tag and varint per field, length prefix per message."""
    def varint(v: np.ndarray, vlen: np.ndarray) -> np.ndarray:
        width = int(vlen.max(initial=1))
        j = np.arange(width, dtype=np.uint64)
        b = ((v[:, None] >> (np.uint64(7) * j)) & np.uint64(0x7F)).astype(np.uint8)
        b |= np.where(j[None, :] + 1 < vlen[:, None], 0x80, 0).astype(np.uint8)
        return b

    tag = np.frombuffer(part.tag, np.uint8)
    if isinstance(part, _Scalar):
        body = varint(part.values, part.vlen)
        out.append((np.concatenate(
            [np.broadcast_to(tag, (len(body), len(tag))), body], axis=1),
            part.length))
        return
    body = varint(part.body.astype(np.uint64), part.blen)
    out.append((np.concatenate(
        [np.broadcast_to(tag, (len(body), len(tag))), body], axis=1),
        len(tag) + part.blen))
    for p in part.parts:
        _segments(p, out)


def _tagged_flow(c: Dict[str, np.ndarray]) -> _Message:
    """The TaggedFlow of `SyntheticAgent.l4_record`, in field order."""
    def g(name: str) -> np.ndarray:
        return c[name] if name in c else np.zeros(len(c["ip_src"]), np.uint32)

    S = _Scalar
    key = _Message(1, [
        S(1, c["vtap_id"]), S(2, np.full(len(c["ip_src"]), 3, np.uint32)),
        S(4, g("mac_src")), S(5, g("mac_dst")), S(6, c["ip_src"]),
        S(7, c["ip_dst"]), S(10, c["port_src"]), S(11, c["port_dst"]),
        S(12, c["proto"])])
    src = _Message(2, [
        S(1, c["byte_tx"]), S(2, g("l3_byte_tx")), S(4, c["packet_tx"]),
        S(5, c["byte_tx"]), S(6, g("total_packet_tx")),
        S(9, g("tcp_flags_bit_0")), S(10, c["l3_epc_id"]),
        S(22, g("gprocess_id_0"))])
    dst = _Message(3, [
        S(1, c["byte_rx"]), S(2, g("l3_byte_rx")), S(4, c["packet_rx"]),
        S(5, c["byte_rx"]), S(6, g("total_packet_rx")),
        S(9, g("tcp_flags_bit_1")),
        S(10, c["l3_epc_id_1"] if "l3_epc_id_1" in c else c["l3_epc_id"]),
        S(22, g("gprocess_id_1"))])
    perf = (c["rtt"] != 0) | (c["retrans"] != 0)
    if not perf.all():
        raise ValueError("the encoder covers rows with perf stats only "
                         "(every generated row has rtt >= 100)")
    tcp = _Message(1, [
        S(1, g("rtt_client")), S(2, g("rtt_server")), S(5, c["rtt"]),
        _Message(14, [S(1, g("retrans_tx"))]),
        _Message(15, [S(1, g("retrans_rx"))]),
        S(16, c["retrans"])])
    l7 = _Message(2, [S(1, g("l7_request")), S(2, g("l7_response"))])
    perf_stats = _Message(13, [tcp, l7, S(3, np.ones(len(perf), np.uint32))])
    start = c["start_time"].astype(np.uint64)
    flow = _Message(1, [
        key, src, dst, S(5, c["flow_id"]), S(6, start),
        S(7, start + c["duration"].astype(np.uint64)), S(8, c["duration"]),
        S(10, g("vlan")), S(11, np.full(len(perf), 0x0800, np.uint32)),
        S(12, np.ones(len(perf), np.uint32)), perf_stats,
        S(14, c["close_type"]), S(18, np.ones(len(perf), np.uint32)),
        S(19, c["tap_side"]), S(20, g("syn_seq")), S(21, g("synack_seq")),
        S(25, g("direction_score"))])
    return flow


def _encode_chunk(c: Dict[str, np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
    flow = _tagged_flow(c)
    prefix = flow.length.astype("<u4").view(np.uint8).reshape(-1, 4)
    segs = [(prefix, np.full(len(prefix), 4, np.int64))]
    _segments(flow, segs)
    mat = np.concatenate([m for m, _ in segs], axis=1)
    keep = np.concatenate(
        [np.arange(m.shape[1])[None, :] < ln[:, None] for m, ln in segs],
        axis=1)
    return mat[keep], keep.sum(axis=1)


def encode_records(cols: Dict[str, np.ndarray], chunk: int = 1 << 15,
                   threads: int = 4) -> Tuple[np.ndarray, np.ndarray]:
    """(buffer, offsets): every row as `| len u32 LE | TaggedFlow |`,
    concatenated; row i spans buffer[offsets[i]:offsets[i + 1]].
    Chunks encode on a few threads (numpy releases the GIL)."""
    from concurrent.futures import ThreadPoolExecutor

    n = len(cols["ip_src"])
    chunks = [{k: v[s:s + chunk] for k, v in cols.items()}
              for s in range(0, n, chunk)]
    with ThreadPoolExecutor(max(1, threads)) as ex:
        done = list(ex.map(_encode_chunk, chunks))
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.concatenate([ln for _, ln in done]), out=offsets[1:])
    return np.concatenate([b for b, _ in done]), offsets


class Pool:
    """A mix's records encoded once, resent in order, cyclically. The
    records of one send are consecutive in the pool, so a frame payload
    is a slice of one buffer: nothing is copied per send."""

    def __init__(self, cols: Dict[str, np.ndarray], per_frame: int) -> None:
        self.buf, self.offsets = encode_records(cols)
        self.n = len(self.offsets) - 1
        self.per_frame = int(per_frame)
        longest = int(np.diff(self.offsets).max())
        if HEADER_LEN + self.per_frame * longest > MESSAGE_FRAME_SIZE_MAX:
            raise ValueError(
                f"{self.per_frame} records of up to {longest} B overflow "
                f"a {MESSAGE_FRAME_SIZE_MAX} B frame")
        self.view = memoryview(self.buf)

    def payload(self, start: int, count: int) -> memoryview:
        """Records [start, start + count) of one pass (no wrap)."""
        return self.view[self.offsets[start]:self.offsets[start + count]]
