"""flow_log pipeline: TAGGEDFLOW/PROTOCOLLOG frames -> enriched columns.

Reference: server/ingester/flow_log/flow_log.go (per-type Loggers, N
decoder threads per queue) + decoder/decoder.go (Gets(1024) batches,
decode by type, PlatformInfoTable enrichment, throttling, CH write,
exporter fan-out :299). Columnar re-design: a decoder thread drains whole
frames, decodes each frame's record batch straight into schema columns,
stamps KnowledgeGraph tags with one vectorized join, and hands the same
chunk to the store writer and every exporter.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

import numpy as np

from deepflow_tpu.decode import columnar
from deepflow_tpu.enrich.platform_data import PlatformDataManager
from deepflow_tpu.pipelines.schemas import (L4_PACKET_TABLE, L4_TABLE,
                                            L7_TABLE)
from deepflow_tpu.runtime.exporters import Exporters
from deepflow_tpu.runtime.queues import MultiQueue
from deepflow_tpu.runtime.receiver import Receiver
from deepflow_tpu.runtime.stats import StatsRegistry
from deepflow_tpu.runtime.throttler import ColumnarThrottler
from deepflow_tpu.runtime.tracing import default_tracer
from deepflow_tpu.store.db import Store
from deepflow_tpu.store.writer import StoreWriter
from deepflow_tpu.wire.codec import iter_pb_records
from deepflow_tpu.wire.framing import Frame, MessageType

# row-id generator (reference: l4_flow_log.go genID :1040 —
# time<<32 | analyzer<<22 | counter, the counter a process-wide atomic).
# The GIL makes the locked window tiny; ids are unique per process.
_ID_LOCK = threading.Lock()
_ID_NEXT = [1]


def stamp_row_ids(cols: Dict[str, np.ndarray],
                  analyzer_id: int = 0) -> Dict[str, np.ndarray]:
    """Fill the `_id` column in-place for rows that lack one."""
    ids = cols.get("_id")
    n = 0 if ids is None else len(ids)
    if n == 0:
        return cols
    with _ID_LOCK:
        start = _ID_NEXT[0]
        _ID_NEXT[0] += n
    count = (np.arange(start, start + n, dtype=np.uint64)
             & np.uint64(0x3FFFFF))
    ts = cols["timestamp"].astype(np.uint64)
    cols["_id"] = (ts << np.uint64(32)) \
        | np.uint64((analyzer_id & 0x3FF) << 22) | count
    return cols

FLOW_LOG_DB = "flow_log"


class _Decoder:
    """One decoder worker for one stream type (reference: decoder.go Run).

    A plain run() loop, not a Thread: the pipeline spawns it through
    the process Supervisor (runtime/supervisor.py), so an unexpected
    crash (decode handles its own known failure shapes below) is
    captured with its traceback and the worker restarts with backoff
    instead of silently going dark."""

    def __init__(self, stream: str, index: int, queues: MultiQueue,
                 decode_fn, enrich_fn,
                 throttler: Optional[ColumnarThrottler],
                 writer: Optional[StoreWriter], exporters: Optional[Exporters],
                 batch: int = 64, payload_decode_fns=None,
                 frame_mode: bool = False) -> None:
        self.name = f"decode-{stream}-{index}"
        self.stream = stream
        self.index = index
        self.queues = queues
        self.decode_fn = decode_fn
        # per-message-type payload fast paths ({MessageType: payload->cols}):
        # the native protobuf walker for TAGGEDFLOW, the planar memcpy
        # decode for COLUMNAR_FLOW; frames without an entry fall back to
        # the Python record-list decoder
        self.payload_decode_fns = payload_decode_fns or {}
        # frame_mode: decode_fn consumes whole frames (msg_type, payload)
        # instead of length-prefixed record lists (the OTel case —
        # one frame = one ExportTraceServiceRequest)
        self.frame_mode = frame_mode
        self.enrich_fn = enrich_fn
        self.throttler = throttler
        self.writer = writer
        self.exporters = exporters
        self.batch = batch
        self._halt = threading.Event()
        self.frames = 0
        self.records = 0
        self.decode_errors = 0
        self._tracer = default_tracer()

    def run(self) -> None:
        from deepflow_tpu.runtime.supervisor import default_supervisor

        sup = default_supervisor()
        while not self._halt.is_set():
            sup.beat()
            frames: List[Frame] = self.queues.gets(self.index, self.batch,
                                                   timeout=0.2)
            if not frames:
                if self.queues.queues[self.index].closed:
                    return
                continue
            self.handle(frames)

    def handle(self, frames: List[Frame]) -> None:
        tracer = self._tracer
        if tracer.enabled:
            # the chunk anchors to its FIRST frame's receiver-stamped
            # batch id (batch causality receiver -> decode -> export);
            # frames received before tracing was enabled get a fresh id.
            bid = getattr(frames[0], "trace_batch_id", 0) or \
                tracer.next_batch()
            tracer.set_batch(bid)
            before = self.records
            with tracer.span("decode", stream=self.stream,
                             batch_id=bid) as sp:
                self._handle_inner(frames)
                sp.rows = self.records - before
        else:
            self._handle_inner(frames)

    def _handle_inner(self, frames: List[Frame]) -> None:
        self.frames += len(frames)
        if self.frame_mode:
            try:
                cols, bad = self.decode_fn(frames)
                self.decode_errors += bad
            except Exception:
                self.decode_errors += len(frames)
                return
            # falls through to the shared enrich/export/throttle tail
        else:
            # fast paths decode per frame (not one joined buffer) so a
            # corrupt frame only loses its own tail, like the Python path;
            # frames without a fast path pool into one record-list decode
            parts: List[Dict[str, np.ndarray]] = []
            records: List[bytes] = []
            for f in frames:
                fast = self.payload_decode_fns.get(f.msg_type)
                if fast is not None:
                    try:
                        c, bad = fast(f.payload)
                        self.decode_errors += bad
                        if len(next(iter(c.values()))):
                            parts.append(c)
                        continue
                    except Exception:
                        pass  # fall through to the Python oracle
                try:
                    records.extend(iter_pb_records(f.payload))
                except ValueError:
                    self.decode_errors += 1
            if records:
                try:
                    c = self.decode_fn(records)
                    self.decode_errors += len(records) - \
                        len(next(iter(c.values())))  # bad records skipped
                    if len(next(iter(c.values()))):
                        parts.append(c)
                except Exception:
                    self.decode_errors += 1
            if not parts:
                return
            cols = parts[0] if len(parts) == 1 else \
                {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        decoded = len(next(iter(cols.values()))) if cols else 0
        self.records += decoded
        if decoded == 0:
            return
        cols = self.enrich_fn(cols)
        # exporters see the full (unthrottled) stream, like the reference's
        # export() running before the CH-write throttler
        if self.exporters is not None:
            self.exporters.put(self.stream, self.index, cols)
        if self.writer is not None:
            if self.throttler is not None:
                self.throttler.offer(cols)
            else:
                # unthrottled stream (diagnosis data): straight to the
                # writer — a reservoir sized "never drop" would have to
                # preallocate its whole capacity
                self.writer.put(cols)

    def stop(self) -> None:
        self._halt.set()
        if self.throttler is not None:
            self.throttler.flush()  # drain the open throttle bucket

    def counters(self) -> dict:
        return {"frames": self.frames, "records": self.records,
                "decode_errors": self.decode_errors}


class FlowLogPipeline:
    """L4 + L7 loggers: registry of queues, decoder fleets, store writers."""

    def __init__(self, receiver: Receiver, store: Optional[Store],
                 platform: PlatformDataManager,
                 exporters: Optional[Exporters] = None,
                 n_decoders: int = 2, queue_size: int = 16384,
                 throttle_per_s: int = 50_000,
                 stats: Optional[StatsRegistry] = None,
                 tag_dicts=None, analyzer_id: int = 0) -> None:
        self.decoders: List[_Decoder] = []
        self.writers: List[StoreWriter] = []
        self._streams = []
        endpoint_dict = None if tag_dicts is None \
            else tag_dicts.get("l7_endpoint")

        def decode_l7(records):
            return columnar.decode_l7_records(records,
                                              endpoint_dict=endpoint_dict)

        def _with_ids(enrich):
            return lambda cols: stamp_row_ids(enrich(cols),
                                              analyzer_id=analyzer_id)

        for stream, msg_type, table_schema, decode_fn, enrich_fn in (
            ("l4_flow_log", MessageType.TAGGEDFLOW, L4_TABLE,
             columnar.decode_l4_records, _with_ids(platform.stamp_l4)),
            ("l7_flow_log", MessageType.PROTOCOLLOG, L7_TABLE,
             decode_l7, _with_ids(platform.stamp_l7)),
        ):
            queues = MultiQueue(f"ingest.{stream}", n_decoders, queue_size)
            queues.trace_dwell(default_tracer(), f"queue.ingest.{stream}")
            receiver.register_handler(msg_type, queues)
            writer = None
            if store is not None:
                table = store.create_table(FLOW_LOG_DB, table_schema)
                writer = StoreWriter(table, stats=stats)
                self.writers.append(writer)
            payload_fns = {}
            if stream == "l4_flow_log":
                # planar frames from deepflow_tpu agents ride the same
                # queues/decoders as protobuf TAGGEDFLOW from reference
                # agents; the decode fast path is picked per frame
                from deepflow_tpu.wire import columnar_wire
                receiver.register_handler(MessageType.COLUMNAR_FLOW, queues)
                payload_fns[MessageType.COLUMNAR_FLOW] = \
                    columnar_wire.decode_columnar
                from deepflow_tpu.decode import native
                if native.available():
                    payload_fns[MessageType.TAGGEDFLOW] = \
                        native.decode_l4_payload
                else:
                    import logging
                    logging.getLogger(__name__).warning(
                        "native l4 decoder unavailable, decoding in "
                        "Python: %s", native.build_error())
            # budget split across every consumer of the stream's writer so
            # the aggregate cap matches the config (reference: flow_log.go
            # throttle/queueCount); the l7 table is also fed by the OTel
            # decoder, so its budget splits one way further
            n_consumers = n_decoders + (1 if stream == "l7_flow_log" else 0)
            for i in range(n_decoders):
                throttler = ColumnarThrottler(
                    (writer.put if writer is not None else lambda c: None),
                    max(1, throttle_per_s // n_consumers), seed=i)
                d = _Decoder(stream, i, queues, decode_fn, enrich_fn,
                             throttler, writer, exporters,
                             payload_decode_fns=payload_fns)
                self.decoders.append(d)
                if stats is not None:
                    stats.register(f"decoder.{stream}.{i}", d.counters)
            self._streams.append((stream, queues))

        if stats is not None:
            # process-wide string-hash LRU shared by every decoder
            # (decode/columnar.py, ISSUE 9) — one registration, not one
            # per decoder thread
            stats.register("decode.hash_cache",
                           columnar.hash_cache_counters)

        # OTel spans: raw + zlib-compressed frames land in l7_flow_log too
        # (reference: flow_log.go OTel+compressed Loggers :99-106)
        def _decode_otel(frames: List[Frame]):
            # per-frame decode so each span batch carries its sender's
            # vtap_id from the flow header (reference stamps VtapID the
            # same way)
            parts, bad = [], 0
            for f in frames:
                c, b = columnar.decode_otel_frames(
                    [f.payload],
                    compressed=(f.msg_type
                                == MessageType.OPENTELEMETRY_COMPRESSED),
                    vtap_id=(f.flow_header.vtap_id if f.flow_header
                             else 0),
                    endpoint_dict=endpoint_dict)
                bad += b
                if len(next(iter(c.values()))):
                    parts.append(c)
            if not parts:
                return columnar.decode_otel_frames([])[0], bad
            return ({k: np.concatenate([p[k] for p in parts])
                     for k in parts[0]}, bad)

        otel_queues = MultiQueue("ingest.otel", 1, queue_size)
        receiver.register_handler(MessageType.OPENTELEMETRY, otel_queues)
        receiver.register_handler(MessageType.OPENTELEMETRY_COMPRESSED,
                                  otel_queues)
        l7_writer = next(
            (w for w in self.writers
             if w.table.schema.name == "l7_flow_log"), None)
        # stream name distinguishes signal source: exporters that match
        # "l7_flow_log" (e.g. the OTLP exporter) must NOT re-export spans
        # that arrived via OTLP — the reference filters by SignalSource
        # bits for the same reason (otlp_exporter IsExportData)
        # OTel rows get the same KnowledgeGraph stamping as PROTOCOLLOG l7
        # rows (reference: decoder.go ProtoLogToL7FlowLog for both sources)
        otel_decoder = _Decoder(
            "l7_flow_log.otel", 0, otel_queues, _decode_otel,
            _with_ids(platform.stamp_l7),
            # the l7 write budget is shared with the PROTOCOLLOG decoders
            # (all feed the same table), so every consumer gets an equal
            # slice of the configured cap
            ColumnarThrottler(
                (l7_writer.put if l7_writer is not None else lambda c: None),
                max(1, throttle_per_s // (n_decoders + 1)),
                seed=n_decoders),
            l7_writer, exporters, frame_mode=True)
        self.decoders.append(otel_decoder)
        self._streams.append(("otel", otel_queues))
        if stats is not None:
            stats.register("decoder.otel.0", otel_decoder.counters)

        # -- l4_packet logger (PACKETSEQUENCE): per-packet TCP headers
        # batched per flow (reference flow_log.go L4Packet logger :107,
        # l4_packet.go DecodePacketSequence). Metadata rows land in the
        # l4_packet table; the opaque batch bytes append to a sidecar
        # blob addressed by (batch_off, batch_len).
        from deepflow_tpu.agent.packet_sequence import decode_blocks

        pseq_writer = None
        self._pseq_table = None
        self._pseq_blob = None          # (partition_start, open file)
        if store is not None:
            pseq_table = store.create_table(FLOW_LOG_DB, L4_PACKET_TABLE)
            pseq_writer = StoreWriter(pseq_table, stats=stats)
            self.writers.append(pseq_writer)
            os.makedirs(pseq_table.root, exist_ok=True)
            self._pseq_table = pseq_table

        def _pseq_blob_for(part: int):
            """Blob files segment per table partition (batches-p<start>)
            so TTL/GC expiry of a partition's rows prunes its batch
            bytes too; the reader derives the file from the row's
            timestamp. One handle stays open (frames are time-ordered)."""
            if self._pseq_blob is not None and self._pseq_blob[0] == part:
                return self._pseq_blob[1]
            if self._pseq_blob is not None:
                self._pseq_blob[1].close()
            f = open(os.path.join(self._pseq_table.root,
                                  f"batches-p{part}.bin"), "ab")
            self._pseq_blob = (part, f)
            return f

        def _decode_pseq(frames: List[Frame]):
            rows, bad = [], 0
            for f in frames:
                r, b = decode_blocks(
                    f.payload,
                    vtap_id=(f.flow_header.vtap_id if f.flow_header
                             else 0))
                rows.extend(r)
                bad += b
            n = len(rows)
            cols = {
                "timestamp": np.fromiter(
                    (r["end_time_us"] // 1_000_000 for r in rows),
                    np.uint32, n),
                "start_time_us": np.fromiter(
                    (r["start_time_us"] for r in rows), np.uint64, n),
                "end_time_us": np.fromiter(
                    (r["end_time_us"] for r in rows), np.uint64, n),
                "flow_id": np.fromiter(
                    (r["flow_id"] for r in rows), np.uint64, n),
                "vtap_id": np.fromiter(
                    (r["vtap_id"] for r in rows), np.uint32, n),
                "packet_count": np.fromiter(
                    (r["packet_count"] for r in rows), np.uint32, n),
                "batch_off": np.zeros(n, np.uint64),
                "batch_len": np.fromiter(
                    (len(r["batch"]) for r in rows), np.uint32, n),
            }
            if self._pseq_table is not None and n:
                psec = self._pseq_table.schema.partition_seconds
                offs = []
                for i, r in enumerate(rows):
                    part = int(cols["timestamp"][i]) // psec * psec
                    fh = _pseq_blob_for(part)
                    offs.append(fh.tell())
                    fh.write(r["batch"])
                self._pseq_blob[1].flush()
                cols["batch_off"] = np.asarray(offs, np.uint64)
            return cols, bad

        pseq_queues = MultiQueue("ingest.l4_packet", 1, queue_size)
        receiver.register_handler(MessageType.PACKETSEQUENCE, pseq_queues)
        pseq_decoder = _Decoder(
            "l4_packet", 0, pseq_queues, _decode_pseq,
            lambda cols: cols,   # bare rows: no KnowledgeGraph
            # diagnosis data is never throttled (reference: the L4Packet
            # logger writes straight through); None = direct writer.put
            None,
            pseq_writer, exporters, frame_mode=True)
        self.decoders.append(pseq_decoder)
        self._streams.append(("l4_packet", pseq_queues))
        if stats is not None:
            stats.register("decoder.l4_packet.0", pseq_decoder.counters)

    def start(self) -> None:
        from deepflow_tpu.runtime.supervisor import default_supervisor

        for w in self.writers:
            w.start()
        sup = default_supervisor()
        self._handles = [sup.spawn(d.name, d.run) for d in self.decoders]

    def flush(self) -> None:
        """Drain open throttle buckets and pending writer rows to disk."""
        for d in self.decoders:
            if d.throttler is not None:
                d.throttler.flush()
        for w in self.writers:
            w.flush()
        self._prune_pseq_blobs()

    def tick(self) -> None:
        """Wall-clock throttle-bucket roll: without it, a stream that
        goes quiet strands its last bucket in the reservoir until the
        NEXT record arrives (possibly never) — the writer's 10s flush
        timer can't see rows the throttler hasn't released."""
        for d in self.decoders:
            if d.throttler is not None:
                d.throttler.tick()

    def _prune_pseq_blobs(self) -> None:
        """Remove batch blob files whose table partition has expired
        (TTL/GC drop the rows; the bytes must follow). Only partitions
        comfortably in the past are candidates: a blob for a BRAND-NEW
        partition exists momentarily before its rows flush to the table
        (decoder writes bytes first), and deleting it in that window
        would strand the rows' offsets."""
        import time as _time

        t = self._pseq_table
        if t is None:
            return
        live = set(t.partitions())
        cur = self._pseq_blob[0] if self._pseq_blob is not None else None
        # grace on the blob file's WALL-CLOCK mtime: the write→row-flush
        # lag is wall-clock, while partition stamps are DATA time — a
        # replayed historical pcap writes "old" partitions whose rows
        # are still in flight (a data-time grace would delete them)
        mtime_horizon = _time.time() - 120.0
        try:
            names = os.listdir(t.root)
        except OSError:
            return
        for name in names:
            if not (name.startswith("batches-p")
                    and name.endswith(".bin")):
                continue
            try:
                part = int(name[len("batches-p"):-len(".bin")])
            except ValueError:
                continue
            path = os.path.join(t.root, name)
            try:
                recent = os.path.getmtime(path) > mtime_horizon
            except OSError:
                continue
            if part not in live and part != cur and not recent:
                try:
                    os.remove(path)
                except OSError:
                    pass

    def close(self) -> None:
        for _, queues in self._streams:
            queues.close()
        for d in self.decoders:
            d.stop()
        for h in getattr(self, "_handles", ()):
            h.stop()
            h.join(timeout=2)
        for w in self.writers:
            w.close()
        if self._pseq_blob is not None:
            self._pseq_blob[1].close()
            self._pseq_blob = None
