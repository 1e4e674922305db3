"""The tpu_sketch exporter: the framework's flagship analytics backend.

This is the component BASELINE.json names: an exporter registered behind
the ingester's plugin interface (beside the store/OTLP-style writers)
that batches decoded l4_flow_log chunks into static-shape device tensors
and advances the FlowSuite sketches (Count-Min top-K, per-service HLL,
traffic entropy) in one jitted program per batch. Window flushes write
heavy-hitter/cardinality/entropy rows into the store for the querier,
and checkpoint the mergeable sketch state so a restart loses at most
`checkpoint_every` windows (default 1; idle windows are skipped)
(SURVEY.md §5 checkpoint/resume).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from deepflow_tpu.batch.batcher import Batcher, TensorBatch
from deepflow_tpu.batch.schema import SKETCH_L4_SCHEMA
from deepflow_tpu.models import flow_suite
from deepflow_tpu.runtime.snapbus import SnapshotBus
from deepflow_tpu.runtime.exporters import QueueWorkerExporter
from deepflow_tpu.runtime.faults import FAULT_DEVICE_ERROR, default_faults
from deepflow_tpu.runtime.stats import StatsRegistry
from deepflow_tpu.runtime.supervisor import default_supervisor
from deepflow_tpu.runtime.tracing import default_tracer
from deepflow_tpu.store.db import Store
from deepflow_tpu.store.table import AggKind, ColumnSpec, TableSchema
from deepflow_tpu.store.writer import StoreWriter

SKETCH_DB = "tpu_sketch"

TOPK_TABLE = TableSchema(
    name="topk_flows",
    columns=(
        ColumnSpec("timestamp", np.dtype(np.uint32), AggKind.KEY),
        ColumnSpec("rank", np.dtype(np.uint32), AggKind.KEY),
        ColumnSpec("flow_key", np.dtype(np.uint32), AggKind.KEY),
        ColumnSpec("count", np.dtype(np.uint32), AggKind.MAX),
        # the 5-tuple behind the key, resolved host-side via the
        # sampled reverse map (0 when the key was never sampled) — the
        # universal-tag role: top-K output a human can read
        # (SURVEY §7 Phase 5 (5); reference:
        # exporters/universal_tag/universal_tag.go QueryUniversalTags)
        ColumnSpec("ip_src", np.dtype(np.uint32), AggKind.MAX),
        ColumnSpec("ip_dst", np.dtype(np.uint32), AggKind.MAX),
        ColumnSpec("port_src", np.dtype(np.uint32), AggKind.MAX),
        ColumnSpec("port_dst", np.dtype(np.uint32), AggKind.MAX),
        ColumnSpec("proto", np.dtype(np.uint32), AggKind.MAX),
    ),
)

WINDOW_TABLE = TableSchema(
    name="window_signals",
    columns=(
        ColumnSpec("timestamp", np.dtype(np.uint32), AggKind.KEY),
        ColumnSpec("rows", np.dtype(np.uint32), AggKind.SUM),
        ColumnSpec("entropy_ip_src", np.dtype(np.float32), AggKind.MAX),
        ColumnSpec("entropy_ip_dst", np.dtype(np.float32), AggKind.MAX),
        ColumnSpec("entropy_port_src", np.dtype(np.float32), AggKind.MAX),
        ColumnSpec("entropy_port_dst", np.dtype(np.float32), AggKind.MAX),
        ColumnSpec("distinct_clients", np.dtype(np.uint32), AggKind.MAX),
    ),
)


class _HostSketch:
    """Host-numpy fallback sketch: the degraded-mode lane.

    When the device is lost, the lane must degrade, not die (PSketch's
    priority-aware-degradation argument applied to the TPU fault
    domain). This is a reduced-rate approximation of FlowSuite on plain
    numpy: rows are stride-subsampled (1/stride admitted, counts scaled
    back up), heavy hitters accumulate in a bounded exact dict instead
    of a CMS+ring, distinct clients in a capped exact set instead of
    HLL, and entropies over modulo-bucketed histograms (the device path
    hashes; estimates are approximate by design and labelled by the
    exporter's `degraded` Countable). flush() emits a standard
    FlowWindowOutput so the store/querier surface is unchanged."""

    DICT_CAP = 1 << 16
    CLIENTS_CAP = 1 << 16

    def __init__(self, cfg: flow_suite.FlowSuiteConfig,
                 stride: int = 4) -> None:
        self.cfg = cfg
        self.stride = max(1, stride)
        self.rows = 0
        self._counts: Dict[int, int] = {}
        self._clients: set = set()
        self._buckets = 1 << cfg.entropy_log2_buckets
        self._ent = np.zeros((len(flow_suite.ENTROPY_FEATURES),
                              self._buckets), np.int64)

    def update(self, cols: Dict[str, np.ndarray]) -> int:
        """Absorb one chunk at 1/stride rate; returns rows admitted."""
        from deepflow_tpu.utils.u32 import fold_columns_np

        n = len(next(iter(cols.values()))) if cols else 0
        if n == 0:
            return 0
        self.rows += n
        sl = slice(None, None, self.stride)
        sub = {k: np.asarray(v)[sl] for k, v in cols.items()}
        keys = fold_columns_np([sub["ip_src"], sub["ip_dst"],
                                sub["port_src"], sub["port_dst"],
                                sub["proto"]])
        uniq, cnt = np.unique(keys, return_counts=True)
        counts = self._counts
        for k, c in zip(uniq.tolist(), cnt.tolist()):
            counts[k] = counts.get(k, 0) + c * self.stride
        if len(counts) > self.DICT_CAP:
            # keep the heavy half: the top-K readout only needs heads.
            # nlargest is O(n log cap) vs the full sort's O(n log n) —
            # this trim runs on the hot degraded path (bench
            # host_fallback), where the sort showed up
            import heapq
            keep = heapq.nlargest(self.DICT_CAP // 2, counts.items(),
                                  key=lambda kv: kv[1])
            self._counts = dict(keep)
        if len(self._clients) < self.CLIENTS_CAP:
            self._clients.update(sub["ip_src"].tolist())
        pkts = np.minimum(sub["packet_tx"].astype(np.int64)
                          + sub["packet_rx"].astype(np.int64), 0xFFFF)
        for i, f in enumerate(flow_suite.ENTROPY_FEATURES):
            # bincount over the bucketed feature beats np.add.at's
            # per-element scatter ~10x at these sizes; float64 weight
            # sums are exact for these integer magnitudes (< 2^53)
            self._ent[i] += np.bincount(
                np.asarray(sub[f]).astype(np.uint32)
                % np.uint32(self._buckets),
                weights=pkts, minlength=self._buckets).astype(np.int64)
        return len(keys)

    def flush(self, cfg: flow_suite.FlowSuiteConfig
              ) -> flow_suite.FlowWindowOutput:
        """Window readout in FlowWindowOutput shape, then reset."""
        import heapq
        k = cfg.top_k
        # heapq.nlargest == sorted(..., reverse=True)[:k] (stable on
        # ties, per its docs) at O(n log k) instead of sorting the
        # whole surviving dict every window
        top = heapq.nlargest(k, self._counts.items(),
                             key=lambda kv: kv[1])
        keys = np.zeros(k, np.uint32)
        counts = np.zeros(k, np.int32)
        for i, (key, c) in enumerate(top):
            keys[i] = key & 0xFFFFFFFF
            counts[i] = min(c, np.iinfo(np.int32).max)
        h = self._ent.astype(np.float64)
        total = h.sum(axis=1, keepdims=True)
        p = h / np.maximum(total, 1.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            xlogx = np.where(p > 0, p * np.log(p), 0.0)
        ent = np.where(total[:, 0] > 0,
                       -xlogx.sum(axis=1) / np.log(self._buckets), 0.0)
        out = flow_suite.FlowWindowOutput(
            topk_keys=keys, topk_counts=counts,
            service_cardinality=np.asarray([len(self._clients)],
                                           np.float32),
            entropies=ent.astype(np.float32),
            rows=np.asarray(self.rows, np.int32))
        self.rows = 0
        self._counts = {}
        self._clients = set()
        self._ent[:] = 0
        return out


class TpuSketchExporter(QueueWorkerExporter):
    """Exporter contract (start/close/is_export_data/put) over FlowSuite."""

    def __init__(self, store: Optional[Store] = None,
                 cfg: Optional[flow_suite.FlowSuiteConfig] = None,
                 batch_rows: int = 1 << 15,
                 window_seconds: float = 1.0,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1,
                 staged: bool = False,
                 wire: str = "dict",
                 prefetch_depth: int = 0,
                 coalesce_batches: int = 1,
                 zero_copy: bool = True,
                 pack_workers: int = 0,
                 pod_shards: int = 0,
                 pod_merge_deadline_s: float = 5.0,
                 pod_hosts: int = 0,
                 dcn_marker_deadline_s: float = 5.0,
                 dcn_transport: str = "auto",
                 dcn_heal_after_s: float = 0.0,
                 audit_rate: float = 0.0,
                 anomaly=None,
                 anomaly_dir: Optional[str] = None,
                 stats: Optional[StatsRegistry] = None) -> None:
        super().__init__("tpu_sketch", ["l4_flow_log"], n_workers=1,
                         batch=64, stats=stats)
        import jax.numpy as jnp  # deferred: exporter import stays light

        self._jnp = jnp
        self.cfg = cfg or flow_suite.FlowSuiteConfig()
        self.window_seconds = window_seconds
        # -- pod fault domains (parallel/pod.py, ISSUE 10) -----------------
        # pod_shards >= 2 routes the lane through the epoch-merged pod:
        # one single-device fault domain per shard, deadline-bounded
        # merges, per-shard degraded mode and rejoin-by-snapshot. The
        # pod runs the lanes wire with its own supervised shard workers
        # (that is where the overlap lives), so the single-chip
        # feed/staging knobs are forced off; each window flush closes
        # one merge epoch.
        # pod_hosts >= 2 stacks the cross-host ladder on top: the lane
        # routes through a HostPodCoordinator (parallel/multihost.py,
        # ISSUE 17) — per-host PodFlowSuites, DCN epoch markers, host
        # deadman exclusion, host kill/rejoin — same duck-typed surface
        # as the single-host pod, so everything below (window flush =
        # epoch close, merged bus, counters) is shared.
        self._pod = None
        if int(pod_shards) >= 2 or int(pod_hosts) >= 2:
            import logging
            if wire == "dict":
                logging.getLogger(__name__).warning(
                    "pod mode runs the lanes wire; wire='dict' ignored")
            if staged or prefetch_depth or pack_workers:
                logging.getLogger(__name__).info(
                    "pod mode: staged/prefetch/zero_copy/pack_workers "
                    "forced off (the pod's shard workers own overlap)")
            wire, staged = "lanes", False
            prefetch_depth = pack_workers = 0
            zero_copy = False
        if int(pod_hosts) >= 2:
            from deepflow_tpu.parallel.multihost import (
                HostPodCoordinator, select_transport)

            # no batch-width divisibility constraint here: the
            # coordinator re-packs each host's flow-hash slice into a
            # fresh plane padded to that lane's own shard width
            self._pod = HostPodCoordinator(
                self.cfg, n_hosts=int(pod_hosts),
                shards_per_host=int(pod_shards) or None,
                transport=select_transport(
                    dcn_transport, int(pod_hosts),
                    heal_after_s=(float(dcn_heal_after_s) or None)),
                dcn_marker_deadline_s=dcn_marker_deadline_s,
                merge_deadline_s=pod_merge_deadline_s,
                snapshot_dir=checkpoint_dir)
        elif int(pod_shards) >= 2:
            from deepflow_tpu.parallel.pod import PodFlowSuite
            import jax as _jax

            # fail BEFORE the pod spawns its shard workers, not
            # per-batch: put_lanes rejects a plane whose width the
            # shard count does not divide (same clamp the pod applies)
            eff_shards = min(int(pod_shards), len(_jax.devices()))
            if batch_rows % max(1, eff_shards) != 0:
                raise ValueError(
                    f"batch_rows={batch_rows} not divisible by the "
                    f"pod's {eff_shards} shard(s); every batch would "
                    f"be rejected at put_lanes")
            self._pod = PodFlowSuite(
                self.cfg, n_shards=int(pod_shards), wire="lanes",
                merge_deadline_s=pod_merge_deadline_s,
                snapshot_dir=checkpoint_dir)
        self.state = None if self._pod is not None \
            else flow_suite.init(self.cfg)
        # snapshot bus (ISSUE 7): the checkpointer refactored into a
        # pub/sub versioned snapshot store. With a checkpoint_dir the
        # bus is disk-backed (restart replay + degraded restore read the
        # same format back); without one it still exists in-process so
        # the serving read path works in StorageDisabled mode.
        # `checkpointer` stays None when undurable — every PR 2/4
        # restore/cadence decision keys off that, unchanged. In pod
        # mode the POD-MERGED bus is the one serving subscribes to.
        # Pod restart semantics differ from the single-chip restore:
        # per-shard snapshots are run-scoped rollback scratch (never
        # restored across a restart — the dead run's merge ledger is
        # gone, so restoring could double-merge already-delivered
        # rows); a restart loses at most the open epoch's per-shard
        # accumulation, while the merged bus snapshots stay replayable
        # and serveable (the pod resumes the epoch counter past them).
        self._snapbus = self._pod.bus if self._pod is not None \
            else SnapshotBus(checkpoint_dir)
        self.checkpointer = self._snapbus \
            if checkpoint_dir is not None and self._pod is None else None
        self.checkpoint_every = max(1, checkpoint_every)
        self.windows = 0
        self._rows_at_flush = 0
        if self.checkpointer is not None:
            restored = self.checkpointer.restore(self.state)
            if restored is not None:
                self.state = restored
                # resume the step counter past existing snapshots, else
                # new saves sort below stale ones and GC eats them
                self.windows = self.checkpointer.latest_step() or 0
                # restored accumulation is live data this process hasn't
                # counted; mark dirty so its replayed window checkpoints
                self._rows_at_flush = -1
        self.topk_writer = self.window_writer = None
        if store is not None:
            self.topk_writer = StoreWriter(
                store.create_table(SKETCH_DB, TOPK_TABLE),
                batch_rows=4096, flush_interval=5.0)
            self.window_writer = StoreWriter(
                store.create_table(SKETCH_DB, WINDOW_TABLE),
                batch_rows=1024, flush_interval=5.0)
        import jax

        # fused single-program update everywhere (cheaper dispatch, full
        # fusion); the staged four-program form is opt-in only and has
        # no workload (ROADMAP D8). The hot path packs the batch
        # into the 4-plane sketch-lane layout on the host before
        # transfer (flow_suite.pack_lanes): 16B/record over the link
        # instead of 68B.
        self.staged = bool(staged)
        # wire="dict" (default): the dictionary lane
        # (models/flow_dict.py) — a flow's tuple crosses the link once,
        # repeats cross as 6B pairs-packed hit rows against a
        # device-resident key table (~halving steady-state transfer
        # again vs the packed lane; the sketch state is bit-identical
        # either way). wire="lanes" keeps the stateless 16B packed
        # lane. The dictionary is NOT checkpointed: on restore a fresh
        # packer re-announces flows as news, and stale device-table
        # rows at unassigned indices are unreachable (hits only
        # reference host-assigned indices), so correctness never
        # depends on host/device dictionary agreement across restarts.
        if wire not in ("dict", "lanes"):
            raise ValueError(f"wire must be 'dict' or 'lanes', got {wire!r}")
        if self.staged and wire == "dict":
            import logging
            logging.getLogger(__name__).warning(
                "staged=True forces the packed lane; wire='dict' ignored")
        self.wire = "lanes" if self.staged else wire
        self._dict_packer = None
        if self.staged:
            self._update = flow_suite.make_staged_update(self.cfg)
        elif self.wire == "dict":
            from deepflow_tpu.models import flow_dict
            self._flow_dict = flow_dict
            # pairs-packed hits planes hold two records per slot, so the
            # packer's hits_batch must be even: an odd batch_rows rounds
            # DOWN (capacity floors at 2) instead of surfacing as the
            # packer's opaque "hits_batch must be even" at construction
            # (ctor params retained: degraded-mode recovery rebuilds the
            # packer + device dictionary from scratch)
            self._packer_capacity = max(2 * batch_rows, 1 << 17)
            self._packer_hits_batch = max(2, batch_rows & ~1)
            self._dict_packer = flow_dict.FlowDictPacker(
                capacity=self._packer_capacity,
                hits_batch=self._packer_hits_batch)
            self._dict_state = flow_dict.init_dict(
                self._dict_packer.capacity)
            self._update_hits = jax.jit(
                lambda s, d, p, n: flow_dict.update_hits(s, d, p, n,
                                                         self.cfg),
                donate_argnums=0)
            self._update_news = jax.jit(
                lambda s, d, p, n: flow_dict.update_news(s, d, p, n,
                                                         self.cfg),
                donate_argnums=(0, 1))
        else:
            self._update = jax.jit(
                lambda s, l, m: flow_suite.update_packed(s, l, m,
                                                         self.cfg),
                donate_argnums=0)
        # NOT donated: the pre-flush state is also the checkpoint payload
        self._flush_fn = jax.jit(lambda s: flow_suite.flush(s, self.cfg))
        self.rows_in = 0
        self._key_tuples: Dict[int, np.ndarray] = {}
        self.last_output: Optional[flow_suite.FlowWindowOutput] = None
        self._window_thread: Optional[threading.Thread] = None
        self._window_stop = threading.Event()
        self._state_lock = threading.Lock()
        # flight recorder: kernel attribution (h2d / dispatch / device,
        # first-call compile split out). _warm tracks which update
        # programs have already compiled; h2d byte totals feed the
        # tpu_h2d_mb_s gauge VERDICT r5 asked for. Attribution needs
        # explicit drains to separate transfer from compute, and a
        # drain serializes the otherwise-async device pipeline — so
        # detailed (blocking) attribution runs on every
        # `trace_attrib_every`-th batch plus every cold compile, and
        # all other traced batches keep the async shape (their "kernel"
        # span measures host-side time only). Sampling keeps the
        # enabled-tracer overhead within the <=3% budget instead of
        # turning observability-on into measurement-mode-always.
        self._tracer = default_tracer()
        self._warm: set = set()
        self.h2d_bytes = 0
        self._attrib_every = 16
        self._batches_traced = 0
        self._detailed = False
        # -- degraded mode (fault domain: the device) ----------------------
        # On a device-classified error (XlaRuntimeError / device loss —
        # RuntimeError subclasses on every jax we run) the lane restores
        # sketch state from the latest checkpoint snapshot (<=1 window
        # lost, checkpoint.py's promise) and, after `degrade_after`
        # consecutive failures, falls back to a host-numpy sketch at
        # reduced rate until a per-window probe finds the device healthy
        # again. All loss is counted, never silent.
        self._faults = default_faults()
        self.degraded = False
        self.device_errors = 0     # device-classified raises
        self.recoveries = 0        # degraded -> device restorations
        self.lost_windows = 0      # window accumulations rolled back
        self.lost_rows = 0         # rows in batches that died on device
        self.host_rows = 0         # rows absorbed by the host fallback
        self._consecutive_errors = 0
        self.degrade_after = 2
        self.host_stride = 4       # host fallback subsample (reduced rate)
        self._host: Optional[_HostSketch] = None
        self._window_lost_counted = False
        # -- overlapped device feed (runtime/feed.py, ISSUE 5) -------------
        # prefetch_depth > 0 routes the hot path through a supervised
        # feed thread: host pack of batch N+1 overlaps the device update
        # of batch N, each group crosses the link as ONE coalesced
        # transfer (vs one per plane/column), and coalesce_batches=K
        # fuses K TensorBatches into a single dispatch. 0 keeps the
        # inline unoverlapped path — the bit-identical reference the
        # equivalence tests diff against. State ownership with the feed
        # on: between feed.drain() barriers the FEED thread is the only
        # writer of self.state/_dict_state/_host; _state_lock serializes
        # producers against the window flush, and the flush touches
        # state only after a drain barrier returned (see feed.py).
        self.prefetch_depth = max(0, int(prefetch_depth))
        self.coalesce_batches = max(1, int(coalesce_batches))
        self.h2d_transfers = 0     # device_put count (TRUE total)
        self.dispatches = 0        # update-program call count
        self._feed = None
        self._programs: Dict[Any, Any] = {}   # shape signature -> jitted
        self._staging_pool: Dict[int, list] = {}
        self._staging_cap = self.prefetch_depth + 2
        if self.staged and self.prefetch_depth:
            import logging
            logging.getLogger(__name__).warning(
                "staged=True has no coalesced feed; prefetch disabled")
            self.prefetch_depth = 0
        # -- zero-copy decode->staging (batch/staging.py, ISSUE 9/20) ------
        # The feed path skips the TensorBatch entirely: decoded chunk
        # columns (frombuffer views of the frame payload) pack DIRECTLY
        # into recycled coalesced staging buffers, whole pre-staged
        # groups ride the feed, and pack_workers > 0 shards the
        # pack/stage work across supervised worker threads. The lanes
        # wire stages slot-contiguous lane planes (LaneStager); the
        # dict wire stages the packer's emitted news/hits word sequence
        # (DictWireStager — ISSUE 20's parity: the DEFAULT wire rides
        # the same prefetch window). The TensorBatch path
        # (zero_copy=False) remains the bit-identity reference the
        # equivalence tests diff against; the staged wire and the
        # inline path are unaffected.
        self.zero_copy = (bool(zero_copy)
                          and self.wire in ("lanes", "dict")
                          and not self.staged and self.prefetch_depth > 0)
        self._stager = None
        self._pack_pool = None
        self.batcher = None
        if self.zero_copy:
            from deepflow_tpu.batch.staging import (DictWireStager,
                                                    LaneStager, PackPool)
            if pack_workers > 0:
                self._pack_pool = PackPool(pack_workers)
            if self.wire == "dict":
                # the stager owns the packer (it must pack at its own
                # batch cuts to keep the inline partition); the inline
                # packer object is retired so restore logic cannot
                # confuse the two
                self._stager = DictWireStager(
                    batch_rows,
                    packer_factory=lambda: self._flow_dict.FlowDictPacker(
                        capacity=self._packer_capacity,
                        hits_batch=self._packer_hits_batch),
                    group_batches=self.coalesce_batches,
                    pool=self._pack_pool,
                    pool_cap=self.prefetch_depth + 2)
                self._dict_packer = None
            else:
                self._stager = LaneStager(
                    batch_rows, group_batches=self.coalesce_batches,
                    pool=self._pack_pool,
                    pool_cap=self.prefetch_depth + 2)
        else:
            # only the kernel-consumed subset is batched and transferred
            # to device — the wide store schema never crosses the
            # PCIe/ICI. Zero-copy stages decoded columns directly and
            # never materializes a TensorBatch, so it skips the eager
            # batch_rows x 68B alloc (and the dead always-zero batcher
            # counters beside the stager's real ones).
            self.batcher = Batcher(SKETCH_L4_SCHEMA, capacity=batch_rows)
        if self.prefetch_depth:
            from deepflow_tpu.runtime.feed import DeviceFeed
            self._feed = DeviceFeed(
                "tpu-sketch-feed",
                self._feed_process_dict_staged
                if (self.zero_copy and self.wire == "dict")
                else self._feed_process_staged if self.zero_copy
                else self._feed_process_group,
                depth=self.prefetch_depth,
                # zero-copy groups are coalesced AT THE STAGER (K slots
                # per buffer, deterministic); the feed moves one staged
                # group per item
                coalesce=1 if self.zero_copy else self.coalesce_batches,
                on_fence_error=self._feed_fence_error,
                on_restart=self._feed_crash_restart)
        # -- accuracy observatory (runtime/audit.py, ISSUE 6) --------------
        # deterministic flow-hash sampled exact shadow, compared against
        # the sketch at every window close. Host-side only and
        # bit-invisible to the device path (tests assert state equality
        # with the audit on/off); degraded/lossy windows are audited too,
        # tagged instead of alarmed on. 0 disables.
        from deepflow_tpu.runtime.profiler import default_profiler
        self._prof = default_profiler()
        self._audit = None
        self.audit_rate = max(0.0, float(audit_rate))
        if self.audit_rate > 0:
            from deepflow_tpu.runtime.audit import ShadowAuditor
            self._audit = ShadowAuditor(self.cfg, rate=self.audit_rate)
            if stats is not None:
                stats.register("tpu_sketch_accuracy", self._audit.counters)
        # -- anomaly plane (deepflow_tpu/anomaly/, ISSUE 15) ---------------
        # The detection lane beside the sketch lane: a device-resident
        # active-flow table fed per batch from the SAME device arrays
        # the sketch update transfers (zero extra h2d), plus one jitted
        # window step per flush (entropy-DDoS z-scores, streaming-PCA
        # residual, matrix-profile discord). Its state is a separate
        # pytree — sketch state is bit-identical with the plane on or
        # off (tests/test_anomaly.py). `anomaly` is an AnomalyConfig,
        # or True for defaults; None disables.
        self._anomaly = None
        if anomaly:
            from deepflow_tpu.anomaly import AnomalyConfig, AnomalyPlane
            acfg = anomaly if isinstance(anomaly, AnomalyConfig) \
                else AnomalyConfig()
            self._anomaly = AnomalyPlane(acfg, directory=anomaly_dir,
                                         stats=stats)

    # -- exporter lifecycle ------------------------------------------------
    def start(self) -> None:
        if self.topk_writer is not None:
            self.topk_writer.start()
            self.window_writer.start()
        super().start()
        # supervised (crash capture + restart), deadman disabled: the
        # loop legitimately blocks a full window_seconds between beats
        self._window_thread = default_supervisor().spawn(
            "tpu-sketch-window", self._window_loop, deadman_s=None)

    def close(self) -> None:
        self._window_stop.set()
        if self._window_thread is not None:
            self._window_thread.stop()
            self._window_thread.join(timeout=5)
        super().close()
        self.flush_window()  # final window (drains the feed first)
        if self._pod is not None:
            # one more (normally empty) epoch so late stragglers'
            # contributions deliver before the workers stop
            self._pod.close(final_epoch=True)
        if self._feed is not None:
            self._feed.close()
        if self._pack_pool is not None:
            # after the feed: in-flight groups may still be waiting on
            # pool packs, so the pool outlives the last fence
            self._pack_pool.close()
        for w in (self.topk_writer, self.window_writer):
            if w is not None:
                w.close()

    # -- data path ---------------------------------------------------------
    def process(self, chunks: List[Any]) -> None:
        """Queue worker: decoded chunks -> static batches -> device.
        Holds _state_lock across batcher + state mutation: the window
        thread's flush_window() touches both under the same lock.
        Chunks arrive as (stream, idx, cols, batch_id); the batch id is
        pinned per chunk so kernel spans anchor to the decoder chunk
        that produced the rows."""
        tracing = self._tracer.enabled
        for stream, _idx, cols, *rest in chunks:
            if tracing and rest:
                self._tracer.set_batch(rest[0])
            schema_cols = self.coerce_to_schema(cols, SKETCH_L4_SCHEMA)
            if self._stager is not None or self._pod is not None:
                # zero-copy: the sampled reverse map reads the chunk
                # HERE, outside the lock (the staged lanes carry no
                # tuple columns any more; the TensorBatch path hashes
                # on the feed thread, equally unlocked) — the serialized
                # section below keeps only the stager/rows_in mutations.
                # The pod path samples here too: its shard workers only
                # ever see packed lane planes.
                self._record_key_tuples(schema_cols)
            with self._state_lock:
                if self._pod is not None:
                    # pod lane: pack into the (4, B) plane and fan the
                    # shard slices onto the per-shard queues. put_lanes
                    # never blocks (a slow/LOST shard drops counted on
                    # its own queue), so this is not an emission that
                    # can deadlock — same argument as the stager put.
                    for tb in self.batcher.put(schema_cols):  # lint: disable=emit-under-lock
                        self._pod_submit_locked(tb)
                elif self._stager is not None:
                    # zero-copy: chunk columns pack straight into the
                    # staging buffer — no TensorBatch, no batcher copy.
                    # Not an emission: the stager is private state
                    # guarded BY this lock (flush_window drains it under
                    # the same lock), and its pack-pool queues drain on
                    # workers that never take it — back-pressure, not
                    # deadlock (the batcher.put argument).
                    for sg in self._stager.put(schema_cols):  # lint: disable=emit-under-lock
                        self._feed.put(  # lint: disable=emit-under-lock
                            sg, self._tracer.current_batch()
                            if self._tracer.enabled else -1)
                else:
                    # not an emission: the batcher is private state
                    # guarded BY this lock (flush_window drains it under
                    # the same lock); no other thread can block on it
                    for tb in self.batcher.put(schema_cols):  # lint: disable=emit-under-lock
                        self._submit_batch_locked(tb)
                # counted once the chunk is fully handed to the device
                # path (inline: on device; feed: in the bounded window,
                # which every flush drains first), so rows_in is a
                # processed-watermark, not an arrival count
                self.rows_in += len(next(iter(schema_cols.values())))
                if self._anomaly is not None:
                    # conservation mirror: the detection lane's
                    # rows_seen moves at the SAME boundary rows_in
                    # does, so `anomaly.rows_seen == rows_in` is an
                    # exact scrape-time invariant (the ci.sh anomaly
                    # smoke asserts it through a mid-attack fault)
                    self._anomaly.observe_rows(
                        len(next(iter(schema_cols.values()))))
                if self._audit is not None:
                    # exact-shadow mirror at the SAME boundary rows_in
                    # moves: the audit window and the sketch window see
                    # the identical row set (flush drains batcher+feed
                    # under this lock before closing both). Host numpy
                    # only — the device path never sees the audit.
                    self._audit.absorb(schema_cols)

    def _pod_submit_locked(self, tb: TensorBatch) -> None:
        """One TensorBatch onto the pod lane: host-pack the 4-plane
        lane matrix (a fresh buffer — the pod keeps views) and fan it
        across the shard queues; the TensorBatch recycles immediately."""
        lanes = flow_suite.pack_lanes(tb.columns)
        plane = np.stack([lanes[k] for k in flow_suite.SKETCH_LANE_NAMES])
        self._pod.put_lanes(plane, int(tb.valid))
        self.batcher.recycle(tb)

    def _submit_batch_locked(self, tb: TensorBatch) -> None:
        """One emitted TensorBatch onto the device path: inline
        dispatch, or the overlapped feed when prefetch is on. The feed
        consumer never takes _state_lock (feed.py's ownership
        protocol), so the blocking put is back-pressure, not a
        deadlock."""
        if self._feed is None:
            self._run_batch_locked(tb)
            return
        self._feed.put(  # lint: disable=emit-under-lock
            tb, self._tracer.current_batch()
            if self._tracer.enabled else -1)

    def _to_device(self, host_array, rows: int):
        """jnp.asarray with flight-recorder h2d attribution. A
        DETAILED batch adds a block_until_ready after the put — the
        only way to separate transfer time from compute — so it is
        sampled (see __init__); everything else stays fully async."""
        jnp = self._jnp
        tr = self._tracer
        # byte/transfer counters are TRUE totals (scraped beside
        # rows_in): every transfer counts, only the blocking
        # measurement samples. transfers-vs-batches is the coalescing
        # regression signal ISSUE 5 asks for — a slide back toward
        # per-plane puts shows up as h2d_transfers outgrowing batches
        self.h2d_bytes += host_array.nbytes
        self.h2d_transfers += 1
        if not (tr.enabled and self._detailed):
            return jnp.asarray(host_array)
        t0 = time.perf_counter()
        dev = jnp.asarray(host_array)
        dev.block_until_ready()
        dt = time.perf_counter() - t0
        tr.observe("kernel.h2d", dt, stream=self.wire, rows=rows)
        self._prof.record("h2d", self.wire, dt, rows=rows)
        if dt > 0:
            tr.gauge("tpu_h2d_mb_s", host_array.nbytes / 1e6 / dt)
        return dev

    def _timed_update(self, key: str, fn, *args):
        """Dispatch + drain attribution around one jitted update call.
        The first call per program is COMPILE (recorded as its own
        stage and gauge, never polluting the steady-state kernel
        quantiles); later calls split into dispatch (host returns) and
        device (block_until_ready drain). Runs the plain async call
        unless this batch is a sampled detailed one or the program is
        cold (a compile must always be attributed — missing it would
        poison the first sampled batch's device quantile instead)."""
        tr = self._tracer
        self.dispatches += 1
        first = key not in self._warm
        if not tr.enabled or not (self._detailed or first):
            return fn(*args)
        import jax
        t0 = time.perf_counter()
        out = fn(*args)
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        if first:
            self._warm.add(key)
            tr.observe("kernel.compile", t2 - t0, stream=key)
            tr.gauge(f"tpu_compile_s_{key}", t2 - t0)
            self._prof.record("device", f"compile:{key}", t2 - t0)
        else:
            tr.observe("kernel.dispatch", t1 - t0, stream=key)
            tr.observe("kernel.device", t2 - t1, stream=key)
            # sampled occupancy evidence for the inline path (the feed
            # path's fence intervals are the continuous signal). The
            # dispatch span ENDED a device-execution ago — anchor its
            # wall-clock end back so the exported timeline shows
            # dispatch preceding device, not stacked on top of it.
            self._prof.record("dispatch", key, t1 - t0,
                              t_end=time.time() - (t2 - t1))
            self._prof.record("device", key, t2 - t1)
        return out

    def _run_batch_locked(self, tb: TensorBatch) -> None:
        if self.degraded:
            self._host_batch_locked(tb)
            return
        tr = self._tracer
        try:
            if not tr.enabled:
                self._run_batch_inner_locked(tb)
                return
            before = self.h2d_transfers
            with tr.span("kernel", stream=self.wire, rows=tb.valid):
                self._run_batch_inner_locked(tb)
            if self._detailed:
                # the same coalescing-regression gauge the feed path
                # records: the inline path honestly reads its
                # per-plane/per-column transfer count (> 1)
                tr.gauge("tpu_transfers_per_batch",
                         float(self.h2d_transfers - before))
        except RuntimeError:
            # XlaRuntimeError (device loss, OOM, preemption) subclasses
            # RuntimeError; anything else device-shaped lands here too.
            # Non-Runtime errors (shape bugs -> TypeError/ValueError)
            # propagate to the worker's process_errors containment.
            self._on_device_error_locked(int(tb.valid))

    def _on_device_error_locked(self, rows: int) -> None:
        """One batch died on the device: roll sketch state back to the
        latest checkpoint (<=1 window lost), and after repeated failures
        hand the lane to the host-numpy fallback."""
        import logging

        self.device_errors += 1
        self._consecutive_errors += 1
        self.lost_rows += rows
        if not self._window_lost_counted:
            self.lost_windows += 1          # this window's accumulation
            self._window_lost_counted = True
        logging.getLogger(__name__).exception(
            "tpu_sketch device error #%d (consecutive %d)",
            self.device_errors, self._consecutive_errors)
        try:
            self._restore_device_state_locked()
        except Exception:
            # the device can't even hold a fresh state: go degraded now
            self._consecutive_errors = self.degrade_after
        if self._consecutive_errors >= self.degrade_after:
            self.degraded = True
            logging.getLogger(__name__).warning(
                "tpu_sketch degraded: host-numpy fallback at 1/%d rate",
                self.host_stride)
        if self._anomaly is not None:
            # the anomaly state may ride the same dead device chain:
            # re-init the table (counted), window counter preserved
            self._anomaly.device_lost()

    def _restore_device_state_locked(self) -> None:
        """Rebuild device-resident state: latest compatible checkpoint
        if one exists, else a fresh init. The dictionary lane's packer +
        device table restart empty — flows re-announce as news, and
        correctness never depends on host/device dictionary agreement
        (see the wire='dict' note in __init__)."""
        fresh = flow_suite.init(self.cfg)
        restored = None
        if self.checkpointer is not None:
            restored = self.checkpointer.restore(fresh)
        if restored is not None:
            import logging
            # which snapshot the rollback landed on (ISSUE 7 satellite:
            # the audit/ops can attribute the replayed window instead of
            # guessing; the same number rides counters() as
            # last_restored_step)
            logging.getLogger(__name__).warning(
                "tpu_sketch state restored from snapshot step %d "
                "(current window %d)",
                self.checkpointer.last_restored_step, self.windows)
        self.state = restored if restored is not None else fresh
        if self.wire == "dict":
            if self.zero_copy and self._stager is not None:
                # the stager owns the packer: swap a fresh one under its
                # lock (bumping the wire epoch so in-flight groups whose
                # slot indices reference the dead table are dropped as
                # counted loss by the dispatcher) and zero the host
                # mirror. The open group's already-packed words die with
                # the old generation; its rows are counted lost here,
                # matching the inline path's loss accounting.
                self.lost_rows += self._stager.reset_packer()
            else:
                self._dict_packer = self._flow_dict.FlowDictPacker(
                    capacity=self._packer_capacity,
                    hits_batch=self._packer_hits_batch)
            self._dict_state = self._flow_dict.init_dict(
                self._packer_capacity)
        self._warm = set()

    def _host_batch_locked(self, tb: TensorBatch) -> None:
        if self._host is None:
            self._host = _HostSketch(self.cfg, stride=self.host_stride)
        mask = tb.mask()
        cols = {k: v[mask] for k, v in tb.columns.items()}
        self.host_rows += self._host.update(cols)

    def _probe_device_locked(self) -> bool:
        """Degraded-mode recovery probe (once per window): a tiny
        device round-trip; healthy -> restore from checkpoint and hand
        the lane back to the device. Host-window tallies were already
        flushed as (reduced-fidelity) window outputs, so they are
        dropped, not merged."""
        try:
            if self._faults.enabled:
                self._faults.maybe_raise(FAULT_DEVICE_ERROR, key="probe")
            probe = self._jnp.asarray(np.ones(8, np.uint32))
            if int(probe.sum()) != 8:
                return False
            self._restore_device_state_locked()
        except Exception:
            return False
        self.degraded = False
        self._consecutive_errors = 0
        self.recoveries += 1
        self._host = None
        return True

    def _run_batch_inner_locked(self, tb: TensorBatch) -> None:
        if self._faults.enabled:   # chaos: simulated device loss
            self._faults.maybe_raise(FAULT_DEVICE_ERROR, key=self.wire)
        if self._tracer.enabled:
            self._detailed = \
                self._batches_traced % self._attrib_every == 0
            self._batches_traced += 1
        self._record_key_tuples(tb.columns)
        if self._dict_packer is not None:
            # dictionary lane: pack only the VALID rows (the packer's
            # row stream has no padding concept; plane padding is
            # masked on device by each batch's n)
            mask = tb.mask()
            cols = {k: v[mask] for k, v in tb.columns.items()}
            wire = self._dict_packer.pack(cols) + self._dict_packer.flush()
            for kind, plane, n in wire:
                nn = np.uint32(n)
                plane_d = self._to_device(plane, n)
                if kind == "news":
                    self.state, self._dict_state = self._timed_update(
                        "news", self._update_news,
                        self.state, self._dict_state, plane_d, nn)
                    if self._anomaly is not None:
                        self._anomaly.feed_news(plane_d, nn)
                else:
                    self.state = self._timed_update(
                        "hits", self._update_hits,
                        self.state, self._dict_state, plane_d, nn)
                    if self._anomaly is not None:
                        self._anomaly.feed_hits(
                            self._dict_state.table, plane_d, nn)
            return
        n = tb.valid
        mask_d = self._to_device(tb.mask(), n)
        if self.staged:   # staged update consumes the full column dict
            cols_d = {k: self._to_device(v, n)
                      for k, v in tb.columns.items()}
            self.state = self._timed_update(
                "staged", self._update, self.state, cols_d, mask_d)
            if self._anomaly is not None:
                self._anomaly.feed_cols(cols_d, mask_d)
            return
        lanes = flow_suite.pack_lanes(tb.columns)
        lanes_d = {k: self._to_device(v, n) for k, v in lanes.items()}
        self.state = self._timed_update(
            "packed", self._update, self.state, lanes_d, mask_d)
        if self._anomaly is not None:
            # the active-flow working set eats the SAME device arrays
            # the sketch update just consumed — no second transfer
            self._anomaly.feed_lanes(lanes_d, mask_d)

    # -- overlapped feed (runtime/feed.py) ---------------------------------
    # Everything below runs on the FEED THREAD. It never takes
    # _state_lock: between drain barriers the feed thread is the only
    # writer of self.state/_dict_state/_host (the ownership protocol
    # feed.py documents), and flush/checkpoint/probe touch state only
    # after a barrier returned.

    def _feed_process(self, group, absorb, dispatch
                      ) -> Optional["InFlight"]:
        """Shared feed-thread shell for one group: degraded-mode host
        absorption, tracer kernel span, and the device-error rollback
        that counts the whole group. One definition so the TensorBatch
        and zero-copy feeds cannot diverge in error accounting — only
        the per-item absorb/dispatch callbacks differ (both item kinds
        expose `.valid`)."""
        if self.degraded:
            for item, _ in group:
                absorb(item)
            return None
        tr = self._tracer
        rows = sum(int(item.valid) for item, _ in group)
        if not tr.enabled:
            try:
                return dispatch(group, rows)
            except RuntimeError:
                self._on_device_error_locked(rows)
                return None
        tr.set_batch(group[0][1])
        try:
            with tr.span("kernel", stream=self.wire, rows=rows):
                return dispatch(group, rows)
        except RuntimeError:
            self._on_device_error_locked(rows)
            return None

    def _feed_process_group(self, group) -> Optional["InFlight"]:
        """Apply one group of (TensorBatch, batch_id): host-pack into a
        single staging buffer, ONE coalesced transfer, one fused async
        dispatch with donated state. Degraded mode absorbs the group
        host-side; a device-classified error rolls back exactly like
        the inline path, with the whole group counted."""
        return self._feed_process(group, self._absorb_tensorbatch,
                                  self._dispatch_group)

    def _absorb_tensorbatch(self, tb) -> None:
        self._host_batch_locked(tb)
        self.batcher.recycle(tb)

    def _dispatch_begin(self) -> int:
        """Chaos fault injection + the every-Nth detailed-attribution
        cadence shared by both dispatch twins; returns the h2d
        transfer count before the dispatch for the per-batch gauge."""
        if self._faults.enabled:   # chaos: simulated device loss
            self._faults.maybe_raise(FAULT_DEVICE_ERROR, key=self.wire)
        if self._tracer.enabled:
            self._detailed = \
                self._batches_traced % self._attrib_every == 0
            self._batches_traced += 1
        return self.h2d_transfers

    def _dispatch_group(self, group, rows: int) -> Optional["InFlight"]:
        from deepflow_tpu.runtime.feed import InFlight

        before = self._dispatch_begin()
        tr = self._tracer
        if self.wire == "dict":
            staged = self._dispatch_dict_group(group)
        else:
            staged = self._dispatch_lanes_group(group)
        if tr.enabled and self._detailed:
            tr.gauge("tpu_transfers_per_batch",
                     (self.h2d_transfers - before) / len(group))
        if staged is None:
            # None = the dict packer emitted no wire for this group
            # (zero valid rows): there is no fence to wait on and no
            # data was abandoned — nothing for the ledger to count
            return None  # lint: disable=silent-drop
        fence, flat = staged
        if tr.enabled and self._detailed:
            tr.gauge("tpu_h2d_coalesced_bytes", float(flat.nbytes))
        return InFlight(fence, rows,
                        lambda: self._staging_release(flat))

    def _dispatch_lanes_group(self, group):
        """K packed-lane batches -> one flat staging buffer -> one
        scan-fused update program (flow_suite.make_coalesced_update)."""
        K = len(group)
        C = self.batcher.capacity
        flat = self._staging_get(flow_suite.coalesced_lanes_words(K, C))
        for k, (tb, _) in enumerate(group):
            self._record_key_tuples(tb.columns)
            flat[k * flow_suite.slot_words(C)] = tb.valid
            flow_suite.pack_lanes_into(tb.columns,
                                       flow_suite.slot_plane(flat, k, C))
            self.batcher.recycle(tb)
        prog = self._program(
            ("lanes", K, C),
            lambda: flow_suite.make_coalesced_update(self.cfg, K, C))
        flat_d = self._to_device(flat, sum(int(tb.valid)
                                          for tb, _ in group))
        self.state, fence = self._timed_update(
            f"lanes_x{K}", prog, self.state, flat_d)
        if self._anomaly is not None:
            self._anomaly.feed_flat(flat_d, K, C)
        return fence, flat

    def _dispatch_dict_group(self, group):
        """K batches through the dictionary packer -> the emitted wire
        sequence staged flat -> one signature-keyed fused program
        (flow_dict.make_wire_update). Emission order is preserved
        per-batch (pack + flush per TensorBatch, exactly the inline
        sequence), so sketch state stays bit-identical."""
        fd = self._flow_dict
        wire = []
        for tb, _ in group:
            self._record_key_tuples(tb.columns)
            mask = tb.mask()
            cols = {k: v[mask] for k, v in tb.columns.items()}
            wire += self._dict_packer.pack(cols)
            wire += self._dict_packer.flush()
            self.batcher.recycle(tb)
        if not wire:
            return None
        sig = fd.wire_signature(wire)
        flat = self._staging_get(fd.wire_words(sig))
        fd.stage_wire(wire, flat)
        prog = self._program(
            ("dict", sig), lambda: fd.make_wire_update(self.cfg, sig))
        flat_d = self._to_device(flat, sum(n for _, _, n in wire))
        key = "dict:" + "+".join(f"{k[0]}{w}" for k, w in sig)
        self.state, self._dict_state, fence = self._timed_update(
            key, prog, self.state, self._dict_state, flat_d)
        if self._anomaly is not None:
            self._anomaly.feed_dict_flat(self._dict_state.table,
                                         flat_d, sig)
        return fence, flat

    def _feed_process_staged(self, group) -> Optional["InFlight"]:
        """Zero-copy variant of _feed_process_group: items are
        pre-staged groups (batch/staging.py StagedGroup) — the host
        pack already happened (possibly on the sharded pack pool), so
        this thread only waits for group readiness, transfers and
        dispatches. Degraded mode absorbs the staged lanes host-side
        via the unpack twin; device errors roll back exactly like the
        TensorBatch path with the whole group counted."""
        return self._feed_process(group, self._absorb_staged_host,
                                  self._dispatch_staged)

    def _dispatch_staged(self, group, rows: int) -> Optional["InFlight"]:
        from deepflow_tpu.runtime.feed import InFlight

        before = self._dispatch_begin()
        tr = self._tracer
        fence = None
        for sg, _ in group:        # coalesce=1: normally exactly one
            # host barrier for the sharded pack (NOT a device sync): a
            # poisoned group raises StagingPackError, which escapes to
            # the supervisor on purpose — restart + on_restart counts
            # the window lost, the ISSUE 5 containment
            sg.wait_ready(timeout=30.0)
            prog = self._program(
                ("lanes", sg.k, sg.capacity),
                lambda k=sg.k, c=sg.capacity:
                flow_suite.make_coalesced_update(self.cfg, k, c))
            flat_d = self._to_device(sg.flat, sg.valid)
            self.state, fence = self._timed_update(
                f"lanes_x{sg.k}", prog, self.state, flat_d)
            if self._anomaly is not None:
                self._anomaly.feed_flat(flat_d, sg.k, sg.capacity)
        if tr.enabled and self._detailed:
            tr.gauge("tpu_transfers_per_batch",
                     (self.h2d_transfers - before)
                     / max(1, sum(sg.k for sg, _ in group)))
            tr.gauge("tpu_h2d_coalesced_bytes",
                     float(sum(sg.flat.nbytes for sg, _ in group)))
        groups = [sg for sg, _ in group]
        return InFlight(
            fence, rows,
            lambda: [self._stager.recycle(sg) for sg in groups])

    def _absorb_staged_host(self, sg) -> None:
        """Degraded mode reached a pre-staged group: the lanes ARE the
        batch now (no TensorBatch ever existed), so the host fallback
        consumes the unpack twin of each slot at its reduced rate."""
        sg.wait_ready(timeout=30.0)
        if self._host is None:
            self._host = _HostSketch(self.cfg, stride=self.host_stride)
        s = flow_suite.slot_words(sg.capacity)
        for k in range(sg.k):
            n = int(sg.flat[k * s])
            if n:
                self.host_rows += self._host.update(
                    flow_suite.unpack_lanes_np(
                        flow_suite.slot_plane(sg.flat, k, sg.capacity),
                        n))
        self._stager.recycle(sg)

    def _feed_process_dict_staged(self, group) -> Optional["InFlight"]:
        """Dict-wire zero-copy twin of _feed_process_staged: items are
        pre-staged wire groups (batch/staging.py StagedWireGroup) —
        the packer ran at put() time on the producer (pack + flush per
        batch_rows cut, exactly the inline partition) and the emitted
        word sequence was staged flat (possibly on the pack pool), so
        this thread only waits for readiness, transfers and dispatches
        the signature-keyed fused program. Degraded mode absorbs the
        staged words host-side via the unpack twin against the
        stager's host key mirror; a group staged before a device
        restart (stale epoch) references a dead table generation and
        is dropped as counted loss."""
        return self._feed_process(group, self._absorb_dict_staged_host,
                                  self._dispatch_dict_staged)

    def _dispatch_dict_staged(self, group,
                              rows: int) -> Optional["InFlight"]:
        from deepflow_tpu.runtime.feed import InFlight

        fd = self._flow_dict
        before = self._dispatch_begin()
        tr = self._tracer
        fence = None
        live = []
        for sg, _ in group:        # coalesce=1: normally exactly one
            sg.wait_ready(timeout=30.0)
            if sg.epoch != self._stager.epoch:
                # staged against a table generation that died in a
                # device restart: its slot indices are meaningless now.
                # Counted loss, exactly like the inline path dropping
                # the packer's pending wire with the dead state.
                self._stager.epoch_drops += 1
                self.lost_rows += int(sg.valid)
                self._stager.recycle(sg)
                continue
            prog = self._program(
                ("dict", sg.sig),
                lambda s=sg.sig: fd.make_wire_update(self.cfg, s))
            flat_d = self._to_device(sg.flat, sg.valid)
            key = "dict:" + "+".join(f"{k[0]}{w}" for k, w in sg.sig)
            self.state, self._dict_state, fence = self._timed_update(
                key, prog, self.state, self._dict_state, flat_d)
            if self._anomaly is not None:
                self._anomaly.feed_dict_flat(self._dict_state.table,
                                             flat_d, sg.sig)
            live.append(sg)
        if tr.enabled and self._detailed:
            tr.gauge("tpu_transfers_per_batch",
                     (self.h2d_transfers - before)
                     / max(1, sum(sg.k for sg, _ in group)))
            tr.gauge("tpu_h2d_coalesced_bytes",
                     float(sum(sg.flat.nbytes for sg, _ in group)))
        if fence is None:
            # every group was a stale-epoch drop (already counted) —
            # nothing in flight
            return None  # lint: disable=silent-drop
        return InFlight(
            fence, sum(int(sg.valid) for sg in live),
            lambda: [self._stager.recycle(sg) for sg in live])

    def _absorb_dict_staged_host(self, sg) -> None:
        """Degraded mode reached a pre-staged wire group: the flat
        word sequence IS the batch now, so the host fallback walks the
        unpack twin (news planes carry their keys inline; hits gather
        them from the stager's host mirror of the device table) at its
        reduced rate."""
        sg.wait_ready(timeout=30.0)
        if sg.epoch != self._stager.epoch:
            self._stager.epoch_drops += 1
            self.lost_rows += int(sg.valid)
            self._stager.recycle(sg)
            return
        if self._host is None:
            self._host = _HostSketch(self.cfg, stride=self.host_stride)
        for cols, n in self._flow_dict.unpack_wire_np(
                sg.flat, sg.sig, self._stager.mirror):
            if n:
                self.host_rows += self._host.update(cols)
        self._stager.recycle(sg)

    _PROGRAM_CACHE_CAP = 128

    def _program(self, key, build):
        """Shape-signature -> jitted fused program cache. Bounded: the
        packer's power-of-two width buckets keep real signature churn
        tiny, but a pathological stream must degrade to recompiles,
        not grow without limit."""
        prog = self._programs.get(key)
        if prog is None:
            if len(self._programs) >= self._PROGRAM_CACHE_CAP:
                self._programs.clear()
            prog = build()
            self._programs[key] = prog
        return prog

    def _staging_get(self, words: int):
        pool = self._staging_pool.get(words)
        if pool:
            try:
                return pool.pop()
            except IndexError:
                pass
        return np.empty(words, np.uint32)

    def _staging_release(self, flat) -> None:
        """Return a staging buffer once its batch's fence retired (the
        only point reuse is provably safe: the program that read the
        buffer has completed). Bounded per shape and in shape count."""
        if len(self._staging_pool) >= 16 \
                and flat.size not in self._staging_pool:
            return
        pool = self._staging_pool.setdefault(flat.size, [])
        if len(pool) < self._staging_cap:
            pool.append(flat)

    def _feed_fence_error(self, exc: BaseException, rows: int) -> None:
        """Async device failure surfaced at a feed fence: the failed
        batch plus every younger in-flight batch (their donated state
        chain is poisoned) arrive as ONE loss — same rollback ladder
        as a synchronous dispatch error."""
        if isinstance(exc, RuntimeError):
            self._on_device_error_locked(rows)
            return
        # not device-shaped: count the loss, restore to a known state
        self.lost_rows += rows
        try:
            self._restore_device_state_locked()
        except Exception:
            self._consecutive_errors = self.degrade_after
            self.degraded = True

    def _feed_crash_restart(self, rows: int) -> None:
        """Supervisor restarted the feed thread after a crash: the
        window's rows are counted lost and device state restored from
        the latest checkpoint (donation leaves the chain uncertain, so
        trusting it would risk silent corruption — the one loss class
        this lane never accepts)."""
        self.lost_rows += rows
        if not self._window_lost_counted:
            self.lost_windows += 1
            self._window_lost_counted = True
        if self.degraded:
            return
        try:
            self._restore_device_state_locked()
        except Exception:
            self._consecutive_errors = self.degrade_after
            self.degraded = True

    def pending_extra(self) -> int:
        """Batches still owed to the device by the prefetch window —
        Exporters.pending() adds this so the drain ladder (PR 4) keeps
        waiting while rows are in flight."""
        return 0 if self._feed is None else self._feed.pending()

    @property
    def snapshot_bus(self) -> SnapshotBus:
        """The ISSUE 7 snapshot bus: serving caches subscribe here.
        Always present (in-process-only when no checkpoint_dir). In pod
        mode this is the POD-MERGED bus — every epoch's merged state
        with shard-participation tags (ISSUE 10)."""
        return self._snapbus

    @property
    def pod(self):
        """The pod fault-domain layer (parallel/pod.py), or None on
        the single-chip lane — Ingester.health reads shard states
        through this."""
        return self._pod

    @property
    def anomaly(self):
        """The anomaly plane (deepflow_tpu/anomaly/), or None when the
        detection lane is off — the Ingester wires the Exporters
        fan-out and serving mounts the alert bus through this."""
        return self._anomaly

    @property
    def audit_alarm(self) -> bool:
        """Accuracy-observatory alarm: observed sketch error exceeded
        its theoretical bound for N consecutive clean windows
        (runtime/audit.py). Ingester.health surfaces it on /healthz."""
        return self._audit is not None and self._audit.alarm

    # one entry per distinct sampled flow key: (ip_src, ip_dst,
    # port_src, port_dst, proto). Sized well above ring_size so standing
    # heavy hitters stay resolvable across windows.
    _KEY_TUPLES_CAP = 1 << 18

    def _record_key_tuples(self, cols: Dict[str, np.ndarray]) -> None:
        """Sampled host-side key -> 5-tuple reverse map (the
        universal-tag role): top-K heavy hitters recur, so a stride
        sample resolves them with near-certainty while costing one
        numpy hash over 1/16 of the batch. Drop-oldest at the cap, so
        churn can't grow the map unboundedly. Takes bare columns (not
        a TensorBatch): the zero-copy path samples the decoded chunk
        directly — staged lane words no longer carry the tuple."""
        from deepflow_tpu.utils.u32 import fold_columns_np

        stride = 16
        sl = slice(None, None, stride)
        sample = [cols["ip_src"][sl], cols["ip_dst"][sl],
                  cols["port_src"][sl], cols["port_dst"][sl],
                  cols["proto"][sl]]
        keys = fold_columns_np(sample)
        tup = np.stack([c.astype(np.uint32) for c in sample], axis=1)
        for i, key in enumerate(keys):
            k = int(key)
            # pop-then-insert refreshes recency: dict re-assignment
            # keeps position, which would make the drop-oldest loop
            # below evict STANDING heavy hitters first. copy(): a row
            # view would pin the whole per-batch tup array per entry.
            self._key_tuples.pop(k, None)
            self._key_tuples[k] = tup[i].copy()
        while len(self._key_tuples) > self._KEY_TUPLES_CAP:
            self._key_tuples.pop(next(iter(self._key_tuples)))

    def checkpoint_now(self) -> bool:
        """Drain-ladder hook (Ingester.close): persist the CURRENT
        accumulation unconditionally, cadence ignored — if the final
        window flush below dies mid-shutdown, the next start restores
        this snapshot instead of losing the accumulation. No-op while
        degraded (the host-fallback state is not a device pytree)."""
        with self._state_lock:
            if self._pod is not None:
                # the pod publishes the merged state every epoch and
                # snapshots per shard; there is no single device state
                # to park here
                return False
            if self.checkpointer is None or self.degraded:
                return False
            if self._feed is not None \
                    and not self._feed.drain(timeout=10.0):
                # the window never settled (wedged device / backlogged
                # feed): saving now would snapshot a state the feed is
                # still advancing — possibly donated-dead buffers — and
                # a raise here would abort the caller's drain ladder
                # before the spill rung. Skip the snapshot; the previous
                # one still bounds the loss.
                import logging
                logging.getLogger(__name__).error(
                    "feed drain timed out; shutdown checkpoint skipped")
                return False
            self._snapbus.publish(self.state, self.windows,
                                  tags={"final": True})
            return True

    # -- windows -----------------------------------------------------------
    def flush_window(self, now: Optional[float] = None) -> Optional[
            flow_suite.FlowWindowOutput]:
        now = time.time() if now is None else now
        tr = self._tracer
        if not tr.enabled:
            return self._flush_window_inner(now)
        with tr.span("window", stream=self.wire):
            return self._flush_window_inner(now)

    def _flush_window_inner(self, now: float) -> Optional[
            flow_suite.FlowWindowOutput]:
        t_flush = time.perf_counter()
        if self._pod is not None:
            out = self._flush_pod_window(now)
            self._prof.record("window", "flush",
                              time.perf_counter() - t_flush)
            if out is None:
                return None
            self.last_output = out
            self._write_output(out, int(now))
            return out
        with self._state_lock:
            if self._stager is not None:
                # zero-copy: the open staging prefix ships as-is (slot
                # contiguity — no repack); same put-under-lock shape as
                # _submit_batch_locked, same back-pressure-not-deadlock
                # argument
                for sg in self._stager.flush():
                    self._feed.put(sg, -1)  # lint: disable=emit-under-lock
            else:
                for tb in self.batcher.flush():
                    self._submit_batch_locked(tb)
            if self._feed is not None:
                # barrier: every in-flight prefetched batch applies and
                # fences before the window reads/resets state (feed.py
                # ownership protocol). The feed thread never takes
                # _state_lock, so holding it across the wait is safe.
                if not self._feed.drain(timeout=60.0):
                    import logging
                    logging.getLogger(__name__).error(
                        "feed drain timed out; window flushed against "
                        "a possibly-advancing state")
            self.windows += 1
            was_degraded = self.degraded
            if self.degraded:
                # host fallback window: reduced-fidelity output, then
                # probe the device for recovery
                out = None if self._host is None \
                    else self._host.flush(self.cfg)
                self._rows_at_flush = self.rows_in
                self._probe_device_locked()
            else:
                # checkpoint the PRE-flush state (the window's
                # accumulation): restore replays the window
                # at-least-once; saving post-flush would snapshot a
                # reset state and recover nothing. Cadence: every
                # checkpoint_every-th window, and only if THIS window's
                # accumulation is non-empty (a full npz per idle 1s
                # window is not "low-overhead"). Rows in already-flushed
                # windows need no snapshot — their output reached the
                # store; restart loses at most the current accumulation,
                # bounded by checkpoint_every windows of data.
                dirty = self.rows_in != self._rows_at_flush
                # snapshot bus (ISSUE 7): a disk publish on the PR 4
                # cadence, PLUS a subscriber-only (no npz) publish for
                # every dirty window when the serving cache is listening
                # — its staleness bound is one window, not
                # checkpoint_every windows. No subscribers, no cadence
                # hit => no device_get at all (the pre-ISSUE 7 shape).
                want_disk = (self.checkpointer is not None and dirty
                             and self.windows % self.checkpoint_every == 0)
                if want_disk or (dirty and self._snapbus.has_subscribers()):
                    self._snapbus.publish(
                        self.state, self.windows, wall_time=now,
                        tags={"lossy": self._window_lost_counted},
                        to_disk=want_disk)
                self._rows_at_flush = self.rows_in
                try:
                    self.state, out = self._flush_fn(self.state)
                except RuntimeError:
                    # the window readback itself died on device: same
                    # classification + recovery as a batch failure
                    self._on_device_error_locked(0)
                    out = None
            if self._anomaly is not None:
                # anomaly plane (ISSUE 15): score the settled window
                # BEFORE the audit closes so the detection audit can
                # compare the device verdict against the exact shadow's
                # twin scorer. Publication happens after the lock
                # releases (publish_pending below) — bus subscribers
                # and the exporter fan-out are emissions.
                self._anomaly.close_window(
                    out, now=now, lossy=self._window_lost_counted,
                    degraded=was_degraded)
            if self._audit is not None:
                # accuracy observatory: compare the settled window
                # against the exact shadow AT the window boundary (same
                # lock, after the drain barrier — the shadow and the
                # sketch saw the identical row set). A window with
                # counted loss or on the degraded lane is audited too,
                # tagged instead of alarmed on.
                self._audit.close_window(
                    out, degraded=was_degraded,
                    lossy=self._window_lost_counted,
                    detection=None if self._anomaly is None
                    else self._anomaly.last_entropy_verdict)
            # the lost-window guard resets at the TRUE window boundary —
            # after the flush attempt — so a window where both a
            # replayed batch and the readback die counts ONCE
            self._window_lost_counted = False
        if self._anomaly is not None:
            # NO lock held: alert fan-out + bus publish + gauges
            self._anomaly.publish_pending()
        self._prof.record("window", "flush",
                          time.perf_counter() - t_flush)
        if out is None:
            return None
        self.last_output = out
        self._write_output(out, int(now))
        return out

    def _flush_pod_window(self, now: float) -> Optional[
            flow_suite.FlowWindowOutput]:
        """Pod mode: a window flush IS a merge-epoch close. The state
        lock is held through the deadline-bounded merge so the audit
        shadow and the epoch see the identical row set (the single-chip
        flush holds it through its drain barrier the same way);
        producers back-pressure into the exporter queue's counted
        drop-oldest, never into decode."""
        with self._state_lock:
            for tb in self.batcher.flush():  # lint: disable=emit-under-lock
                self._pod_submit_locked(tb)
            self.windows += 1
            res = self._pod.close_epoch(now=now)
            if self._anomaly is not None:
                # the pod lane scores the MERGED epoch output — in
                # cross-host mode that is the CROSS-HOST merged window,
                # scored once pod-wide, never once per host; the
                # active-flow features read 0 there (shard batches
                # never cross this process's device) and the alert
                # inherits the epoch's participation tags (shard AND
                # host ladders) so a reduced-participation detection
                # says so
                self._anomaly.close_window(
                    res.out, now=now, lossy=res.lossy,
                    degraded=bool(res.degraded),
                    participation={
                        k: res.tags[k]
                        for k in ("pod_shards_participated",
                                  "pod_shards", "pod_missing",
                                  "pod_hosts_participated",
                                  "pod_hosts", "pod_hosts_missing")
                        if k in res.tags})
            if self._audit is not None:
                # epochs that excluded a shard (straggler/kill) or
                # counted loss are tagged lossy/degraded — the accuracy
                # alarm never fires on shard-loss variance (ISSUE 10)
                self._audit.close_window(
                    res.out, degraded=bool(res.degraded),
                    lossy=res.lossy,
                    detection=None if self._anomaly is None
                    else self._anomaly.last_entropy_verdict)
        if self._anomaly is not None:
            self._anomaly.publish_pending()   # NO lock held
        return res.out

    def _write_output(self, out: flow_suite.FlowWindowOutput,
                      second: int) -> None:
        if self.topk_writer is None:
            return
        keys = np.asarray(out.topk_keys)
        counts = np.asarray(out.topk_counts)
        live = counts > 0
        k = int(live.sum())
        if k:
            rows = {
                "timestamp": np.full(k, second, np.uint32),
                "rank": np.arange(k, dtype=np.uint32),
                "flow_key": keys[live].astype(np.uint32),
                "count": np.maximum(counts[live], 0).astype(np.uint32),
            }
            tuples = np.zeros((k, 5), np.uint32)
            for i, key in enumerate(keys[live].astype(np.uint32)):
                t = self._key_tuples.get(int(key))
                if t is not None:
                    tuples[i] = t
            for j, name in enumerate(("ip_src", "ip_dst", "port_src",
                                      "port_dst", "proto")):
                rows[name] = tuples[:, j]
            self.topk_writer.put(rows)
        ent = np.asarray(out.entropies, np.float32)
        card = np.asarray(out.service_cardinality)
        self.window_writer.put({
            "timestamp": np.asarray([second], np.uint32),
            "rows": np.asarray([int(np.asarray(out.rows))], np.uint32),
            "entropy_ip_src": ent[0:1], "entropy_ip_dst": ent[1:2],
            "entropy_port_src": ent[2:3], "entropy_port_dst": ent[3:4],
            "distinct_clients": np.asarray([card.sum()], np.uint32),
        })

    def flush(self) -> None:
        """Drain pending sketch-output rows to disk (Ingester.flush)."""
        for w in (self.topk_writer, self.window_writer):
            if w is not None:
                w.flush()

    def _window_loop(self) -> None:
        while not self._window_stop.wait(self.window_seconds):
            self.flush_window()

    def counters(self) -> dict:
        c = super().counters()
        c.update({"rows_in": self.rows_in, "windows": self.windows,
                  "h2d_bytes": self.h2d_bytes,
                  # coalescing health: transfers vs dispatches vs
                  # batches — a regression back to per-plane puts shows
                  # here (and as the tpu_transfers_per_batch gauge)
                  "h2d_transfers": self.h2d_transfers,
                  "dispatches": self.dispatches,
                  # the zero-copy path batches at the stager, not the
                  # (unused) TensorBatch batcher
                  "batches": (self._stager.staged_batches
                              if self._stager is not None
                              else self.batcher.emitted_batches),
                  # degraded-mode fault domain: every loss is a number
                  "degraded": 1 if self.degraded else 0,
                  "device_errors": self.device_errors,
                  "recoveries": self.recoveries,
                  "lost_windows": self.lost_windows,
                  "lost_rows": self.lost_rows,
                  "host_rows": self.host_rows})
        # staged-update admission skips (flow_suite.make_staged_update):
        # bounded data loss that must show in deepflow_system, not logs.
        # _update only exists on the staged/lanes wires — the dict wire
        # has hits/news programs instead, and reading through it raised
        # AttributeError here, which StatsRegistry.collect swallowed:
        # the whole tpu_sketch Countable silently vanished from scrapes
        failures = getattr(getattr(self, "_update", None),
                           "admission_failures", None)
        if failures is not None:
            c["ring_admission_failures"] = failures
        if self._feed is not None:
            c.update(self._feed.counters())
        if self._pod is not None:
            # pod fault-domain ledger: shard states, epoch merges and
            # the pod-wide conservation terms (sent = delivered + host
            # + lost + pending), all scrape-visible
            c.update(self._pod.counters())
        if self._stager is not None:
            # zero-copy staging health: groups/batches staged, buffer
            # pool reuse, and the sharded pack pool's task counts
            c["zero_copy"] = 1
            c.update(self._stager.counters())
        # the snapshot bus is always live (in-process-only without a
        # checkpoint_dir): saves/restores plus the ISSUE 7 pub/sub and
        # restored-step attribution counters
        c.update(self._snapbus.counters())
        if self._audit is not None:
            # headline verdicts only — the full family is the separate
            # `tpu_sketch_accuracy` Countable (runtime/audit.py)
            c["audit_alarm"] = 1 if self._audit.alarm else 0
            c["audit_windows"] = self._audit.windows
        if self._anomaly is not None:
            # headline conservation terms only — the full family is
            # the separate `anomaly` Countable (anomaly/alerts.py);
            # rows_seen here against rows_in above is the detection
            # lane's conservation check in ONE scrape
            c["anomaly_rows_seen"] = self._anomaly.rows_seen
            c["anomaly_alerts"] = sum(self._anomaly.alerts_total)
            c["anomaly_windows_unscored"] = \
                self._anomaly.windows_unscored
        return c
