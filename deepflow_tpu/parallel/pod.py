"""Pod fault domains: epoch-merged mergeable sketches, one fault domain per shard.

The mesh lane (`parallel/sharded.py`) runs every shard inside ONE jitted
`shard_map` program: a single device error kills the whole pod's update,
a slow host stalls every merge collective, and a lost host silently
shrinks the merged sketch.  This module is the fault-domained form of
the same math — it exists because the sketches are MERGEABLE (CMS add,
HLL max, histogram add, ring re-top-k), so nothing forces the shards
into one failure domain:

- each shard owns ONE device, its own shard-local ``FlowSuiteState``,
  its own supervised worker thread (deadman beats via
  ``runtime/supervisor.py``) and its own bounded ingest queue — a slow
  or dead shard back-pressures/drops COUNTED on its own queue and never
  blocks ingest on the surviving shards;
- a **merge epoch** closes with whatever shards made
  ``merge_deadline_s``: each shard's contribution is a host-side copy of
  its state (taken at the epoch marker riding its own queue, so epoch
  membership is exact), the merge is the same
  ``_merge_axis0`` + ring-rescore + ``flush`` the mesh lane runs (one
  jitted program over the stacked contributions), and a straggler past
  the deadline is EXCLUDED — counted in ``pod_merge_missed`` /
  ``pod_rows_excluded`` — not awaited.  Its late contribution merges
  into the NEXT epoch (mergeable sketches make late delivery exact,
  never double-counted);
- each shard carries the PR 2 degraded ladder privately: a
  device-classified error rolls THAT shard back from its latest
  snapshot on the bus (<= one snapshot cadence of rows lost, counted),
  and past ``degrade_after`` consecutive errors the shard drops to the
  ``_HostSketch`` fallback while the rest of the pod keeps merging;
- a killed shard (``shard.lost`` fault / :meth:`kill`) **rejoins by
  snapshot**: at the next epoch boundary the coordinator restores the
  shard's last bus snapshot — its un-merged accumulation survives the
  kill as a late contribution (delivered, not lost) — and the shard
  re-enters with fresh state.  Only rows past the last snapshot are
  lost, and they are counted.

The POD-MERGED state is published to a ``runtime/snapbus.py`` bus every
epoch with shard-participation tags (``pod_shards_participated``,
``pod_missing``, ``pod_degraded``, ``lossy``), so ``serving/`` reads
survive shard loss honestly — a reduced-participation answer says so
instead of silently serving a partial sketch.

Conservation (the PR 4 invariant, pod-wide)::

    rows_sent == rows_delivered + rows_host + rows_lost + pending_rows()

holds at every instant under the ledger lock, through device errors,
straggler exclusion, kill and rejoin.  ``tests/test_pod.py`` drives it
to ``pending_rows() == 0`` and asserts equality.

Wire support: the **lanes** wire (the production pod wire — the PR 8
zero-copy staging direction) carries the full fault ladder.  The
**dict** wire is supported for fault-free operation and bit-identity
with the mesh lane (replicated news + interleaved count masks, sharded
hits); its device errors mark the shard LOST with rows counted — the
dictionary's host/device index agreement cannot survive a mid-stream
table reset without the packer rebuild the single-chip lane does (see
the wire='dict' note in runtime/tpu_sketch.py).

Bit-identity: with no faults injected and every shard on time, the
epoch-merged output equals the mesh lane's merged flush leaf-for-leaf
on both wires — asserted in tests/test_pod.py.  The per-shard update is
literally the same ``flow_suite.update`` / ``flow_dict.update_*`` call
over the same slice with the same mask arithmetic, and the merge is the
same stacked-state program ``ShardedFlowSuite`` flushes through.
"""

from __future__ import annotations

import logging
import queue as _queue
import threading
import time
import uuid
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from deepflow_tpu.models import flow_suite
from deepflow_tpu.models.flow_suite import FlowSuiteConfig, FlowWindowOutput
from deepflow_tpu.runtime.faults import (
    FAULT_MERGE_STALL,
    FAULT_SHARD_DEVICE_ERROR,
    FAULT_SHARD_LOST,
    default_faults,
)
from deepflow_tpu.runtime.snapbus import SnapshotBus
from deepflow_tpu.runtime.supervisor import default_supervisor
from deepflow_tpu.runtime.tracing import default_tracer

__all__ = ["PodFlowSuite", "EpochResult"]

_LOG = logging.getLogger(__name__)

# shard lifecycle: ACTIVE shards ingest on device; DEGRADED shards
# absorb on the host fallback (lanes wire) until a probe recovers the
# device; LOST shards accept nothing (drops counted) until rejoin
ACTIVE = "active"
DEGRADED = "degraded"
LOST = "lost"

class _Contribution(NamedTuple):
    """One shard's epoch contribution: host-side state leaves (device
    contributions) or a reduced-fidelity host window output (degraded
    shards — participation evidence, never merged into the sketch)."""

    shard: int
    epoch: int
    rows: int
    leaves: Optional[Tuple[np.ndarray, ...]]     # None = host (degraded)
    host_out: Optional[FlowWindowOutput] = None
    late: bool = False


class EpochResult(NamedTuple):
    """What one closed merge epoch produced."""

    epoch: int
    out: Optional[FlowWindowOutput]   # merged window output (None: empty)
    tags: Dict[str, Any]              # the published participation tags
    participated: List[int]           # shards whose contribution merged
    missed: List[int]                 # expected but past the deadline
    degraded: List[int]               # shards on the host fallback
    lost: List[int]                   # shards currently LOST
    merged_rows: int                  # rows in the merged output
    host_outputs: List[Tuple[int, FlowWindowOutput]]
    lossy: bool                       # exclusion, counted loss, or a
    #                                   late merge this epoch


class _Shard:
    """One pod fault domain: device, state, queue, worker, ledger."""

    def __init__(self, idx: int, device, bus: SnapshotBus,
                 queue_batches: int) -> None:
        self.idx = idx
        self.device = device
        self.bus = bus                     # per-shard snapshot bus
        self.q: _queue.Queue = _queue.Queue(maxsize=queue_batches)
        self.status = ACTIVE
        self.handle = None                 # supervisor ThreadHandle
        self.stop_ev: Optional[threading.Event] = None   # per-spawn
        self.state = None                  # device FlowSuiteState
        self.dtable = None                 # dict wire: device key table
        # ledger (ints mutated under the pod ledger lock)
        self.qrows = 0                     # valid rows sitting in q
        self.active_rows = 0               # rows in the worker's hands
        self.rows_epoch = 0                # rows in the current device state
        self.snap_rows = 0                 # rows covered by the last snapshot
        self.gen = 0                       # bumped per contribution taken
        self.contrib_inflight = 0          # device_get'd, not yet posted
        self.restorable_rows = 0           # LOST: rows a rejoin can recover
        self.rows_in = 0
        self.rows_dropped = 0
        self.rows_lost = 0
        self.host_rows = 0
        self.device_errors = 0
        self.recoveries = 0
        self.consecutive_errors = 0
        self.last_contributed_epoch = -1
        self.marker_rows = 0               # epoch membership at marker post
        self.batches_since_snapshot = 0
        self._host = None                  # _HostSketch when degraded


class PodFlowSuite:
    """The pod fault-domain layer over N single-device shard lanes.

    ``put_lanes(plane, n)`` / ``put_wire(wire)`` partition a batch
    exactly the way the mesh lane shards it (contiguous blocks on the
    batch axis; interleaved count masks for dict news), so per-shard
    states match the mesh's per-device partials bit-for-bit.
    ``close_epoch()`` runs the deadline-bounded merge.  With ``epoch_s``
    set, a supervised merge thread closes epochs on a timer.
    """

    def __init__(self, cfg: FlowSuiteConfig,
                 n_shards: Optional[int] = None,
                 wire: str = "lanes", *,
                 dict_capacity: int = 1 << 16,
                 merge_deadline_s: float = 5.0,
                 epoch_s: Optional[float] = None,
                 degrade_after: int = 2,
                 host_stride: int = 4,
                 snapshot_dir: Optional[str] = None,
                 snapshot_batches: int = 8,
                 queue_batches: int = 64,
                 auto_rejoin: bool = True,
                 name: str = "pod") -> None:
        if wire not in ("lanes", "dict"):
            raise ValueError(f"wire must be 'lanes' or 'dict', got {wire!r}")
        devices = jax.devices()
        self.n_shards = len(devices) if n_shards is None \
            else min(int(n_shards), len(devices))
        if self.n_shards < 1:
            raise ValueError("pod needs at least one device")
        self.cfg = cfg
        self.wire = wire
        self.merge_deadline_s = float(merge_deadline_s)
        self.degrade_after = int(degrade_after)
        self.host_stride = int(host_stride)
        self.snapshot_batches = max(1, int(snapshot_batches))
        self.auto_rejoin = bool(auto_rejoin)
        self.name = name
        # the POD-MERGED bus serving/ subscribes to, plus one bus per
        # shard for rollback snapshots + rejoin-by-snapshot. One
        # directory, distinct names — snapbus filenames never collide.
        self.bus = SnapshotBus(snapshot_dir, name=name)
        self._shards: List[_Shard] = [
            _Shard(i, devices[i],
                   SnapshotBus(snapshot_dir, name=f"{name}-shard{i}"),
                   queue_batches)
            for i in range(self.n_shards)]
        # resume the epoch counter past a prior run's disk snapshots,
        # else new merged publishes sort below the stale files and the
        # bus GC eats the NEW run's snapshots while reads serve the old
        # run's sketch (the single-chip lane resumes `windows` the same
        # way)
        last = self.bus.latest_step()
        self.epoch = 0 if last is None else last + 1
        # per-incarnation nonce on shard snapshots: with a disk-backed
        # bus, latest() falls back to a PRIOR process's snapshots —
        # restoring one would risk double-merging rows the dead run
        # already delivered (its gen ledger died with it), so a restart
        # loses at most the open epoch's per-shard accumulation instead
        self._run_id = uuid.uuid4().hex
        self._ledger = threading.Lock()
        # serializes close_epoch against itself: the epoch_s timer
        # thread and a direct close()/flush call must never interleave
        # marker posts and _pending swaps for the same epoch
        self._close_lock = threading.Lock()
        self._pending: List[_Contribution] = []
        self._merge_inflight = 0           # taken-but-unmerged rows
        # pod-level ledger (mutated under _ledger)
        self.rows_sent = 0
        self.rows_delivered = 0
        self.rows_host = 0
        self.rows_lost = 0
        self.rows_excluded = 0
        self.merges = 0
        self.epochs = 0
        self.merge_missed = 0
        self.rejoins = 0
        self.late_merges = 0
        self.last_merge_s = 0.0
        self._faults = default_faults()
        self._tracer = default_tracer()
        self._auditor = None
        self._lossy_epoch = False          # counted loss since last close
        template = flow_suite.init(cfg)
        self._treedef = jax.tree_util.tree_structure(template)
        self._leaf_shapes = [x.shape for x in
                             jax.tree_util.tree_leaves(template)]
        # flatten index of rows_seen, derived (not hard-coded) so a
        # FlowSuiteState layout change cannot silently misread a leaf
        # as the contribution row count
        sentinel = np.int32(-1)
        marked = jax.tree_util.tree_leaves(
            template._replace(rows_seen=sentinel))
        self._rows_leaf = next(i for i, x in enumerate(marked)
                               if x is sentinel)
        nd = self.n_shards
        cfg_ = cfg

        # -- per-shard programs (the mesh body, minus shard_map) -----------
        # mask arithmetic mirrors sharded.local_update_lanes: global
        # position = arange(b) + shard_offset, valid iff < n. Same
        # values, same flow_suite.update — per-shard state equals the
        # mesh lane's per-device partial bit-for-bit.
        def _upd_lanes(s, p, off, n):
            lanes = {"ip_src": p[0], "ip_dst": p[1],
                     "ports": p[2], "proto_pkts": p[3]}
            mask = (jnp.arange(p.shape[1], dtype=jnp.uint32) + off) < n
            return flow_suite.update(s, flow_suite.unpack_lanes(lanes),
                                     mask, cfg_)

        self._upd_lanes = jax.jit(_upd_lanes, donate_argnums=0)
        if wire == "dict":
            from deepflow_tpu.models import flow_dict as _fd
            self._fd = _fd
            self._dict_capacity = int(dict_capacity)

            def _upd_news(s, table, p, n, shard_idx):
                rows = jnp.arange(p.shape[1], dtype=jnp.uint32)
                count = (rows < n) & (rows % jnp.uint32(nd) == shard_idx)
                st, ts = _fd.update_news(
                    s, _fd.FlowDictState(table=table), p, n, cfg_,
                    count_mask=count)
                return st, ts.table

            def _upd_hits(s, table, p, off_pairs, n):
                hp = p.shape[1]
                pos_a = jnp.arange(hp, dtype=jnp.uint32) + off_pairs
                gmask = jnp.concatenate(
                    [pos_a, pos_a + jnp.uint32(hp * nd)]) < n
                return _fd.update_hits(
                    s, _fd.FlowDictState(table=table), p, n, cfg_,
                    mask=gmask)

            self._upd_news = jax.jit(_upd_news, donate_argnums=(0, 1))
            self._upd_hits = jax.jit(_upd_hits, donate_argnums=0)
        self._merge_progs: Dict[int, Any] = {}
        for sh in self._shards:
            self._init_shard_state(sh)
            self._spawn_worker(sh)
        self._merge_handle = None
        self._merge_stop = threading.Event()
        if epoch_s is not None:
            period = float(epoch_s)

            def _merge_loop() -> None:
                while not self._merge_stop.wait(period):
                    default_supervisor().beat()
                    self.close_epoch()

            self._merge_handle = default_supervisor().spawn(
                f"{name}-merge", _merge_loop, beat_period_s=period)

    # -- construction helpers ----------------------------------------------
    def _init_shard_state(self, sh: _Shard) -> None:
        sh.state = jax.device_put(flow_suite.init(self.cfg), sh.device)
        if self.wire == "dict":
            sh.dtable = jax.device_put(
                jnp.zeros((4, self._dict_capacity), jnp.uint32), sh.device)

    def _spawn_worker(self, sh: _Shard) -> None:
        # each spawn gets its OWN stop event, captured by the closure:
        # stopping is per-worker-generation, so a replacement spawned at
        # rejoin can never be halted by (or race) its predecessor's stop
        ev = threading.Event()
        sh.stop_ev = ev
        sh.handle = default_supervisor().spawn(
            f"{self.name}-shard-{sh.idx}", lambda: self._worker(sh, ev))

    def attach_auditor(self, auditor) -> None:
        """Attach a ShadowAuditor (runtime/audit.py): host batches are
        mirrored at ``put_lanes`` (the unpack twin of the staged plane)
        and the audit closes against the MERGED epoch output with
        ``lossy``/``degraded`` tags whenever the epoch excluded a shard
        or counted loss — so the accuracy alarm can never fire on
        shard-loss variance, and the audit's rows_in conservation keeps
        counting excluded rows (the shadow saw them; the tags say the
        sketch did not). Lanes wire only."""
        self._auditor = auditor

    # -- ingest (producer side; never blocks on a slow shard) --------------
    def put_lanes(self, plane: np.ndarray, n: int) -> None:
        """One (4, B) packed-lane plane with n valid rows, B divisible
        by n_shards.  Shard i consumes columns [i*b, (i+1)*b) with the
        mesh lane's global-position mask.  Takes ownership of `plane`
        (shards keep views); pass a freshly packed buffer."""
        if self.wire != "lanes":
            raise ValueError("put_lanes on a dict-wire pod")
        b = plane.shape[1] // self.n_shards
        if b * self.n_shards != plane.shape[1]:
            raise ValueError(
                f"batch width {plane.shape[1]} not divisible by "
                f"{self.n_shards} shards")
        n = int(n)
        with self._ledger:
            # absorb + booking + enqueue are ONE atomic step vs
            # close_epoch's marker post: a marker landing between the
            # shadow absorbing a batch and its slices reaching the
            # shard queues would push the batch into the NEXT epoch's
            # merge while this window's shadow holds it (an untagged
            # audit mismatch), and a concurrent counters() scrape must
            # never see the sent side of a batch without its pending
            # side
            if self._auditor is not None and n:
                self._auditor.absorb(
                    flow_suite.unpack_lanes_np(plane, n))
            self.rows_sent += n
            for sh in self._shards:
                off = sh.idx * b
                valid = max(0, min(b, n - off))
                if self._book_locked(sh, valid):
                    self._enqueue_locked(
                        sh, ("lanes", plane[:, off:off + b], off, n),
                        valid)

    def put_wire(self, wire: List[Tuple[str, np.ndarray, int]]) -> None:
        """A flow_dict wire sequence [(kind, plane, n), ...] in emission
        order: news planes replicate to every shard (each record COUNTED
        by exactly one, interleaved like the mesh lane), hits planes
        shard on the pairs axis."""
        if self.wire != "dict":
            raise ValueError("put_wire on a lanes-wire pod")
        nd = self.n_shards
        for kind, plane, n in wire:
            n = int(n)
            if kind == "news":
                with self._ledger:
                    self.rows_sent += n
                    for sh in self._shards:
                        counted = len(range(sh.idx, n, nd))
                        if self._book_locked(sh, counted):
                            self._enqueue_locked(
                                sh, ("news", plane, n), counted)
            else:
                hp = plane.shape[1] // nd
                if hp * nd != plane.shape[1]:
                    raise ValueError(
                        f"hits width {plane.shape[1]} not divisible by "
                        f"{nd} shards")
                with self._ledger:
                    self.rows_sent += n
                    for sh in self._shards:
                        off = sh.idx * hp
                        valid = max(0, min(hp, n - off)) \
                            + max(0, min(hp, n - (hp * nd + off)))
                        if self._book_locked(sh, valid):
                            self._enqueue_locked(
                                sh, ("hits", plane[:, off:off + hp],
                                     off, n), valid)

    def _book_locked(self, sh: _Shard, rows: int) -> bool:
        """Ledger booking for one shard's slice (ledger lock held):
        True when the slice should enqueue, False when the shard is
        LOST (drop counted)."""
        sh.rows_in += rows
        if sh.status == LOST:
            sh.rows_dropped += rows
            sh.rows_lost += rows
            self.rows_lost += rows
            self._lossy_epoch = self._lossy_epoch or rows > 0
            return False
        sh.qrows += rows
        return True

    def _enqueue_locked(self, sh: _Shard, item: tuple,
                        rows: int) -> None:
        """Non-blocking enqueue of a booked slice (ledger lock held —
        put_nowait cannot block or re-enter, hence the justified
        pragma; keeping booking and enqueue atomic means an epoch
        marker can never land between them and split a batch's shadow
        absorb from its merge epoch); a full queue (straggler
        back-pressure) drops COUNTED — ingest on the surviving shards
        never blocks on this one."""
        try:
            sh.q.put_nowait(item + (rows,))  # lint: disable=emit-under-lock
        except _queue.Full:
            sh.qrows -= rows
            sh.rows_dropped += rows
            sh.rows_lost += rows
            self.rows_lost += rows
            self._lossy_epoch = self._lossy_epoch or rows > 0

    # -- shard worker -------------------------------------------------------
    def _worker(self, sh: _Shard, stop_ev: threading.Event) -> None:
        sup = default_supervisor()
        while not stop_ev.is_set():
            try:
                item = sh.q.get(timeout=0.2)
            except _queue.Empty:
                sup.beat()
                continue
            sup.beat()
            kind = item[0]
            if kind == "epoch":
                self._contribute(sh, item[1])
                continue
            rows = item[-1]
            with self._ledger:
                # queued -> active, never a gap: pending_rows() must not
                # observe a transient undercount while a batch compiles
                # or updates (the drain-ladder discipline feed.py keeps)
                sh.qrows -= rows
                sh.active_rows = rows
                if sh.status == LOST:
                    # killed while this item sat queued: counted, done
                    sh.active_rows = 0
                    sh.rows_lost += rows
                    self.rows_lost += rows
                    continue
            if self._faults.enabled and self._faults.should_fire(
                    FAULT_SHARD_LOST, key=f"shard{sh.idx}:lost"):
                # simulated host loss: the worker dies mid-epoch; rows
                # past the last snapshot are lost (counted), snapshotted
                # rows stay restorable for the rejoin
                self._mark_lost(sh, extra_rows=rows)
                return
            if sh.status == DEGRADED:
                self._absorb_host(sh, item, rows)
                continue
            try:
                self._apply_device(sh, item, rows)
            except RuntimeError:
                # XlaRuntimeError (device loss/preemption) subclasses
                # RuntimeError — same classification as the single-chip
                # lane; anything else is a bug that must crash into the
                # supervisor with its rows counted first
                self._on_device_error(sh, rows)
            except Exception:
                with self._ledger:
                    sh.active_rows = 0
                    sh.rows_lost += rows
                    self.rows_lost += rows
                    self._lossy_epoch = True
                raise

    def _apply_device(self, sh: _Shard, item: tuple, rows: int) -> None:
        if self._faults.enabled:
            self._faults.maybe_raise(FAULT_SHARD_DEVICE_ERROR,
                                     key=f"shard{sh.idx}:update")
        kind = item[0]
        if kind == "lanes":
            _, plane, off, n, _ = item
            p = jax.device_put(np.ascontiguousarray(plane), sh.device)
            sh.state = self._upd_lanes(sh.state, p, jnp.uint32(off),
                                       jnp.uint32(n))
        elif kind == "news":
            _, plane, n, _ = item
            p = jax.device_put(np.ascontiguousarray(plane), sh.device)
            sh.state, sh.dtable = self._upd_news(
                sh.state, sh.dtable, p, jnp.uint32(n), jnp.uint32(sh.idx))
        else:  # hits
            _, plane, off, n, _ = item
            p = jax.device_put(np.ascontiguousarray(plane), sh.device)
            sh.state = self._upd_hits(sh.state, sh.dtable, p,
                                      jnp.uint32(off), jnp.uint32(n))
        with self._ledger:
            sh.active_rows = 0
            if sh.status == LOST:
                # killed mid-update: the state is about to be discarded,
                # so these rows are loss, not accumulation
                sh.rows_lost += rows
                self.rows_lost += rows
                return
            sh.rows_epoch += rows
            sh.consecutive_errors = 0
        sh.batches_since_snapshot += 1
        if sh.batches_since_snapshot >= self.snapshot_batches:
            self._snapshot_shard(sh)

    def _snapshot_shard(self, sh: _Shard) -> None:
        """Mid-epoch rollback point: the shard's partial state goes to
        its bus tagged with the epoch, so a device error (or kill) loses
        at most ``snapshot_batches`` batches of this shard's slice."""
        sh.bus.publish(sh.state, step=self.epoch,
                       tags={"epoch": self.epoch, "rows": sh.rows_epoch,
                             "gen": sh.gen, "run": self._run_id},
                       to_disk=sh.bus.directory is not None)
        with self._ledger:
            sh.snap_rows = sh.rows_epoch
        sh.batches_since_snapshot = 0

    def _absorb_host(self, sh: _Shard, item: tuple, rows: int) -> None:
        """Degraded shard: reduced-rate host fallback (lanes only; the
        mesh-shaped slice unpacks through the np twin)."""
        if item[0] != "lanes":
            with self._ledger:       # dict wire: no host twin — counted
                sh.active_rows = 0
                sh.rows_lost += rows
                self.rows_lost += rows
                self._lossy_epoch = True
            return
        _, plane, off, n, _ = item
        valid = max(0, min(plane.shape[1], int(n) - int(off)))
        if valid:
            if sh._host is None:
                from deepflow_tpu.runtime.tpu_sketch import _HostSketch
                sh._host = _HostSketch(self.cfg, stride=self.host_stride)
            sh._host.update(flow_suite.unpack_lanes_np(plane, valid))
        with self._ledger:
            sh.active_rows = 0
            sh.host_rows += rows
            self.rows_host += rows

    def _on_device_error(self, sh: _Shard, batch_rows: int) -> None:
        """Shard-scoped rollback: restore THIS shard from its latest
        same-epoch bus snapshot; only rows past the snapshot (plus the
        failed batch) are lost.  Past degrade_after consecutive errors
        the shard drops to the host fallback (lanes wire) or LOST (dict
        wire) while the rest of the pod keeps merging."""
        sh.device_errors += 1
        sh.consecutive_errors += 1
        _LOG.exception("%s shard %d device error #%d (consecutive %d)",
                       self.name, sh.idx, sh.device_errors,
                       sh.consecutive_errors)
        if self.wire == "dict":
            self._mark_lost(sh, extra_rows=batch_rows)
            return
        restored_rows = 0
        try:
            restored = self._restore_from_bus(sh)
            if restored is not None:
                sh.state, restored_rows = restored
            else:
                self._init_shard_state(sh)
        except Exception:
            # the device can't even hold a state: degrade now
            sh.consecutive_errors = self.degrade_after
            restored_rows = 0
        with self._ledger:
            sh.active_rows = 0
            lost = sh.rows_epoch - restored_rows + batch_rows
            sh.rows_lost += lost
            self.rows_lost += lost
            sh.rows_epoch = restored_rows
            sh.snap_rows = restored_rows
            self._lossy_epoch = True
        sh.batches_since_snapshot = 0
        if sh.consecutive_errors >= self.degrade_after:
            with self._ledger:
                sh.status = DEGRADED
            _LOG.warning("%s shard %d degraded: host fallback at 1/%d "
                         "rate", self.name, sh.idx, self.host_stride)

    def _restore_from_bus(self, sh: _Shard
                          ) -> Optional[Tuple[Any, int]]:
        """(device state, rows) from the shard's latest bus snapshot —
        only if no contribution was taken since it was written (its
        ``gen`` tag matches): a pre-contribution snapshot's rows were
        already posted for merge, and resurrecting them would
        double-count AND drive the loss ledger negative.  The one
        sanctioned device round-trip of the rollback path."""
        snap = sh.bus.latest()
        if snap is None or snap.tags.get("run") != self._run_id \
                or snap.tags.get("gen") != sh.gen \
                or len(snap.leaves) != len(self._leaf_shapes):
            return None
        if any(a.shape != s for a, s in zip(snap.leaves,
                                            self._leaf_shapes)):
            return None
        state = jax.device_put(
            jax.tree_util.tree_unflatten(
                self._treedef, [jnp.asarray(a) for a in snap.leaves]),
            sh.device)
        if self.wire == "dict":
            sh.dtable = jax.device_put(
                jnp.zeros((4, self._dict_capacity), jnp.uint32),
                sh.device)
        return state, int(snap.tags.get("rows", 0))

    def _mark_lost(self, sh: _Shard, extra_rows: int = 0) -> None:
        # trust the BUS for the restorable row count, not the booked
        # snap_rows: a kill racing _snapshot_shard between its publish
        # and its ledger update would otherwise count the newest
        # snapshot's extra rows lost here AND deliver them at rejoin
        snap = sh.bus.latest()
        snap_rows = sh.snap_rows
        if snap is not None and snap.tags.get("run") == self._run_id \
                and snap.tags.get("gen") == sh.gen:
            snap_rows = max(snap_rows, int(snap.tags.get("rows", 0)))
        with self._ledger:
            if extra_rows:               # the item in the worker's hands
                sh.active_rows = 0
            lost = sh.rows_epoch - snap_rows + extra_rows
            sh.rows_lost += lost
            self.rows_lost += lost
            sh.restorable_rows = snap_rows
            sh.rows_epoch = 0
            sh.snap_rows = 0
            sh.status = LOST
            self._lossy_epoch = True
        _LOG.warning("%s shard %d LOST (%d rows counted lost, %d "
                     "restorable from its snapshot)", self.name, sh.idx,
                     lost, sh.restorable_rows)

    # -- contribution (worker side of the epoch protocol) -------------------
    def _contribute(self, sh: _Shard, epoch: int) -> None:
        """The shard reached epoch `epoch`'s marker on its own queue:
        hand the coordinator a host-side copy of the shard state and
        reset for the next epoch.  The sanctioned device sync of the
        epoch path (one device_get per shard per epoch).  The
        ``merge.stall`` fault fires between the copy and the post — a
        stalled shard misses the deadline but its rows deliver late."""
        degraded = sh.status == DEGRADED
        host_out = None
        if degraded and sh._host is not None:
            host_out = sh._host.flush(self.cfg)
        # a degraded shard may still hold device rows it restored from
        # its snapshot before the degrade — they contribute too, or
        # conservation would strand them in a state nothing ever merges
        leaves = None
        rows = 0
        if not degraded or sh.rows_epoch > 0:
            try:
                leaves = tuple(np.asarray(x) for x in jax.device_get(
                    jax.tree_util.tree_leaves(sh.state)))
            except RuntimeError:
                # device lost at the epoch sync: the same ladder as a
                # failed update — roll back from the gen-matching
                # snapshot (or degrade); this shard reads as missed and
                # its restored rows contribute next epoch
                self._on_device_error(sh, 0)
                if host_out is None:
                    return
            if leaves is not None:
                rows = int(leaves[self._rows_leaf])
                with self._ledger:
                    if sh.status == LOST:
                        # killed while the copy was in flight:
                        # _mark_lost already counted these rows;
                        # posting would double-count them as delivered
                        # AND bumping gen would orphan the snapshot the
                        # rejoin restores
                        return
                    if rows != sh.rows_epoch:
                        _LOG.error(
                            "%s shard %d ledger drift: device rows_seen "
                            "%d != tracked %d", self.name, sh.idx, rows,
                            sh.rows_epoch)
                    sh.contrib_inflight = rows
                    sh.rows_epoch = 0
                    sh.snap_rows = 0
                    # invalidate pre-contribution bus snapshots: their
                    # rows are in this contribution; restoring one after
                    # this point would merge them twice
                    sh.gen += 1
                sh.batches_since_snapshot = 0
                # reset the sketch state only — the dict wire's key
                # table persists across epochs (the packer's announced
                # indices live there; the mesh lane never resets it
                # either)
                try:
                    sh.state = jax.device_put(flow_suite.init(self.cfg),
                                              sh.device)
                except RuntimeError:
                    # the copied contribution is intact on the host, but
                    # the device refused a fresh state: degrade NOW so
                    # the stale device state (whose rows are in this
                    # contribution) can never be contributed twice
                    sh.device_errors += 1
                    with self._ledger:
                        sh.consecutive_errors = self.degrade_after
                        sh.status = DEGRADED
                        self._lossy_epoch = True
                    _LOG.exception(
                        "%s shard %d degraded: state reset failed after "
                        "contribution copy", self.name, sh.idx)
                    degraded = True
        if self._faults.enabled:
            # site keys are namespaced `shardN:<site>` so `match=shardN:`
            # targets exactly one domain even on pods with >= 10 shards
            # (fault matching is substring: bare `shard1` also hits
            # shard12); bare `match=shardN` still works on small pods
            self._faults.maybe_stall(FAULT_MERGE_STALL,
                                     key=f"shard{sh.idx}:stall")
        with self._ledger:
            self._pending.append(
                _Contribution(sh.idx, epoch, rows, leaves,
                              host_out=host_out))
            sh.contrib_inflight = 0
            sh.last_contributed_epoch = epoch
        if degraded:
            self._probe_device(sh)

    def _probe_device(self, sh: _Shard) -> bool:
        """Degraded-shard recovery probe at the epoch boundary: a tiny
        device round-trip; healthy -> fresh state, back to ACTIVE (the
        host tallies were flushed as this epoch's reduced-fidelity
        contribution)."""
        try:
            if self._faults.enabled:
                self._faults.maybe_raise(FAULT_SHARD_DEVICE_ERROR,
                                         key=f"shard{sh.idx}:probe")
            probe = jax.device_put(jnp.ones(8, jnp.uint32), sh.device)
            if int(probe.sum()) != 8:
                return False
            self._init_shard_state(sh)
        except Exception:
            return False
        with self._ledger:
            sh.status = ACTIVE
            sh.consecutive_errors = 0
            sh.recoveries += 1
            sh._host = None
        _LOG.warning("%s shard %d recovered: back on device", self.name,
                     sh.idx)
        return True

    # -- the merge epoch (coordinator) --------------------------------------
    def close_epoch(self, now: Optional[float] = None,
                    deadline_s: Optional[float] = None) -> EpochResult:
        """Close the current merge epoch: post the epoch marker on every
        live shard's queue (so epoch membership is exactly "rows
        enqueued before this call"), wait up to the deadline, merge
        whatever contributions are in, count the rest.  LOST shards are
        rejoined at this boundary when auto_rejoin is on."""
        with self._close_lock:
            return self._close_epoch_serialized(now, deadline_s)

    def _close_epoch_serialized(self, now: Optional[float],
                                deadline_s: Optional[float]
                                ) -> EpochResult:
        # holds _close_lock (coordinator serialization), NOT _ledger —
        # marker puts and the deadline wait must not starve the workers
        t0 = time.perf_counter()
        ep = self.epoch
        with self._ledger:
            # dirty gating (the single-chip lane's idle-window shape):
            # a pod with nothing queued, nothing accumulated, nothing
            # pending, every shard healthy and no loss to tag skips the
            # epoch entirely — no per-shard device_get, no merge
            # program, no bus publish, every window, forever, at 0 rows
            idle = (not self._pending and not self._lossy_epoch
                    and all(sh.status == ACTIVE and sh.qrows == 0
                            and sh.active_rows == 0
                            and sh.rows_epoch == 0
                            and sh.contrib_inflight == 0
                            for sh in self._shards))
        if idle:
            return EpochResult(ep, None, {}, [], [], [], [], 0, [],
                               False)
        with self._ledger:
            expected = [sh.idx for sh in self._shards
                        if sh.status in (ACTIVE, DEGRADED)]
            lost_now = [sh.idx for sh in self._shards
                        if sh.status == LOST]
        with self._ledger:
            # every marker posts inside ONE ledger section, atomic vs
            # put_lanes/put_wire's book+enqueue: a batch is wholly
            # before or wholly after this epoch on EVERY shard (never
            # split across epochs under the audit shadow), and each
            # marker_rows membership snapshot — rows in the shard's
            # pipeline at its marker — is exact. Rows arriving during
            # the deadline wait belong to the NEXT epoch and never
            # inflate this epoch's exclusion count.
            for sh in self._shards:
                if sh.idx in expected:
                    sh.marker_rows = (sh.qrows + sh.active_rows
                                      + sh.rows_epoch
                                      + sh.contrib_inflight)
                    try:
                        sh.q.put_nowait(("epoch", ep))  # lint: disable=emit-under-lock
                    except _queue.Full:
                        # a full queue is already a deep straggler: the
                        # shard reads as missed and merges late
                        pass
        deadline = time.monotonic() + (self.merge_deadline_s
                                       if deadline_s is None
                                       else float(deadline_s))
        while time.monotonic() < deadline:
            with self._ledger:
                got = {c.shard for c in self._pending if c.epoch == ep}
            if set(expected) <= got:
                break
            time.sleep(0.002)
        with self._ledger:
            take, self._pending = self._pending, []
            # the lossy flag is snapped HERE, at the contribution take,
            # not before the markers: loss counted while shards drain
            # THIS epoch's backlog during the deadline wait belongs to
            # this epoch's published window, or the accuracy alarm sees
            # an untagged mismatch (shard-loss variance, not error)
            lossy = self._lossy_epoch
            self._lossy_epoch = False
            # taken contributions stay ledger-visible through the merge
            # (pending_rows() must never transiently undercount them)
            self._merge_inflight = sum(c.rows for c in take
                                       if c.leaves is not None)
            got = {c.shard for c in take if c.epoch == ep}
            missed = [i for i in expected if i not in got]
            for i in missed:
                sh = self._shards[i]
                self.merge_missed += 1
                # CUMULATIVE row-epoch exclusions: rows this epoch's
                # merged answer was missing at close — the membership
                # snapshot taken at marker post, NOT the live pipeline
                # (which also holds next-epoch rows under live ingest).
                # The rows are not lost — they merge late
                # (pod_late_merges, delivered) — this counts how much
                # any published answer undercounted.
                self.rows_excluded += sh.marker_rows
            degraded_now = [sh.idx for sh in self._shards
                            if sh.status == DEGRADED]
        device_contribs = sorted(
            (c for c in take if c.leaves is not None),
            key=lambda c: (c.epoch, c.shard))
        host_outputs = [(c.shard, c.host_out) for c in take
                        if c.host_out is not None]
        late = [c for c in device_contribs if c.epoch < ep or c.late]
        # a late merge makes THIS epoch lossy too: the merged output
        # carries a prior epoch's rows its own window never covered, so
        # an untagged close would let the accuracy alarm fire on the
        # shadow-vs-sketch mismatch (shard-loss variance, not error)
        lossy = lossy or bool(missed) or bool(late)
        out = None
        merged_rows = 0
        if device_contribs:
            try:
                out, merged_rows = self._merge_epoch(
                    device_contribs, ep, now=now, missed=missed,
                    degraded=degraded_now, lost=lost_now, lossy=lossy)
            except Exception:
                # the merge path itself died (device loss during the
                # stacked program or the publish device_get — the very
                # failure class this layer exists to survive): the
                # taken contributions cannot deliver, so count them
                # LOST before surfacing the crash to the supervisor —
                # otherwise the next close overwrites _merge_inflight
                # and the conservation ledger gaps forever
                with self._ledger:
                    for c in device_contribs:
                        self._shards[c.shard].rows_lost += c.rows
                        self.rows_lost += c.rows
                    self._merge_inflight = 0
                    self._lossy_epoch = True
                raise
        participated = sorted({c.shard for c in device_contribs})
        tags = self._epoch_tags(ep, participated, missed, degraded_now,
                                lost_now, lossy, merged_rows)
        with self._ledger:
            self._merge_inflight = 0      # no-contribution epochs too
            self.epochs += 1
            self.late_merges += len(late)
            self.last_merge_s = time.perf_counter() - t0
            active = sum(1 for sh in self._shards
                         if sh.status == ACTIVE)
        self.epoch = ep + 1
        if self.auto_rejoin:
            for i in lost_now:
                self.rejoin(i)
        if self._auditor is not None:
            self._auditor.close_window(
                out, degraded=bool(degraded_now),
                lossy=lossy or bool(lost_now))
        tr = self._tracer
        if tr.enabled:
            tr.gauge("pod_shards_active", float(active))
            tr.gauge("pod_merge_epoch_s", self.last_merge_s)
            tr.gauge("pod_merge_missed", float(self.merge_missed))
        return EpochResult(ep, out, tags, participated, missed,
                           degraded_now, lost_now, merged_rows,
                           host_outputs, lossy or bool(lost_now))

    def _merge_epoch(self, contribs: List[_Contribution], ep: int,
                     now: Optional[float], missed: List[int],
                     degraded: List[int], lost: List[int],
                     lossy: bool) -> Tuple[FlowWindowOutput, int]:
        """Stack the contributions and run the SAME merged-flush program
        the mesh lane runs (sharded._merge_axis0 + ring rescore +
        flow_suite.flush), then publish the merged pre-flush state to
        the pod bus.  The sanctioned device sync of the merge path."""
        m = len(contribs)
        prog = self._merge_progs.get(m)
        if prog is None:
            prog = self._make_merge(m)
            self._merge_progs[m] = prog
        stacked_leaves = [
            jnp.asarray(np.stack([c.leaves[j] for c in contribs]))
            for j in range(len(self._leaf_shapes))]
        stacked = jax.tree_util.tree_unflatten(self._treedef,
                                               stacked_leaves)
        merged, out = prog(stacked)
        rows = int(np.asarray(out.rows))
        participated = sorted({c.shard for c in contribs})
        # subscribers (serving) get every epoch; the fsync'd npz only
        # when the epoch carried rows — an idle pod must not write a
        # full merged-sketch file per empty window (the same dirty
        # gating the single-chip lane's checkpoint cadence applies)
        self.bus.publish(
            merged, step=ep, wall_time=now, to_disk=rows > 0,
            tags=self._epoch_tags(ep, participated, missed, degraded,
                                  lost, lossy, rows))
        with self._ledger:
            self.merges += 1
            delivered = sum(c.rows for c in contribs)
            self.rows_delivered += delivered
            self._merge_inflight = 0
        return out, rows

    def _epoch_tags(self, ep: int, participated: List[int],
                    missed: List[int], degraded: List[int],
                    lost: List[int], lossy: bool, rows: int) -> dict:
        # NOT named pod_shards_active: that counter/gauge/healthz field
        # means "shards currently in ACTIVE status", while this tag
        # means "shards whose contribution made THIS epoch's merge" —
        # one name for two meanings would make /metrics and a serving
        # answer disagree on a healthy pod that merely missed a deadline
        return {"epoch": ep, "pod_shards": self.n_shards,
                "pod_shards_participated": len(participated),
                "pod_participated": participated,
                "pod_missing": sorted(set(missed) | set(lost)),
                "pod_degraded": degraded,
                "lossy": bool(lossy), "rows": rows}

    def _make_merge(self, m: int):
        from deepflow_tpu.parallel import sharded as _sh

        cfg = self.cfg

        def prog(stacked):
            merged = _sh._merge_axis0(stacked)
            merged = _sh.rescore_ring(merged)
            _fresh, out = flow_suite.flush(merged, cfg)
            return merged, out

        return jax.jit(prog)

    # -- kill / rejoin -------------------------------------------------------
    def kill(self, idx: int) -> None:
        """Simulate host loss of one shard (tests/chaos drive this
        directly; the ``shard.lost`` fault site does the same from
        inside the worker).  Rows past the shard's last snapshot are
        counted lost; its snapshot stays restorable for the rejoin."""
        sh = self._shards[idx]
        if sh.status == LOST:
            return
        self._mark_lost(sh)
        # event, not a queue marker: posting to a possibly-full queue
        # could block, and a marker behind backlog races the rejoin
        # drain. The worker notices within its 0.2s get timeout; its
        # queued backlog stays booked in qrows until rejoin() counts it.
        if sh.stop_ev is not None:
            sh.stop_ev.set()
        if sh.handle is not None:
            sh.handle.stop()

    def rejoin(self, idx: int) -> bool:
        """Rejoin-by-snapshot at an epoch boundary: the dead shard's
        last bus snapshot (if no contribution was taken after it — its
        ``gen`` tag matches) re-enters as a LATE contribution — its rows
        deliver in the next merge instead of vanishing — and the shard
        restarts with fresh state."""
        sh = self._shards[idx]
        if sh.status != LOST:
            return False
        if self.wire == "dict":
            # the dict wire cannot survive a mid-stream key-table reset
            # (the packer's announced host/device index agreement is
            # gone — see the module docstring): a rejoined shard with a
            # zeroed table would silently count every hit under the
            # all-zero key. The shard stays LOST, its drops counted.
            return False
        # the predecessor worker MUST be dead before a replacement
        # spawns — two consumers on one queue would race sh.state and
        # the ledger. A wedged one (e.g. mid merge.stall) defers the
        # rejoin to the next epoch boundary.
        if sh.stop_ev is not None:
            sh.stop_ev.set()
        if sh.handle is not None:
            sh.handle.stop()
            sh.handle.join(timeout=2.0)
            if sh.handle.is_alive():
                return False
        stale_rows = 0
        while True:          # drain whatever the dead worker left behind
            try:
                item = sh.q.get_nowait()
            except _queue.Empty:
                break
            if item[0] in ("lanes", "news", "hits"):
                stale_rows += item[-1]
        recovered = 0
        snap = sh.bus.latest()
        if self.wire == "lanes" and snap is not None \
                and snap.tags.get("run") == self._run_id \
                and snap.tags.get("gen") == sh.gen \
                and len(snap.leaves) == len(self._leaf_shapes) \
                and all(a.shape == s for a, s in zip(snap.leaves,
                                                     self._leaf_shapes)):
            recovered = int(snap.tags.get("rows", 0))
            with self._ledger:
                self._pending.append(_Contribution(
                    sh.idx, int(snap.tags["epoch"]),
                    recovered, tuple(snap.leaves), late=True))
        with self._ledger:
            lost_now = stale_rows + max(0, sh.restorable_rows - recovered)
            sh.qrows = max(0, sh.qrows - stale_rows)
            sh.rows_lost += lost_now
            self.rows_lost += lost_now
            sh.restorable_rows = 0
            sh.status = ACTIVE
            sh.consecutive_errors = 0
            sh.rows_epoch = 0
            sh.snap_rows = 0
            # the recovered snapshot's rows are now posted for merge;
            # a later rollback must never restore it again
            sh.gen += 1
            self.rejoins += 1
        self._init_shard_state(sh)
        self._spawn_worker(sh)
        _LOG.warning("%s shard %d rejoined (%d rows recovered from its "
                     "bus snapshot, %d stale rows counted lost)",
                     self.name, idx, recovered, lost_now)
        return True

    # -- lifecycle / observability -------------------------------------------
    def drain(self, timeout: float = 30.0) -> bool:
        """Wait for every live shard to go QUIET: queue empty, nothing
        in the worker's hands, and no due snapshot still unpublished.
        Tests kill/close right after a drain — the quiet point must be
        a consistent cut, or a kill can land between a batch's ledger
        update and its cadence snapshot and lose rows the caller
        believed were snapshotted.  (The epoch marker already orders
        contributions after all prior puts; this is for direct
        drivers.)"""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._ledger:
                quiet = all(
                    sh.status == LOST
                    or (sh.q.empty() and sh.active_rows == 0
                        and (sh.batches_since_snapshot
                             < self.snapshot_batches))
                    for sh in self._shards)
            if quiet:
                return True
            time.sleep(0.005)
        return False

    def close(self, final_epoch: bool = True) -> Optional[EpochResult]:
        """Final epoch merge (delivering everything still pending),
        then stop the merge thread and every worker."""
        self._merge_stop.set()
        if self._merge_handle is not None:
            self._merge_handle.stop()
            self._merge_handle.join(timeout=2)
        res = None
        if final_epoch:
            self.drain(timeout=10.0)
            res = self.close_epoch()
            with self._ledger:
                leftovers = any(c.leaves is not None
                                for c in self._pending)
            if leftovers:
                # late stragglers from the final epoch: one more merge
                # so close() never strands delivered-late rows
                time.sleep(0.01)
                res = self.close_epoch(deadline_s=self.merge_deadline_s)
        for sh in self._shards:
            # per-worker stop event, never a queue put: shutdown cannot
            # block on a full queue whose consumer is already dead
            if sh.stop_ev is not None:
                sh.stop_ev.set()
        for sh in self._shards:
            if sh.handle is not None:
                sh.handle.stop()
                sh.handle.join(timeout=5)
        return res

    def pending_rows(self) -> int:
        """Rows accepted but not yet delivered or counted lost: queued +
        in shard states + contribution-in-flight + posted-but-unmerged +
        restorable-after-kill.  Conservation: rows_sent ==
        rows_delivered + rows_host + rows_lost + pending_rows()."""
        with self._ledger:
            return self._pending_rows_locked()

    def _pending_rows_locked(self) -> int:
        n = sum(sh.qrows + sh.active_rows + sh.rows_epoch
                + sh.contrib_inflight + sh.restorable_rows
                for sh in self._shards)
        n += sum(c.rows for c in self._pending
                 if c.leaves is not None)
        return n + self._merge_inflight

    def shard_status(self) -> List[dict]:
        with self._ledger:
            return [{"shard": sh.idx, "status": sh.status,
                     "rows_in": sh.rows_in, "rows_lost": sh.rows_lost,
                     "rows_dropped": sh.rows_dropped,
                     "host_rows": sh.host_rows,
                     "device_errors": sh.device_errors,
                     "recoveries": sh.recoveries,
                     "last_contributed_epoch": sh.last_contributed_epoch}
                    for sh in self._shards]

    def shard_devices(self) -> List[set]:
        """The devices each shard's state leaves sit on, one set per
        shard: a pod whose shards share a device is no pod."""
        return [set().union(*(x.devices()
                              for x in jax.tree_util.tree_leaves(sh.state)))
                for sh in self._shards]

    def counters(self) -> dict:
        with self._ledger:
            active = sum(1 for sh in self._shards if sh.status == ACTIVE)
            degraded = sum(1 for sh in self._shards
                           if sh.status == DEGRADED)
            lost = sum(1 for sh in self._shards if sh.status == LOST)
            c = {"pod_shards": self.n_shards,
                 "pod_shards_active": active,
                 "pod_shards_degraded": degraded,
                 "pod_shards_lost": lost,
                 "pod_epochs": self.epochs,
                 "pod_merges": self.merges,
                 "pod_merge_missed": self.merge_missed,
                 "pod_rows_sent": self.rows_sent,
                 "pod_rows_delivered": self.rows_delivered,
                 "pod_rows_host": self.rows_host,
                 "pod_rows_lost": self.rows_lost,
                 "pod_rows_excluded": self.rows_excluded,
                 "pod_rejoins": self.rejoins,
                 "pod_late_merges": self.late_merges,
                 "pod_device_errors": sum(sh.device_errors
                                          for sh in self._shards),
                 "pod_merge_epoch_s": round(self.last_merge_s, 6),
                 # same locked section as the ledger fields above: the
                 # conservation equality this dict exposes must hold
                 # within ONE snapshot (ci.sh asserts it off one scrape)
                 "pod_rows_pending": self._pending_rows_locked()}
        return c
