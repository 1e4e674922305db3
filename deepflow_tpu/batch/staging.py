"""Zero-copy decode->staging: decoded columns land straight in the
device staging buffer.

The ISSUE 5 hot path still paid two full host copies per record
between the decoder and the link: decoded chunk columns were copied
into a TensorBatch (68 B/record of schema the sketch kernels mostly
never read), and the TensorBatch's 7 sketch columns were then packed
into the coalesced staging buffer (16 B/record). The flight recorder
put host pack, not transfer, as the residual gap between the ~2.5-4M
rec/s e2e and the ~34M rec/s device kernel (ROADMAP item 2).

`LaneStager` deletes the middle step: decoded chunk columns (usually
frombuffer VIEWS of the receiver's frame payload — wire/columnar_wire)
are packed DIRECTLY into a recycled coalesced staging buffer in the
slot layout `flow_suite.make_coalesced_update` consumes. The staging
buffer is the only host copy between the wire bytes and the single
device_put. Slot-contiguity (flow_suite.slot_words/slot_plane) is
what makes this possible: a partially-filled buffer of k complete
slots is already a valid k-batch transfer, so a window flush ships
the prefix without moving a byte.

`PackPool` shards the remaining pack work across supervised worker
threads by FLOW HASH (ROADMAP item 2's "shard decode across cores"):
pack destinations are pre-assigned in arrival order by the (single)
producer, the numpy pack of each sub-chunk runs on a worker keyed by
the sub-chunk's leading flow hash, and a group only dispatches once
its readiness countdown hits zero. Placement is deterministic and
writes are disjoint, so worker timing can never reorder rows — the
staged bytes are identical to the single-threaded pack, which is what
keeps the zero-copy path bit-identical to the TensorBatch reference
(tests/test_staging.py). numpy's pack kernels release the GIL for the
bulk of the copy, so the shards genuinely overlap on cores.

Fault posture: a pack failure poisons its group (StagingPackError from
`wait_ready`), which crashes the feed thread INTO the supervisor — the
group's rows are counted lost and device state restored, exactly the
ISSUE 5 containment for an unexplained feed error. The pool workers
themselves never die on a bad chunk; they beat the deadman like every
PR 2 thread.
"""

from __future__ import annotations

import queue as _queue
import threading
from typing import Callable, Dict, List, Optional

import numpy as np

from deepflow_tpu.models import flow_dict, flow_suite

__all__ = ["DictWireStager", "LaneStager", "PackPool", "StagedGroup",
           "StagedWireGroup", "StagingPackError"]

_PACK_COLS = ("ip_src", "ip_dst", "port_src", "port_dst", "proto",
              "packet_tx", "packet_rx")


class StagingPackError(Exception):
    """A sharded pack task failed; the staged group is poisoned."""


class _GroupState:
    """Readiness countdown for one staging buffer: pre-assigned pack
    tasks check in as they complete; `wait` returns once all have."""

    __slots__ = ("_cond", "_pending", "error")

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._pending = 0
        self.error: Optional[BaseException] = None

    def add(self, n: int = 1) -> None:
        with self._cond:
            self._pending += n

    def done(self, error: Optional[BaseException] = None) -> None:
        with self._cond:
            self._pending -= 1
            if error is not None and self.error is None:
                self.error = error
            if self._pending <= 0:
                self._cond.notify_all()

    def wait(self, timeout: Optional[float]) -> bool:
        with self._cond:
            return self._cond.wait_for(lambda: self._pending <= 0,
                                       timeout)


class StagedGroup:
    """k complete batch slots staged in one coalesced buffer — what the
    device feed transfers and dispatches as a unit. `flat` is the
    prefix actually shipped; `buffer` the full backing array returned
    whole through `LaneStager.recycle` once the feed fence retired.
    `valid` (total rows) is the feed's loss-accounting contract
    (runtime/feed.py reads it exactly like TensorBatch.valid)."""

    __slots__ = ("flat", "buffer", "k", "capacity", "valid", "_state")

    def __init__(self, flat: np.ndarray, buffer: np.ndarray, k: int,
                 capacity: int, valid: int, state: _GroupState) -> None:
        self.flat = flat
        self.buffer = buffer
        self.k = k
        self.capacity = capacity
        self.valid = valid
        self._state = state

    def wait_ready(self, timeout: Optional[float] = None) -> None:
        """Block until every sharded pack task of this group completed
        (a HOST barrier — the device is never touched). Raises
        StagingPackError if any pack task failed, or on timeout (a
        wedged pool worker must not hang the feed silently)."""
        if not self._state.wait(timeout):
            raise StagingPackError(
                f"staged group ({self.k} batches) never became ready "
                f"within {timeout}s")
        if self._state.error is not None:
            raise StagingPackError(
                f"pack task failed: {self._state.error!r}") \
                from self._state.error


class PackPool:
    """Flow-hash-sharded pack workers (Supervisor-spawned, deadman
    beats). One queue per worker: tasks for the same flow shard stay
    FIFO on the same core, giving flow affinity without any
    cross-worker ordering requirement (destinations are pre-assigned,
    so any interleaving lands the same bytes)."""

    def __init__(self, n_workers: int, name: str = "stage-pack") -> None:
        from deepflow_tpu.runtime.supervisor import default_supervisor

        self.n_workers = max(1, int(n_workers))
        # routing width: submit shards over the first `active` workers.
        # The autotuner resizes this live — shrink just narrows routing
        # (idle workers keep beating), grow spawns more workers.
        self.active = self.n_workers
        self.name = name
        self._queues: List[_queue.Queue] = [
            _queue.Queue(maxsize=256) for _ in range(self.n_workers)]
        self.tasks = 0
        self.task_errors = 0
        # workers increment task_errors concurrently; += is not atomic
        self._err_lock = threading.Lock()
        self._closed = False
        sup = default_supervisor()
        self._handles = [
            sup.spawn(f"{name}-{i}", self._make_worker(i))
            for i in range(self.n_workers)]

    def _make_worker(self, i: int) -> Callable[[], None]:
        q = self._queues[i]

        def run() -> None:
            from deepflow_tpu.runtime.supervisor import default_supervisor

            sup = default_supervisor()
            while True:
                try:
                    item = q.get(timeout=0.2)
                except _queue.Empty:
                    sup.beat()
                    if self._closed:
                        return
                    continue
                sup.beat()
                if item is None:
                    return
                fn, state = item
                # a bad chunk poisons ITS group, never the worker: the
                # error surfaces at the group's wait_ready, the pool
                # keeps serving every other shard
                try:
                    fn()
                except BaseException as e:   # noqa: BLE001 — contained
                    with self._err_lock:
                        self.task_errors += 1
                    state.done(e)
                else:
                    state.done()

        return run

    def submit(self, shard_key: int, fn: Callable[[], None],
               state: _GroupState) -> None:
        state.add()
        self.tasks += 1
        self._queues[shard_key % self.active].put((fn, state))

    def resize(self, n_workers: int) -> int:
        """Retarget the routing width to `n_workers` (autotune's
        pack_workers knob). Growing past the spawned count spawns new
        supervised workers; shrinking only narrows `active` — routing
        is a single GIL-atomic int read in submit(), already-queued
        tasks finish on their original worker, and the same-shard FIFO
        property holds for all tasks submitted after the change (what
        correctness actually needs: destinations are pre-assigned, so
        any routing is byte-identical). Returns the applied width."""
        from deepflow_tpu.runtime.supervisor import default_supervisor

        n = max(1, int(n_workers))
        if self._closed:
            return self.active
        if n > self.n_workers:
            sup = default_supervisor()
            for i in range(self.n_workers, n):
                self._queues.append(_queue.Queue(maxsize=256))
                self._handles.append(
                    sup.spawn(f"{self.name}-{i}", self._make_worker(i)))
            self.n_workers = n
        self.active = n
        return n

    def close(self, timeout: float = 5.0) -> None:
        self._closed = True
        for q in self._queues:
            q.put(None)
        for h in self._handles:
            h.stop()
            h.join(timeout=timeout)

    def counters(self) -> dict:
        return {"pack_workers": self.active,
                "pack_tasks": self.tasks,
                "pack_task_errors": self.task_errors}


class LaneStager:
    """Accumulates decoded chunks straight into coalesced staging
    buffers (slot layout, `group_batches` slots per buffer).

    Mirrors Batcher's cut semantics exactly — fill each slot to
    `capacity` rows, carry the remainder, pad+zero only the final
    partial slot at flush — so the batch partition (and therefore the
    sketch state, ring phase included) is bit-identical to the
    TensorBatch path on the same stream. Buffers cycle through a
    bounded free list via `recycle()` (called from the feed thread
    after the fence retired, like Batcher.recycle; list ops are
    GIL-atomic and a losing race just allocates)."""

    def __init__(self, capacity: int, group_batches: int = 1,
                 pool: Optional[PackPool] = None,
                 pool_cap: int = 4) -> None:
        self.capacity = int(capacity)
        self.group_batches = max(1, int(group_batches))
        self._pack_pool = pool
        self._pool_cap = max(1, int(pool_cap))
        self._words = flow_suite.coalesced_lanes_words(
            self.group_batches, self.capacity)
        self._pending_group: Optional[int] = None
        self._free: list = []
        self._buf: Optional[np.ndarray] = None
        self._state: Optional[_GroupState] = None
        self._slot = 0          # complete slots in the current buffer
        self._fill = 0          # rows in the current (open) slot
        self._rows = 0          # valid rows staged in the current buffer
        self.total_rows = 0
        self.staged_groups = 0
        self.staged_batches = 0
        self.pool_hits = 0
        self.recycled = 0

    # -- producer side (the exporter worker, serialized) -------------------
    def put(self, cols: Dict[str, np.ndarray]) -> List[StagedGroup]:
        """Append one decoded chunk; returns zero or more complete
        groups (every slot full). The chunk's column arrays must stay
        unmodified until the returned groups' packs complete — decoded
        chunks are fresh views per frame, so this holds by
        construction."""
        n = len(next(iter(cols.values())))
        self.total_rows += n
        out: List[StagedGroup] = []
        off = 0
        while n - off > 0:
            self._ensure_buffer()
            take = min(self.capacity - self._fill, n - off)
            self._pack(cols, off, take)
            self._fill += take
            self._rows += take
            off += take
            if self._fill == self.capacity:
                self._close_slot(self.capacity)
                if self._slot == self.group_batches:
                    out.append(self._emit())
        return out

    def flush(self) -> List[StagedGroup]:
        """Emit the partial remainder as a prefix group (padded final
        slot, tail zeroed — the exact bytes the TensorBatch path would
        have staged)."""
        if self._buf is None or (self._slot == 0 and self._fill == 0):
            return []
        if self._fill > 0:
            plane = flow_suite.slot_plane(self._buf, self._slot,
                                          self.capacity)
            plane[:, self._fill:] = 0
            self._close_slot(self._fill)
        return [self._emit()]

    # -- consumer side (the feed thread) -----------------------------------
    def recycle(self, group: StagedGroup) -> None:
        """Return a group's backing buffer once its fence retired (the
        only point reuse is provably safe)."""
        if group.buffer.size != self._words:
            return
        self.recycled += 1
        if len(self._free) < self._pool_cap:
            self._free.append(group.buffer)

    def set_group_batches(self, n: int) -> None:
        """Retarget the coalesce width (autotune's coalesce_batches
        knob). Applied at the NEXT group boundary — the open buffer
        keeps its layout, so in-flight groups and the feed's
        per-signature jitted programs are untouched; the free list is
        dropped (its buffers are sized for the old width; recycle()'s
        size check would reject them anyway)."""
        self._pending_group = max(1, int(n))

    # -- internals ---------------------------------------------------------
    def _ensure_buffer(self) -> None:
        if self._buf is not None:
            return
        if self._pending_group is not None \
                and self._pending_group != self.group_batches:
            self.group_batches = self._pending_group
            self._words = flow_suite.coalesced_lanes_words(
                self.group_batches, self.capacity)
            self._free.clear()
        self._pending_group = None
        try:
            self._buf = self._free.pop()
            self.pool_hits += 1
        except IndexError:
            self._buf = np.empty(self._words, np.uint32)
        self._state = _GroupState()
        self._slot = self._fill = self._rows = 0

    def _pack(self, cols: Dict[str, np.ndarray], off: int,
              take: int) -> None:
        """Pack cols[off:off+take] into the open slot at _fill — the
        ONE copy between decoded wire views and the device transfer."""
        sub = {k: cols[k][off:off + take] for k in _PACK_COLS}
        plane = flow_suite.slot_plane(self._buf, self._slot,
                                      self.capacity)
        dest = plane[:, self._fill:self._fill + take]
        if self._pack_pool is None:
            flow_suite.pack_lanes_into(sub, dest)
            return
        # flow-hash shard of the sub-chunk's leading 5-tuple: packs for
        # the same flow stream land on the same worker (FIFO per queue)
        from deepflow_tpu.utils.u32 import fold_columns_np

        shard = int(fold_columns_np(
            [sub[c][:1] for c in ("ip_src", "ip_dst", "port_src",
                                  "port_dst", "proto")])[0])
        self._pack_pool.submit(
            shard,
            lambda s=sub, d=dest: flow_suite.pack_lanes_into(s, d),
            self._state)

    def _close_slot(self, valid: int) -> None:
        self._buf[self._slot * flow_suite.slot_words(self.capacity)] = valid
        self._slot += 1
        self._fill = 0
        self.staged_batches += 1

    def _emit(self) -> StagedGroup:
        k = self._slot
        flat = self._buf if k == self.group_batches else \
            self._buf[:flow_suite.coalesced_lanes_words(k, self.capacity)]
        group = StagedGroup(flat=flat, buffer=self._buf, k=k,
                            capacity=self.capacity, valid=self._rows,
                            state=self._state)
        self._buf = None
        self._state = None
        self._slot = self._fill = self._rows = 0
        self.staged_groups += 1
        return group

    def counters(self) -> dict:
        c = {"staged_groups": self.staged_groups,
             "staged_batches": self.staged_batches,
             "staged_rows": self.total_rows,
             "staging_pool_hits": self.pool_hits,
             "staging_recycled": self.recycled}
        if self._pack_pool is not None:
            c.update(self._pack_pool.counters())
        return c


class StagedWireGroup(StagedGroup):
    """A staged dict-wire group: one coalesced flat buffer holding an
    emission-ordered news/hits word sequence (flow_dict.stage_wire
    layout) plus the static signature that selects the fused
    make_wire_update program. `epoch` stamps which packer generation
    emitted it: after a device-state restore swaps the packer
    (DictWireStager.reset_packer), in-flight groups from the old
    generation reference dictionary indices the fresh device table
    never scattered — the dispatcher drops them as counted loss
    instead of applying garbage gathers."""

    __slots__ = ("sig", "epoch", "_wire_src")

    def __init__(self, flat: np.ndarray, sig, k: int, capacity: int,
                 valid: int, epoch: int, state: _GroupState) -> None:
        super().__init__(flat=flat, buffer=flat, k=k, capacity=capacity,
                         valid=valid, state=state)
        self.sig = sig
        self.epoch = epoch


class DictWireStager:
    """Dict-wire twin of LaneStager: decoded chunks -> recycled
    coalesced news/hits staging buffers.

    The dict wire cannot pack chunk slices independently — the packer
    is a stateful LRU whose news/hits split depends on every record
    seen before — so the stager accumulates the 7 sketch columns into a
    preallocated batch buffer, cut at exactly `capacity` rows, and runs
    ONE pack()+flush() per cut. That reproduces the inline path's batch
    partition bit-for-bit: same pack-call boundaries -> same news
    bucket cuts -> same plane count -> same batches_seen -> identical
    ring admission phase. What the staging plane adds is everything
    AFTER the pack: emitted planes from `group_batches` consecutive
    batches coalesce into one recycled flat buffer (flow_dict.stage_wire
    layout, one device transfer per group), optionally copied by the
    sharded PackPool (destinations pre-assigned per plane, disjoint
    writes), riding the DeviceFeed prefetch window exactly like staged
    lane groups.

    Producer side (put/flush) runs on the exporter worker, serialized;
    recycle()/reset_packer() run on the feed thread. `_lock` is a LEAF
    lock (nothing else is acquired under it) guarding the packer and
    the open group's emitted-wire accumulation — the only state both
    threads touch."""

    def __init__(self, capacity: int, packer_factory,
                 group_batches: int = 1,
                 pool: Optional[PackPool] = None,
                 pool_cap: int = 4) -> None:
        self.capacity = int(capacity)
        self.group_batches = max(1, int(group_batches))
        self._packer_factory = packer_factory
        self._packer = packer_factory()
        self.epoch = 0
        self._lock = threading.Lock()
        self._pack_pool = pool
        self._pool_cap = max(1, int(pool_cap))
        self._pending_group: Optional[int] = None
        # host key mirror of the device table (lane-word layout), fed
        # at stage time so degraded absorb can gather hit keys — see
        # flow_dict.mirror_news_np for the eviction-reuse caveat
        self.mirror = np.zeros((4, self._packer.capacity), np.uint32)
        self._cols = {c: np.empty(self.capacity, np.uint32)
                      for c in _PACK_COLS}
        self._fill = 0           # rows in the open (unpacked) batch
        self._wire: list = []    # emitted planes of the open group
        self._batches = 0        # packed batches in the open group
        self._rows = 0           # valid rows packed into the open group
        # size-keyed free lists: signatures vary, but the packer's
        # power-of-two width buckets keep the distinct sizes few
        self._free: Dict[int, list] = {}
        self.total_rows = 0
        self.staged_groups = 0
        self.staged_batches = 0
        self.pool_hits = 0
        self.recycled = 0
        self.epoch_drops = 0
        # rows whose flow keys the packer resolved natively / in Python
        self.resolve_native_rows = 0
        self.resolve_python_rows = 0

    # -- producer side (the exporter worker, serialized) -------------------
    def put(self, cols: Dict[str, np.ndarray]) -> List[StagedWireGroup]:
        """Append one decoded chunk; returns zero or more complete
        groups. Chunk columns are copied into the batch accumulation
        buffer immediately, so the caller's views may be invalidated
        as soon as put() returns."""
        n = len(next(iter(cols.values())))
        self.total_rows += n
        out: List[StagedWireGroup] = []
        off = 0
        while n - off > 0:
            take = min(self.capacity - self._fill, n - off)
            for c in _PACK_COLS:
                np.copyto(self._cols[c][self._fill:self._fill + take],
                          cols[c][off:off + take], casting="unsafe")
            self._fill += take
            off += take
            if self._fill == self.capacity:
                g = self._cut_batch(self.capacity)
                if g is not None:
                    out.append(g)
        return out

    def flush(self) -> List[StagedWireGroup]:
        """Pack the partial remainder batch and emit whatever the open
        group holds — the window-boundary prefix emission."""
        g = None
        if self._fill > 0:
            g = self._cut_batch(self._fill, force_emit=True)
        elif self._batches > 0 or self._wire:
            with self._lock:
                g = self._emit_locked()
        if g is None:
            return []
        self._stage(g)
        return [g]

    # -- consumer side (the feed thread) -----------------------------------
    def recycle(self, group: StagedWireGroup) -> None:
        """Return a group's flat buffer once its fence retired."""
        self.recycled += 1
        free = self._free.setdefault(group.flat.size, [])
        if len(free) < self._pool_cap and len(self._free) <= 16:
            free.append(group.flat)

    def reset_packer(self) -> int:
        """Device-state restore: swap in a fresh packer generation (the
        fresh device table knows no index, so every flow must
        re-announce as news). The open group's already-packed planes
        belong to the dead generation and are dropped — returns their
        row count so the caller adds it to the window's counted loss
        (exactly the inline path's accounting: those rows died with
        the device state). The open UNPACKED batch accumulation
        survives: its rows pack under the new generation."""
        with self._lock:
            self._packer = self._packer_factory()
            self.epoch += 1
            self.mirror[:] = 0
            dropped = self._rows
            self._wire = []
            self._batches = 0
            self._rows = 0
            return dropped

    # -- knobs -------------------------------------------------------------
    def set_group_batches(self, n: int) -> None:
        """Retarget the coalesce width; applied at the next group
        boundary, like LaneStager.set_group_batches. Free lists are
        size-keyed so old buffers stay reusable whenever a signature
        repeats."""
        self._pending_group = max(1, int(n))

    # -- internals ---------------------------------------------------------
    def _cut_batch(self, n: int,
                   force_emit: bool = False) -> Optional[StagedWireGroup]:
        batch = {c: self._cols[c][:n] for c in _PACK_COLS}
        g = None
        with self._lock:
            if self._batches == 0 and self._pending_group is not None:
                self.group_batches = self._pending_group
                self._pending_group = None
            # the inline dispatch sequence, verbatim: one pack + one
            # hit-drain per batch cut (the flush is what pins the batch
            # partition — and therefore ring phase — to the inline path)
            wire = self._packer.pack(batch)
            wire += self._packer.flush()
            if self._packer.resolve_native:
                self.resolve_native_rows += n
            else:
                self.resolve_python_rows += n
            self._fill = 0       # pack() consumed the accumulation
            self._wire.extend(wire)
            self._batches += 1
            self._rows += n
            self.staged_batches += 1
            if force_emit or self._batches >= self.group_batches:
                g = self._emit_locked()
        if g is not None and not force_emit:
            self._stage(g)
        return g

    def _emit_locked(self) -> Optional[StagedWireGroup]:
        """Swap the open group out under the lock; staging the bytes
        happens outside it (the wire list is local after the swap)."""
        wire, self._wire = self._wire, []
        k, self._batches = self._batches, 0
        rows, self._rows = self._rows, 0
        if not wire:
            return None
        sig = flow_dict.wire_signature(wire)
        g = StagedWireGroup(
            flat=np.empty(0, np.uint32), sig=sig, k=k,
            capacity=self.capacity, valid=rows, epoch=self.epoch,
            state=_GroupState())
        g._wire_src = wire
        return g

    def _stage(self, g: StagedWireGroup) -> None:
        wire = g._wire_src
        del g._wire_src
        words = flow_dict.wire_words(g.sig)
        try:
            flat = self._free[words].pop()
            self.pool_hits += 1
        except (KeyError, IndexError):
            flat = np.empty(words, np.uint32)
        g.flat = g.buffer = flat
        flow_dict.mirror_news_np(wire, self.mirror)
        if self._pack_pool is None:
            flow_dict.stage_wire(wire, flat)
            self.staged_groups += 1
            return
        # header words on the producer, plane copies sharded by plane
        # index (disjoint destinations, pre-assigned — any worker
        # interleaving lands the same bytes)
        P = len(wire)
        off = P
        for i, (_, plane, nv) in enumerate(wire):
            flat[i] = nv
            dest = flat[off:off + plane.size]
            self._pack_pool.submit(
                i, lambda p=plane, d=dest: np.copyto(d, p.reshape(-1)),
                g._state)
            off += plane.size
        self.staged_groups += 1

    def counters(self) -> dict:
        c = {"staged_groups": self.staged_groups,
             "staged_batches": self.staged_batches,
             "staged_rows": self.total_rows,
             "staging_pool_hits": self.pool_hits,
             "staging_recycled": self.recycled,
             "dict_epoch": self.epoch,
             "dict_epoch_drops": self.epoch_drops,
             "dict_resolve_native_rows": self.resolve_native_rows,
             "dict_resolve_python_rows": self.resolve_python_rows}
        if self._pack_pool is not None:
            c.update(self._pack_pool.counters())
        return c
