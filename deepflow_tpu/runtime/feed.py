"""Overlapped host->device feed: double-buffered prefetch for sketch lanes.

The flight recorder (ISSUE 1) showed the tpu_sketch hot path leaving the
chip idle >85% of the time: the worker packed, transferred and dispatched
each batch serially, so host packing of batch N+1 never overlapped the
device update of batch N. `DeviceFeed` is the missing staging discipline
(FENXI's host-accelerator pipelining argument applied to this repo's
link):

- the exporter's queue worker ENQUEUES TensorBatches (cheap, back-
  pressured by a bounded queue) instead of dispatching inline;
- a Supervisor-spawned feed thread pulls groups of up to
  `coalesce` batches, calls the owner's `process_group` (host pack into
  one staging buffer -> ONE coalesced transfer -> one fused async
  dispatch with donated state), and
- keeps at most `depth` dispatched updates in flight: before admitting a
  new one it FENCES the oldest (block_until_ready on the program's small
  non-donated fence output) — the classic double-buffer window. The
  fence is also what makes staging-buffer recycling safe: a buffer
  returns to its pool only after the program that read it completed.
- before it waits on its queue it retires, without blocking, every
  in-flight update whose fence is already ready, and while updates stay
  in flight it waits in short slices so it sees them retire. The
  device track's dispatch->retire interval therefore ends where the
  program did, not at the next batch's arrival, and a wait with nothing
  left in flight is the device starved by the host: Tracer stage
  `feed.starved` and the profiler's stall seconds.

Accounting contract (the PR 2/PR 4 ladders depend on it):

- `pending()` counts every batch the feed still owes the device
  (queued + being processed + in flight), so the drain ladder's
  `Exporters.pending()` never reads zero while rows are in the window;
- `drain()` is a barrier: when it returns True every batch enqueued
  before the call has been applied AND fenced — window flushes,
  checkpoints and degraded-mode probes run against settled state;
- a feed-thread crash is recovered on supervisor restart: the group
  that was mid-flight is counted lost through `on_restart` (which also
  restores device state — a crash mid-dispatch leaves donation
  uncertain), never silently dropped.

State ownership protocol (replaces lock-per-mutation for device state):
between `drain()` barriers the feed thread is the ONLY writer of the
owner's device state; everyone else (window flush, checkpoint, probe)
mutates it only after a drain returned. That is why the owner's
callbacks never take the owner's state lock — the lock serializes
producers against the flush, the barrier serializes the flush against
the feed.
"""

from __future__ import annotations

import logging
import queue as _queue
import threading
import time
from collections import deque
from typing import Any, Callable, List, Optional, Tuple

from deepflow_tpu.runtime.profiler import default_profiler
from deepflow_tpu.runtime.supervisor import default_supervisor
from deepflow_tpu.runtime.tracing import default_tracer

__all__ = ["DeviceFeed", "InFlight"]

_LOG = logging.getLogger(__name__)

# gauge cadence: every Nth group, matching the exporter's every-16th
# gauge cadence
_GAUGE_EVERY = 16
# queue-wait slice while updates are in flight: how late the feed may
# notice that the device finished them
_RETIRE_POLL_S = 0.001


class InFlight(tuple):
    """(fence, rows, release) — one dispatched-but-unfenced update.
    `fence` is a small NON-donated device output of the fused program
    (None for host-path groups); `rows` the records it carried;
    `release` returns the staging buffer to its pool (or None)."""

    __slots__ = ()

    def __new__(cls, fence: Any, rows: int,
                release: Optional[Callable[[], None]] = None):
        return tuple.__new__(cls, (fence, rows, release))

    @property
    def fence(self):
        return self[0]

    @property
    def rows(self) -> int:
        return self[1]

    @property
    def release(self):
        return self[2]


class DeviceFeed:
    """The overlapped feed engine. Owns the bounded batch queue, the
    supervised feed thread and the in-flight fence window; the sketch
    owner supplies the jax-specific work through three callbacks:

    - process_group(group) -> Optional[InFlight]: host-pack + transfer +
      async dispatch of a list of (TensorBatch, batch_id) pairs; returns
      None when the group was absorbed host-side (degraded mode) or a
      handled device error already accounted for it. Exceptions escaping
      it crash the feed thread INTO the supervisor on purpose — restart
      + `on_restart` recovery is the containment, not a silent drop.
    - on_fence_error(exc, rows): an async device error surfaced at a
      fence; `rows` aggregates the failed batch plus every younger
      in-flight batch (they consumed the poisoned donated state chain).
    - on_restart(rows): supervisor restarted the feed thread after a
      crash; `rows` were in the window and can no longer be trusted.
    """

    def __init__(self, name: str,
                 process_group: Callable[[List[Tuple[Any, int]]],
                                         Optional[InFlight]],
                 *, depth: int = 2, coalesce: int = 1,
                 on_fence_error: Optional[Callable[[BaseException, int],
                                                   None]] = None,
                 on_restart: Optional[Callable[[int], None]] = None,
                 queue_batches: Optional[int] = None) -> None:
        self.name = name
        self._process_group = process_group
        self.depth = max(1, int(depth))
        self.coalesce = max(1, int(coalesce))
        self._on_fence_error = on_fence_error
        self._on_restart = on_restart
        # bounded: a full queue back-pressures the enqueuing worker the
        # same way the old inline dispatch did, so overload still lands
        # in the exporter queue's counted drop-oldest, never in RAM
        cap = queue_batches or max(4, 2 * self.depth * self.coalesce)
        self._q: _queue.Queue = _queue.Queue(maxsize=cap)
        self._inflight: deque = deque()
        self._active: Optional[List[Tuple[Any, int]]] = None
        self._handle = None
        self._spawn_lock = threading.Lock()
        self._pending_lock = threading.Lock()
        self._queued_batches = 0
        self._active_batches = 0   # group inside process_group right now
        self._tracer = default_tracer()
        # occupancy profiler (runtime/profiler.py): feed/fence/device
        # spans at group granularity — the dispatch->retire interval is
        # what tpu_device_busy_fraction unions, and q.get waits with an
        # empty window are the feed-stall (starved device) time
        self._prof = default_profiler()
        # counters (surfaced through the owner's Countable)
        self.groups = 0
        self.batches = 0
        self.fences = 0
        self.fence_errors = 0
        self.crash_recoveries = 0
        self.fence_wait_s = 0.0
        # enqueue -> pull latency, summed per batch: the queue-dwell
        # signal the autotuner (runtime/autotune.py) reads — dwell
        # rising while the device idles means the feed shape (coalesce/
        # depth) is wrong for the current arrival rate
        self.queue_dwell_s = 0.0
        self.dwell_batches = 0
        self._mark_t = time.perf_counter()
        self._mark_fence_s = 0.0
        self._closed = False

    # -- producer side -----------------------------------------------------
    def put(self, batch: Any, batch_id: int = -1) -> None:
        """Enqueue one TensorBatch (blocks when the window is full —
        that back-pressure IS the bounded in-flight guarantee)."""
        self._ensure_started()
        with self._pending_lock:
            self._queued_batches += 1
        self._q.put(("batch", batch, batch_id, time.perf_counter()))

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Barrier: returns True once everything enqueued before this
        call has been applied and fenced. False = the feed thread never
        got there inside `timeout` (dead supervisor / wedged device) —
        the caller decides whether that is fatal."""
        if self._handle is None:
            return True        # nothing ever enqueued
        if self._closed and not self._handle.is_alive():
            return True        # close() already drained and stopped us
        done = threading.Event()
        self._q.put(("barrier", done))
        return done.wait(timeout)

    def pending(self) -> int:
        """Batches the feed still owes the device: queued + active +
        in flight. The drain ladder reads this through the exporter's
        `pending_extra` so close() cannot declare victory while rows
        sit in the prefetch window."""
        with self._pending_lock:
            n = self._queued_batches + self._active_batches
        n += len(self._inflight)     # fence entries (approximate is fine:
        return n                     # drain() is the correctness barrier)

    def close(self, timeout: float = 10.0) -> None:
        """Stop the feed thread after it drains the queue and fences
        the window. Idempotent."""
        if self._handle is None or self._closed:
            self._closed = True
            return
        self._closed = True
        self._q.put(("stop",))
        self._handle.join(timeout=timeout)

    # -- feed thread -------------------------------------------------------
    def _ensure_started(self) -> None:
        if self._handle is not None:
            return
        with self._spawn_lock:
            if self._handle is None:
                self._handle = default_supervisor().spawn(
                    self.name, self._run)

    def _run(self) -> None:
        sup = default_supervisor()
        if self._active is not None or self._inflight:
            self._recover_after_crash()
        # the device has had nothing in flight since this instant (None:
        # work is in flight, or the feed is busy). It is counted when
        # the next batch arrives: starvation preceding real work. A
        # barrier keeps the clock running, unless no batch came since
        # the previous control item — a window with no batch at all is
        # an idle pipeline, not a starved device, so a pipeline without
        # traffic accrues nothing.
        starved_since = None
        batch_since_ctl = False
        while True:
            self._retire_ready()
            if self._inflight:
                timeout = _RETIRE_POLL_S
            else:
                timeout = 0.2
                if starved_since is None:
                    starved_since = time.perf_counter()
            try:
                item = self._q.get(timeout=timeout)
            except _queue.Empty:
                sup.beat()
                continue
            sup.beat()
            if item[0] != "batch":
                if not batch_since_ctl:
                    starved_since = None
                batch_since_ctl = False
                if self._handle_control(item):
                    return
                continue
            batch_since_ctl = True
            now = time.perf_counter()
            if starved_since is not None:
                self._starved(now - starved_since)
                starved_since = None
            self.queue_dwell_s += now - item[3]
            self.dwell_batches += 1
            group = [(item[1], item[2])]
            ctl = None
            while len(group) < self.coalesce:
                try:
                    nxt = self._q.get_nowait()
                except _queue.Empty:
                    break
                if nxt[0] == "batch":
                    self.queue_dwell_s += now - nxt[3]
                    self.dwell_batches += 1
                    group.append((nxt[1], nxt[2]))
                else:
                    ctl = nxt          # handle after the group applies
                    break
            self._apply_group(group)
            if ctl is not None:
                batch_since_ctl = False
                if self._handle_control(ctl):
                    return

    def _starved(self, dur_s: float) -> None:
        """The device sat with nothing in flight for `dur_s` before this
        batch arrived. Recorded without a device-profiler annotation, so
        that a trace names the gap by the upstream stage that starved
        the feed."""
        self._prof.add_stall(dur_s)
        if self._tracer.enabled:
            self._tracer.observe("feed.starved", dur_s)

    def _retire_ready(self) -> None:
        """Retire, oldest first and without blocking, every in-flight
        update whose fence is ready: release its buffer, record its
        device interval, count the fence."""
        while self._inflight:
            fence = self._inflight[0][0].fence
            try:
                ready = fence is None or fence.is_ready()
            except Exception:
                ready = True           # a failed fence: _fence_one says so
            if not ready:
                return
            self._fence_one(*self._inflight.popleft())

    def _handle_control(self, item: tuple) -> bool:
        """Barrier/stop handling; True = the loop should exit."""
        self._fence_all()
        if item[0] == "barrier":
            item[1].set()
            return False
        return True                    # "stop": normal completion

    def _apply_group(self, group: List[Tuple[Any, int]]) -> None:
        # the group stays visible to pending() while it is being
        # processed (queued -> active -> in flight, never a gap): the
        # drain ladder polls pending()==0 and must not observe a
        # transient zero while rows are mid-dispatch
        with self._pending_lock:
            self._queued_batches -= len(group)
            self._active_batches = len(group)
        self._active = group
        # escaping exceptions crash into the supervisor BY DESIGN: the
        # owner's process_group contains everything it understands
        # (device errors, degraded fallback); what's left is a bug whose
        # group must be recovered on restart, not guessed at here
        t0 = time.perf_counter()
        out = self._process_group(group)
        t1 = time.perf_counter()
        rows = sum(int(getattr(tb, "valid", 0)) for tb, _ in group)
        self._prof.record("feed", f"group[{len(group)}]", t1 - t0,
                          rows=rows)
        self._active = None
        self.groups += 1
        self.batches += len(group)
        if out is not None:
            # the dispatch timestamp rides beside the fence: when the
            # fence retires, [dispatch, retire] is the device-execution
            # interval the busy-fraction gauge unions
            self._inflight.append((out, t1))
            while len(self._inflight) > self.depth:
                self._fence_one(*self._inflight.popleft())
        with self._pending_lock:       # after the in-flight append: the
            self._active_batches = 0   # count may overlap, never gap
        self._maybe_gauges()

    def _fence_one(self, f: InFlight,
                   t_dispatch: Optional[float] = None) -> None:
        """Wait for one dispatched update to retire (the sanctioned
        blocking sync of this module: the bounded-window fence; a no-op
        wait when `_retire_ready` found the fence ready). An
        error here is an ASYNC device failure — the donated state chain
        behind it is poisoned, so every younger in-flight batch is
        discarded and the whole loss reported once."""
        t0 = time.perf_counter()
        try:
            if f.fence is not None:
                import jax
                jax.block_until_ready(f.fence)
        except Exception as e:
            self.fence_wait_s += time.perf_counter() - t0
            self.fence_errors += 1
            if f.release is not None:
                f.release()
            extra = self._discard_inflight()
            if self._on_fence_error is not None:
                self._on_fence_error(e, f.rows + extra)
            return
        t1 = time.perf_counter()
        self.fence_wait_s += t1 - t0
        self.fences += 1
        self._prof.record("fence", "wait", t1 - t0, rows=f.rows)
        if t_dispatch is not None:
            # dispatch -> retirement brackets the program's device
            # execution: the fence can only ack after completion, and
            # the retire poll keeps retirement close behind it
            self._prof.record("device", "update", t1 - t_dispatch,
                              rows=f.rows)
        if f.release is not None:
            f.release()

    def _fence_all(self) -> None:
        while self._inflight:
            self._fence_one(*self._inflight.popleft())

    def _discard_inflight(self) -> int:
        """Drop every outstanding fence, swallowing their (expected)
        errors; returns the rows they carried so the caller can count
        the loss in one place."""
        rows = 0
        while self._inflight:
            f, _t = self._inflight.popleft()
            rows += f.rows
            try:
                if f.fence is not None:
                    import jax
                    jax.block_until_ready(f.fence)
            except Exception:
                pass
            if f.release is not None:
                f.release()
        return rows

    def _recover_after_crash(self) -> None:
        """Supervisor restarted us mid-group: the active group may or
        may not have reached the device, and donation leaves the state
        chain uncertain either way — count everything in the window as
        lost and let the owner restore from its checkpoint."""
        group, self._active = self._active, None
        with self._pending_lock:
            self._active_batches = 0
        rows = sum(int(getattr(tb, "valid", 0)) for tb, _ in (group or []))
        rows += self._discard_inflight()
        self.crash_recoveries += 1
        _LOG.warning("%s: recovered after crash; %d rows in the window "
                     "counted lost", self.name, rows)
        if self._on_restart is not None:
            self._on_restart(rows)

    def _maybe_gauges(self) -> None:
        tr = self._tracer
        if not tr.enabled or self.groups % _GAUGE_EVERY:
            return
        now = time.perf_counter()
        wall = now - self._mark_t
        if wall > 0:
            # fraction of feed wall time spent waiting on the device
            # fence: ~1.0 = the chip is the bottleneck (perfect
            # overlap), ~0.0 = the host feed is
            tr.gauge("tpu_feed_overlap_efficiency",
                     min(1.0, max(0.0, (self.fence_wait_s
                                        - self._mark_fence_s) / wall)))
        tr.gauge("tpu_feed_inflight", float(len(self._inflight)))
        self._mark_t = now
        self._mark_fence_s = self.fence_wait_s

    # -- observability -----------------------------------------------------
    def counters(self) -> dict:
        return {"feed_groups": self.groups, "feed_batches": self.batches,
                "feed_pending": self.pending(),
                "feed_fences": self.fences,
                "feed_fence_errors": self.fence_errors,
                "feed_fence_wait_s": round(self.fence_wait_s, 6),
                "feed_queue_dwell_s": round(self.queue_dwell_s, 6),
                "feed_queue_dwell_batches": self.dwell_batches,
                "feed_crash_recoveries": self.crash_recoveries}
