"""Multi-host scale-out: DCN x ICI meshes, process-local batch feeding.

Reference: the deployment splits capture across many agents and shards
agents across ingester replicas (server/controller/monitor/ rebalancing,
agent/src/sender/uniform_sender.rs one-TCP-stream-per-type); scaling
past one ingester node is horizontal sharding with no cross-node merge.
The TPU re-design instead forms ONE logical device mesh across hosts:
every host runs this same program, `jax.distributed` wires the
coordination service (the role the reference's controller plays for its
fleet), each host's receiver feeds only its local batch shard, and
window merges ride ICI within a host and DCN across hosts — the
collective backend the task needs where the reference would reach for
NCCL/MPI.

Axis layout follows the scaling-book recipe: the outer (`dcn_data`)
axis maps to host boundaries so the only cross-host traffic is the
window-flush psum/max of sketch state (KBs per second), while the hot
batch axis (`data`) stays inside each host's ICI domain. A
batch-sharded suite over the flattened ("data",) mesh of a multi-host
run therefore still places each record's work on the host that
received it: `process_local_batch` builds the global array from purely
local shards with zero data movement.

Cross-host pod (ISSUE 17): `HostPodCoordinator` stacks a HOST fault
domain on top of the per-device pod ladder (parallel/pod.py).  Each
host runs its own `PodFlowSuite` over its local devices; epoch markers
and per-host epoch contributions cross the DCN through a pluggable
`DcnTransport` — real `jax.distributed` collectives when
`jax.process_count() > 1` (silicon), an in-process `SimulatedDcnTransport`
with seeded marker loss / partition / host-kill injection everywhere
else (CPU CI drives the full ladder deterministically).  The protocol —
marker broadcast over a lossy DCN, deadline exclusion of a whole host,
host kill with rejoin-by-snapshot off the host's snapbus, partition
heal with late-contribution merge-next-epoch — was model-checked BEFORE
this runtime was written: `analysis/model/host_pod.py` proves the
pod-wide conservation ledger (`pod_rows_sent == pod_rows_delivered +
pod_rows_host + pod_rows_lost + pod_rows_pending`, exact in every
reachable state at <=2 faults), and the conformance gate
(`.model-conform.json`) twins that model's transitions onto the methods
below by qualname, so this file cannot drift from the proof silently.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepflow_tpu.models import flow_suite
from deepflow_tpu.models.flow_suite import FlowSuiteConfig
from deepflow_tpu.parallel.pod import (ACTIVE, LOST, EpochResult,
                                       PodFlowSuite)
from deepflow_tpu.runtime.faults import (
    FAULT_DCN_MARKER_LOSS,
    FAULT_DCN_PARTITION,
    FAULT_HOST_LOST,
    default_faults,
)
from deepflow_tpu.runtime.snapbus import SnapshotBus
from deepflow_tpu.runtime.supervisor import ThreadHandle, default_supervisor
from deepflow_tpu.runtime.tracing import default_tracer

__all__ = ["init_distributed", "make_global_mesh", "process_local_batch",
           "local_shard", "HostPodCoordinator", "SimulatedDcnTransport",
           "JaxDcnTransport", "select_transport"]

_LOG = logging.getLogger(__name__)

# the flow-hash host key reuses the staging pack-pool's 5-tuple column
# order (batch/staging.py): packs of one flow stream land on one host,
# so per-flow sketch state never splits across host sketches
_HASH_COLS = ("ip_src", "ip_dst", "port_src", "port_dst", "proto")


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> int:
    """Join (or stand alone in) a multi-host run; returns process count.

    With no arguments this is a no-op for single-host runs (the common
    dev path) — callers can use the same code for 1..N hosts. With a
    coordinator address every host calls this once before touching any
    jax device API (reference analogue: the agent's sync-first startup,
    trident.rs boot ordering).
    """
    if coordinator is None:
        return jax.process_count()
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return jax.process_count()


def make_global_mesh(axes: Sequence[str] = ("data",)) -> Mesh:
    """Mesh over every device of every process.

    1-D (default): one flat `data` axis across all hosts — right for the
    batch-sharded suites (cross-chip traffic happens only at flush).
    2-D ("dcn_data", "data"): outer axis = hosts (DCN), inner = each
    host's chips (ICI), for programs that want explicit host-local
    collectives before a cross-host reduce.
    """
    if len(axes) == 1:
        from deepflow_tpu.parallel.mesh import make_mesh
        return make_mesh(axes=axes)   # one construction path for 1-D
    if len(axes) == 2:
        # jax.devices() orders by process index, so rows = hosts
        arr = np.array(jax.devices()).reshape(jax.process_count(),
                                              jax.local_device_count())
        return Mesh(arr, axes)
    raise ValueError(f"axes must be 1-D or 2-D, got {axes!r}")


def process_local_batch(cols: Dict[str, np.ndarray], mask: np.ndarray,
                        mesh: Mesh, axis: str = "data"
                        ) -> Tuple[Dict, jax.Array]:
    """Assemble the global sharded batch from THIS host's rows only.

    Each host passes the rows its own receiver decoded (local_rows =
    global_rows / process_count, the static-shape contract the Batcher
    already enforces); `make_array_from_process_local_data` places each
    host's shard on its own devices with no cross-host transfer. The
    returned arrays are valid inputs to ShardedFlowSuite/
    ShardedMetricsSuite built over the same mesh.
    """
    sharding = NamedSharding(mesh, P(axis))

    def put(x: np.ndarray) -> jax.Array:
        return jax.make_array_from_process_local_data(sharding, x)

    return {k: put(np.asarray(v)) for k, v in cols.items()}, \
        put(np.asarray(mask))


def local_shard(arr: jax.Array) -> np.ndarray:
    """This host's rows of a `data`-sharded global output (e.g. the
    per-record anomaly scores): fetch only addressable shards.

    Replicated arrays (flush window scalars, out_spec P()) come back
    whole, once — every addressable shard covers the full array, so
    concatenating them would silently duplicate rows."""
    if arr.is_fully_replicated:
        return np.asarray(arr)
    seen = {}
    for s in arr.addressable_shards:
        seen.setdefault(s.index[0].start or 0, s.data)
    return np.concatenate(
        [np.asarray(seen[k]) for k in sorted(seen)])


# ---------------------------------------------------------------------------
# DCN transports
# ---------------------------------------------------------------------------

class _DcnMessage(NamedTuple):
    """One host's epoch contribution crossing the DCN leader-ward.

    ``(host, gen, local_epoch)`` is the leader's dedup key: a rejoin
    re-ships the dead incarnation's unshipped outbox, and a kill landing
    between a send and its outbox pop re-ships an already-delivered
    entry — the model's double-merge mutant is exactly what the dedup
    set prevents.  ``rows == 0`` with ``leaves is None`` is a pure
    participation heartbeat (never merged, never deduped)."""

    host: int
    gen: int
    local_epoch: int
    global_epoch: int
    rows: int
    leaves: Optional[Tuple[np.ndarray, ...]]
    late: bool = False


class SimulatedDcnTransport:
    """In-process DCN with the fault surface of the real one.

    Two channel families: a per-host marker link (leader -> host) and
    one contribution channel (hosts -> leader).  A partition severs BOTH
    directions of one host's link; severed traffic is HELD BACK, not
    dropped, and delivered FIFO at ``heal`` — the healed host's
    contribution then reads as a prior-epoch late merge at the leader
    (the model's ``tl``/``ql`` demotion).  Marker loss
    (``dcn.marker_loss``) is the only way a message vanishes, and the
    caller counts it from the ``False`` return.  Fault injection keys
    are ``host{i}`` so ``--fault 'dcn.partition:count=1,match=host1'``
    targets one host's link, same idiom as the pod's ``shard{i}`` keys.
    """

    collective = False

    def __init__(self, n_hosts: int, *,
                 heal_after_s: Optional[float] = None) -> None:
        self.n_hosts = int(n_hosts)
        self.heal_after_s = heal_after_s
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._marker_q = [collections.deque() for _ in range(n_hosts)]
        self._marker_hold: List[list] = [[] for _ in range(n_hosts)]
        self._contrib_q: collections.deque = collections.deque()
        self._contrib_hold: List[list] = [[] for _ in range(n_hosts)]
        self._link = [True] * n_hosts
        self._severed_at = [0.0] * n_hosts
        self._partitions = 0
        self._heals = 0
        self._closed = False
        self._faults = default_faults()

    # -- link state ---------------------------------------------------------
    def partition(self, host: int) -> None:
        """Sever one host's DCN link (both directions)."""
        with self._cv:
            if not self._link[host]:
                return
            self._link[host] = False
            self._severed_at[host] = time.monotonic()
            self._partitions += 1

    def heal(self, host: Optional[int] = None) -> None:
        """Restore severed links and deliver everything held back, FIFO
        — the healed host sees every missed marker (it contributes for
        the newest), and the leader sees the held contributions as
        prior-epoch arrivals (merged LATE next close, counted
        ``pod_host_late_merges``, never lost)."""
        with self._cv:
            hosts = range(self.n_hosts) if host is None else (host,)
            self._heal_hosts_locked(hosts)

    def _heal_hosts_locked(self, hosts) -> None:
        for h in hosts:
            if self._link[h]:
                continue
            self._link[h] = True
            self._heals += 1
            for m in self._marker_hold[h]:
                self._marker_q[h].append(m)
            self._marker_hold[h].clear()
            for m in self._contrib_hold[h]:
                self._contrib_q.append(m)
            self._contrib_hold[h].clear()
        self._cv.notify_all()

    def _auto_heal_locked(self) -> None:
        if self.heal_after_s is None:
            return
        now = time.monotonic()
        due = [h for h in range(self.n_hosts)
               if not self._link[h]
               and now - self._severed_at[h] >= self.heal_after_s]
        if due:
            self._heal_hosts_locked(due)

    def link_up(self, host: int) -> bool:
        with self._lock:
            return self._link[host]

    # -- marker link (leader -> host) ---------------------------------------
    def send_marker(self, host: int, marker: Dict[str, Any]) -> bool:
        """Returns False when the marker was LOST in transit (the
        ``dcn.marker_loss`` site) — the caller books the loss.  A
        severed link holds the marker back instead (True: held, not
        lost)."""
        if self._faults.enabled and self._faults.should_fire(
                FAULT_DCN_PARTITION, f"host{host}"):
            self.partition(host)
        with self._cv:
            self._auto_heal_locked()
            if self._link[host] and self._faults.enabled \
                    and self._faults.should_fire(
                        FAULT_DCN_MARKER_LOSS, f"host{host}"):
                return False
            if not self._link[host]:
                self._marker_hold[host].append(dict(marker))
            else:
                self._marker_q[host].append(dict(marker))
                self._cv.notify_all()
            return True

    def recv_marker(self, host: int,
                    timeout: float = 0.05) -> Optional[Dict[str, Any]]:
        deadline = time.monotonic() + timeout
        with self._cv:
            while not self._marker_q[host] and not self._closed:
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                self._cv.wait(left)
            if self._marker_q[host]:
                return self._marker_q[host].popleft()
            return None

    # -- contribution channel (host -> leader) ------------------------------
    def send_contribution(self, host: int, msg: _DcnMessage) -> bool:
        with self._cv:
            self._auto_heal_locked()
            if not self._link[host]:
                self._contrib_hold[host].append(msg)
            else:
                self._contrib_q.append(msg)
                self._cv.notify_all()
            return True

    def recv_contributions(self) -> List[_DcnMessage]:
        with self._cv:
            self._auto_heal_locked()
            out = list(self._contrib_q)
            self._contrib_q.clear()
            return out

    # -- observability / lifecycle ------------------------------------------
    def quiet(self) -> bool:
        """Nothing queued or held anywhere on the DCN."""
        with self._lock:
            return (not self._contrib_q
                    and not any(self._marker_q)
                    and not any(self._marker_hold)
                    and not any(self._contrib_hold))

    def counters(self) -> Dict[str, int]:
        with self._lock:
            held = (sum(len(q) for q in self._marker_hold)
                    + sum(len(q) for q in self._contrib_hold))
            return {"dcn_partitions": self._partitions,
                    "dcn_heals": self._heals,
                    "dcn_held_messages": held,
                    "dcn_links_down": sum(1 for up in self._link
                                          if not up)}

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()


class JaxDcnTransport:
    """Real-collective DCN for multiprocess (silicon) runs.

    Collective rendezvous replaces the queue pair: every process closes
    its local host lane, then one ``process_allgather`` moves every
    host's epoch leaves over the DCN and every process computes the
    identical merge (SPMD — there is no distinguished leader, and no
    marker deadline: a straggler host is the collective's timeout, a
    dead host is the collective's error, surfaced to the supervisor
    like any device loss).  Partition/kill are the network's to inject,
    not ours — the simulated transport is where the fault ladder runs.
    """

    collective = True

    def __init__(self) -> None:
        if jax.process_count() <= 1:
            raise ValueError(
                "JaxDcnTransport needs a jax.distributed run "
                "(process_count > 1); use SimulatedDcnTransport")
        self.n_hosts = jax.process_count()
        self.local_host = jax.process_index()

    def exchange(self, leaves: Tuple[np.ndarray, ...],
                 rows: int) -> Tuple[List[Tuple[np.ndarray, ...]],
                                     List[int]]:
        """All-gather (leaves, rows) from every host; returns per-host
        lists indexed by process id."""
        from jax.experimental import multihost_utils
        payload = tuple(leaves) + (np.asarray([rows], np.int64),)
        gathered = multihost_utils.process_allgather(payload)
        per_host_leaves = []
        per_host_rows = []
        for h in range(self.n_hosts):
            per_host_leaves.append(tuple(
                np.asarray(leaf[h]) for leaf in gathered[:-1]))
            per_host_rows.append(int(np.asarray(gathered[-1][h, 0])))
        return per_host_leaves, per_host_rows

    def quiet(self) -> bool:
        return True

    def counters(self) -> Dict[str, int]:
        return {"dcn_partitions": 0, "dcn_heals": 0,
                "dcn_held_messages": 0, "dcn_links_down": 0}

    def close(self) -> None:
        pass


def select_transport(kind: str = "auto", n_hosts: int = 2, *,
                     heal_after_s: Optional[float] = None):
    """'jax' = real collectives (requires a multiprocess run), 'sim' =
    in-process simulated DCN, 'auto' = jax when the process actually
    joined a multi-host coordination service, sim otherwise (CPU CI,
    single-host dev)."""
    if kind not in ("auto", "sim", "jax"):
        raise ValueError(f"transport must be auto|sim|jax, got {kind!r}")
    if kind == "jax" or (kind == "auto" and jax.process_count() > 1):
        return JaxDcnTransport()
    return SimulatedDcnTransport(n_hosts, heal_after_s=heal_after_s)


# ---------------------------------------------------------------------------
# HostPodCoordinator
# ---------------------------------------------------------------------------

class _HostLane:
    """One HOST fault domain: a whole PodFlowSuite, its DCN agent, and
    the coordinator-level slice of the pod-wide conservation ledger.

    The ``base_*`` fields fold in dead incarnations' final pod ledgers
    at rejoin (the lane pod is rebuilt from scratch; its counters must
    not reset pod-wide totals), and ``gen`` bumps per incarnation — it
    rides every contribution as the leader's dedup key."""

    __slots__ = ("idx", "pod", "status", "gen", "outbox", "del_seen",
                 "last_local", "marker_rows", "base_sent",
                 "base_delivered", "base_host", "base_lost", "base_h2d",
                 "gmerged", "glost", "drop_rows", "rejoin_lost",
                 "stop_ev", "handle", "close_lock")

    def __init__(self, idx: int, pod: PodFlowSuite) -> None:
        self.idx = idx
        self.pod = pod
        self.status = ACTIVE
        self.gen = 0
        self.outbox: List[_DcnMessage] = []   # closed, not yet shipped
        self.del_seen = 0          # lane pod delivered at last local close
        self.last_local: Optional[EpochResult] = None
        self.marker_rows = 0       # epoch membership at marker send
        self.base_sent = 0
        self.base_delivered = 0
        self.base_host = 0
        self.base_lost = 0
        self.base_h2d = 0
        self.gmerged = 0           # rows globally merged (pod-wide delivered)
        self.glost = 0             # taken-for-merge rows the merge lost
        self.drop_rows = 0         # routed to a LOST host: sent AND lost
        self.rejoin_lost = 0       # dead incarnations' unrecoverable pending
        self.stop_ev: Optional[threading.Event] = None
        self.handle: Optional[ThreadHandle] = None
        self.close_lock = threading.Lock()   # serializes local closes


class HostPodCoordinator:
    """The cross-host pod: N host lanes, each a full `PodFlowSuite`,
    coordinated into pod-wide merge epochs over a DCN transport.

    `put_lanes(plane, n)` routes each row to a host by the SAME flow
    hash the staging pack-pool shards by, so one flow's sketch state
    lives on exactly one host.  `close_epoch()` broadcasts the epoch
    marker to every live host, waits up to `dcn_marker_deadline_s` for
    their contributions, merges what arrived through the SAME stacked
    program the single-host pod merges through, and counts the rest: a
    host past the deadline is EXCLUDED, not awaited (`pod_hosts_missed`,
    `pod_host_rows_excluded`) — its contribution merges LATE next epoch
    (`pod_host_late_merges`), tagged lossy, exactly the single-host
    pod's straggler contract one level up.

    The conservation ledger `pod_rows_sent == pod_rows_delivered +
    pod_rows_host + pod_rows_lost + pod_rows_pending` holds at every
    instant (model-proven in analysis/model/host_pod.py; `counters()`
    snapshots it under one lock so ci.sh asserts it off one scrape).
    """

    def __init__(self, cfg: FlowSuiteConfig,
                 n_hosts: int = 2,
                 shards_per_host: Optional[int] = None, *,
                 transport: Any = "auto",
                 dcn_marker_deadline_s: float = 5.0,
                 merge_deadline_s: float = 5.0,
                 epoch_s: Optional[float] = None,
                 snapshot_dir: Optional[str] = None,
                 snapshot_batches: int = 8,
                 queue_batches: int = 64,
                 auto_rejoin: bool = True,
                 name: str = "hostpod") -> None:
        if n_hosts < 2:
            raise ValueError("a cross-host pod needs at least 2 hosts")
        self.cfg = cfg
        self.n_hosts = int(n_hosts)
        self.dcn_marker_deadline_s = float(dcn_marker_deadline_s)
        self.merge_deadline_s = float(merge_deadline_s)
        self.auto_rejoin = bool(auto_rejoin)
        self.name = name
        self._snapshot_dir = snapshot_dir
        self._snapshot_batches = int(snapshot_batches)
        self._queue_batches = int(queue_batches)
        # each lane clamps to the device count itself; on a 1-device CPU
        # host every lane runs 1 shard — the HOST ladder is what this
        # layer adds, the shard ladder below it is pod.py's
        self.shards_per_host = shards_per_host
        self.transport = transport if not isinstance(transport, str) \
            else select_transport(transport, n_hosts)
        self.bus = SnapshotBus(snapshot_dir, name=name)
        last = self.bus.latest_step()
        self._epoch = 0 if last is None else last + 1
        self._lock = threading.Lock()
        self._close_lock = threading.Lock()
        self._lanes = [
            _HostLane(i, self._make_lane_pod(i, 0))
            for i in range(self.n_hosts)]
        # leader dedup: (host, gen, local_epoch) -> global epoch merged,
        # pruned once old enough that no rejoin can re-ship it
        self._merged_keys: Dict[Tuple[int, int, int], int] = {}
        self._lossy_epoch = False
        self._hosts_missed = 0
        self._host_rows_excluded = 0
        self._host_late_merges = 0
        self._host_rejoins = 0
        self._hosts_killed = 0
        self._dup_contribs = 0
        self._markers_sent = 0
        self._markers_lost = 0
        self._marker_errors = 0
        self._epochs = 0
        self._merges = 0
        self._last_merge_s = 0.0
        self._merge_progs: Dict[int, Any] = {}
        template = flow_suite.init(cfg)
        self._treedef = jax.tree_util.tree_structure(template)
        self._leaf_shapes = [x.shape for x in
                             jax.tree_util.tree_leaves(template)]
        self._faults = default_faults()
        self._tracer = default_tracer()
        self._closed = False
        self._epoch_handle: Optional[ThreadHandle] = None
        self._epoch_stop = threading.Event()
        if not getattr(self.transport, "collective", False):
            for ln in self._lanes:
                self._spawn_agent(ln)
        if epoch_s is not None:
            period = float(epoch_s)
            self._epoch_handle = default_supervisor().spawn(
                f"{name}-epochs", lambda: self._epoch_timer(period),
                beat_period_s=period)

    # -- construction helpers -----------------------------------------------
    def _make_lane_pod(self, idx: int, gen: int) -> PodFlowSuite:
        return PodFlowSuite(
            self.cfg, n_shards=self.shards_per_host, wire="lanes",
            merge_deadline_s=self.merge_deadline_s,
            snapshot_dir=self._snapshot_dir,
            snapshot_batches=self._snapshot_batches,
            queue_batches=self._queue_batches, auto_rejoin=True,
            name=f"{self.name}-host{idx}g{gen}")

    def _spawn_agent(self, ln: _HostLane) -> None:
        # each spawn gets its OWN stop event, captured by the closure
        # (pod.py worker idiom): a replacement agent spawned at rejoin
        # can never be halted by its predecessor's stop
        ev = threading.Event()
        ln.stop_ev = ev
        ln.handle = default_supervisor().spawn(
            f"{self.name}-agent{ln.idx}",
            lambda: self._agent_loop(ln, ev), beat_period_s=0.05)

    def _epoch_timer(self, period_s: float) -> None:
        while not self._epoch_stop.wait(period_s):
            default_supervisor().beat()
            try:
                self.close_epoch()
            except Exception:
                _LOG.exception("%s timed epoch close failed", self.name)

    @property
    def n_shards(self) -> int:
        return sum(ln.pod.n_shards for ln in self._lanes)

    @property
    def epoch(self) -> int:
        return self._epoch

    # -- ingest (the model's `send`) ----------------------------------------
    def put_lanes(self, plane: np.ndarray, n: int) -> None:
        """Route one (4, B) packed-lane plane with n valid rows across
        hosts by the staging flow hash.  Each host's slice re-packs
        into a fresh host-local plane padded to that lane's shard
        width.  A LOST host's slice drops COUNTED (`pod_rows_lost`,
        lossy epoch) — pod-wide ingest never blocks on a dead host."""
        n = int(n)
        if n <= 0:
            return
        from deepflow_tpu.utils.u32 import fold_columns_np
        cols = flow_suite.unpack_lanes_np(plane, n)
        key = fold_columns_np(
            [cols[c] for c in _HASH_COLS]) % np.uint32(self.n_hosts)
        for ln in self._lanes:
            sel = np.nonzero(key == np.uint32(ln.idx))[0]
            ni = int(sel.size)
            if ni == 0:
                continue
            with self._lock:
                dead = ln.status != ACTIVE
                if dead:
                    ln.drop_rows += ni
                    self._lossy_epoch = True
            if dead:
                continue
            ns = ln.pod.n_shards
            width = max(ns, -(-ni // ns) * ns)
            sub = np.zeros((plane.shape[0], width), dtype=plane.dtype)
            sub[:, :ni] = plane[:, sel]
            ln.pod.put_lanes(sub, ni)

    # -- host agent (the model's `marker_arrive` / `contribute`) ------------
    def _agent_loop(self, ln: _HostLane,
                    stop_ev: threading.Event) -> None:
        while not stop_ev.is_set():
            default_supervisor().beat()
            marker = self.transport.recv_marker(ln.idx, timeout=0.05)
            if marker is None:
                continue
            if self._faults.enabled and self._faults.should_fire(
                    FAULT_HOST_LOST, f"host{ln.idx}"):
                # the host dies holding the marker: no contribution, no
                # heartbeat — the leader's deadline excludes it and the
                # epoch boundary rejoins it from its snapbus snapshot
                self.kill_host(ln.idx)
                return
            self._pump_host(ln, marker)

    def _pump_host(self, ln: _HostLane, marker: Dict[str, Any]) -> None:
        """One marker taken off the host's DCN link (the model's
        `marker_arrive`): contribute for the epoch it names."""
        try:
            self._host_contribute(ln.idx, int(marker["epoch"]))
        except Exception:
            # counted, not swallowed: a failed contribution leaves the
            # host un-responded for this epoch, so the leader's deadline
            # excludes it — the ledger must show the failure happened
            with self._lock:
                self._marker_errors += 1
            _LOG.exception("%s host %d contribution failed",
                           self.name, ln.idx)

    def _host_contribute(self, idx: int, ep: int) -> None:
        """Close the host's LOCAL epoch, ship every outbox entry
        leader-ward (oldest first), then a participation heartbeat.
        Entries survive in the outbox until the transport takes them —
        a kill mid-ship re-ships at rejoin and the leader dedups."""
        ln = self._lanes[idx]
        if ln.status != ACTIVE:
            return
        self._local_close(ln)
        while True:
            with self._lock:
                if not ln.outbox or ln.status != ACTIVE:
                    break
                c = ln.outbox[0]
            msg = c._replace(global_epoch=ep,
                             late=c.late or c.global_epoch < ep)
            self.transport.send_contribution(idx, msg)
            with self._lock:
                if ln.outbox and ln.outbox[0] is c:
                    ln.outbox.pop(0)
        self.transport.send_contribution(idx, _DcnMessage(
            host=idx, gen=ln.gen, local_epoch=-1, global_epoch=ep,
            rows=0, leaves=None))

    def _local_close(self, ln: _HostLane) -> int:
        """Close one local pod epoch and capture its merged snapbus
        snapshot into the outbox; returns the rows captured.  The bus
        leaves ARE the contribution — host-side numpy, exactly what a
        rejoin restores — so 'ship the epoch' and 'snapshot the epoch'
        are one artifact (the model's `snapshot` == restorable wire)."""
        with ln.close_lock:
            ln.last_local = ln.pod.close_epoch(now=time.time())
            pc = ln.pod.counters()
            rows = pc["pod_rows_delivered"] - ln.del_seen
            if rows <= 0:
                return 0
            snap = ln.pod.bus.latest()
            if snap is None:
                # delivered rows with no published snapshot should be
                # impossible (the merge publishes before returning);
                # count them lost rather than strand them pending
                with self._lock:
                    ln.glost += rows
                    ln.del_seen = pc["pod_rows_delivered"]
                    self._lossy_epoch = True
                return 0
            msg = _DcnMessage(
                host=ln.idx, gen=ln.gen, local_epoch=int(snap.step),
                global_epoch=self._epoch, rows=rows,
                leaves=tuple(snap.leaves))
            with self._lock:
                ln.del_seen = pc["pod_rows_delivered"]
                ln.outbox.append(msg)
            return rows

    def snapshot_host(self, idx: int) -> int:
        """Force one local epoch close on a host mid-global-epoch (the
        model's `snapshot`): its accumulation lands on the host snapbus
        AND the outbox, so a kill right after loses nothing of it."""
        ln = self._lanes[idx]
        if ln.status != ACTIVE:
            return 0
        return self._local_close(ln)

    # -- leader (the model's `close_epoch` / `deliver` / `deadline_merge`) --
    def close_epoch(self, now: Optional[float] = None,
                    deadline_s: Optional[float] = None) -> EpochResult:
        """Broadcast the epoch marker over the DCN, collect host
        contributions up to the marker deadline, merge, count the rest.
        LOST hosts rejoin at this boundary when auto_rejoin is on."""
        with self._close_lock:
            if getattr(self.transport, "collective", False):
                return self._close_epoch_collective(now)
            return self._close_epoch_serialized(now, deadline_s)

    def _close_epoch_serialized(self, now: Optional[float],
                                deadline_s: Optional[float]
                                ) -> EpochResult:
        t0 = time.perf_counter()
        ep = self._epoch
        with self._lock:
            live = [ln for ln in self._lanes if ln.status == ACTIVE]
            lost_now = [ln.idx for ln in self._lanes
                        if ln.status == LOST]
            lossy0 = self._lossy_epoch
        idle = (not lossy0 and not lost_now
                and len(live) == self.n_hosts
                and self.transport.quiet()
                and all(not ln.outbox and ln.pod.pending_rows() == 0
                        for ln in live))
        if idle:
            return EpochResult(ep, None, {}, [], [], [], [], 0, [],
                               False)
        for ln in live:
            with self._lock:
                ln.marker_rows = (ln.pod.pending_rows()
                                  + sum(c.rows for c in ln.outbox))
                self._markers_sent += 1
            if not self.transport.send_marker(
                    ln.idx, {"epoch": ep, "host": ln.idx}):
                with self._lock:
                    self._markers_lost += 1
                    self._lossy_epoch = True
        deadline = time.monotonic() + (self.dcn_marker_deadline_s
                                       if deadline_s is None
                                       else float(deadline_s))
        want = {ln.idx for ln in live}
        arrived: List[_DcnMessage] = []
        while time.monotonic() < deadline:
            arrived.extend(self._collect())
            if want <= {m.host for m in arrived
                        if m.global_epoch == ep}:
                break
            time.sleep(0.002)
        arrived.extend(self._collect())
        res = self._merge_global(ep, arrived, live, lost_now, now, t0)
        self._epoch = ep + 1
        if self.auto_rejoin:
            for i in lost_now:
                self.rejoin_host(i)
        tr = self._tracer
        if tr.enabled:
            tr.gauge("pod_hosts_active",
                     float(sum(1 for ln in self._lanes
                               if ln.status == ACTIVE)))
            tr.gauge("pod_hosts_missed", float(self._hosts_missed))
            tr.gauge("pod_merge_epoch_s", self._last_merge_s)
        return res

    def _collect(self) -> List[_DcnMessage]:
        """Take contributions off the DCN channel (the model's
        `deliver`)."""
        return self.transport.recv_contributions()

    def _merge_global(self, ep: int, arrived: List[_DcnMessage],
                      live: List[_HostLane], lost_now: List[int],
                      now: Optional[float], t0: float) -> EpochResult:
        """Merge the epoch's host contributions through the same
        stacked program the single-host pod merges shards through, and
        settle the pod-wide ledger: dedup'd re-ships skipped, missed
        live hosts excluded-not-awaited, prior-epoch arrivals merged
        LATE, a merge crash counting its taken rows LOST before it
        surfaces.  The sanctioned device sync of the cross-host path."""
        with self._lock:
            lossy = self._lossy_epoch
            self._lossy_epoch = False
            take: List[_DcnMessage] = []
            for m in arrived:
                if m.leaves is None or m.rows <= 0:
                    continue
                k = (m.host, m.gen, m.local_epoch)
                if k in self._merged_keys:
                    self._dup_contribs += 1
                    continue
                take.append(m)
            responded = {m.host for m in arrived
                         if m.global_epoch == ep}
            missed = sorted(ln.idx for ln in live
                            if ln.idx not in responded)
            for i in missed:
                self._hosts_missed += 1
                self._host_rows_excluded += self._lanes[i].marker_rows
            late = [m for m in take
                    if m.global_epoch < ep or m.late]
            lossy = (lossy or bool(missed) or bool(late)
                     or bool(lost_now))
        out = None
        rows = 0
        merged_state = None
        if take:
            try:
                prog = self._merge_progs.get(len(take))
                if prog is None:
                    prog = self._make_merge(len(take))
                    self._merge_progs[len(take)] = prog
                stacked_leaves = [
                    jnp.asarray(np.stack([m.leaves[j] for m in take]))
                    for j in range(len(self._leaf_shapes))]
                stacked = jax.tree_util.tree_unflatten(
                    self._treedef, stacked_leaves)
                merged_state, out = prog(stacked)
                rows = int(np.asarray(out.rows))
            except Exception:
                # the cross-host merge itself died: the taken
                # contributions cannot deliver — count them LOST (and
                # dedup them: a rejoin re-ship must not resurrect rows
                # the ledger already settled) before surfacing
                with self._lock:
                    for m in take:
                        self._lanes[m.host].glost += m.rows
                        self._merged_keys[
                            (m.host, m.gen, m.local_epoch)] = ep
                    self._lossy_epoch = True
                raise
        participated = sorted({m.host for m in take}
                              | {i for i in responded
                                 if self._lanes[i].status == ACTIVE})
        tags = self._epoch_tags(ep, participated, missed, lost_now,
                                lossy, rows, live)
        if merged_state is not None:
            self.bus.publish(merged_state, step=ep, wall_time=now,
                             to_disk=rows > 0, tags=tags)
        with self._lock:
            for m in take:
                ln = self._lanes[m.host]
                ln.gmerged += m.rows
                self._merged_keys[(m.host, m.gen, m.local_epoch)] = ep
                if m.global_epoch < ep or m.late:
                    self._host_late_merges += 1
            if take:
                self._merges += 1
            self._epochs += 1
            self._last_merge_s = time.perf_counter() - t0
            # prune dedup keys no rejoin can re-ship any more (an
            # outbox entry never outlives its host by this many epochs)
            if len(self._merged_keys) > 4096:
                self._merged_keys = {
                    k: e for k, e in self._merged_keys.items()
                    if ep - e < 64}
        return EpochResult(ep, out, tags, participated, missed, [],
                           lost_now, rows, [], lossy)

    def _epoch_tags(self, ep: int, participated: List[int],
                    missed: List[int], lost: List[int], lossy: bool,
                    rows: int, live: List[_HostLane]) -> dict:
        # host-level participation beside the aggregated shard-level
        # tags the single-host pod publishes: serving answers and the
        # anomaly plane read BOTH ladders off one window
        missing = sorted(set(missed) | set(lost))
        shard_part = 0
        for ln in live:
            if ln.idx in participated and ln.last_local is not None:
                shard_part += len(ln.last_local.participated)
        return {"epoch": ep,
                "pod_hosts": self.n_hosts,
                "pod_hosts_participated": len(participated),
                "pod_hosts_missing": missing,
                "pod_shards": self.n_shards,
                "pod_shards_participated": shard_part,
                "pod_participated": participated,
                "pod_missing": missing,
                "pod_degraded": [],
                "lossy": bool(lossy), "rows": rows}

    def _make_merge(self, m: int):
        from deepflow_tpu.parallel import sharded as _sh

        cfg = self.cfg

        def pod_merge(stacked):
            merged = _sh._merge_axis0(stacked)
            merged = _sh.rescore_ring(merged)
            _fresh, out = flow_suite.flush(merged, cfg)
            return merged, out

        return jax.jit(pod_merge)

    def _close_epoch_collective(self, now: Optional[float]
                                ) -> EpochResult:
        """Collective (multiprocess) epoch close: every process closes
        its LOCAL host lane, all-gathers (leaves, rows) over the DCN,
        and computes the identical merge — no marker deadline, no
        leader; a dead host is the collective's error."""
        t0 = time.perf_counter()
        ep = self._epoch
        ln = self._lanes[self.transport.local_host % self.n_hosts]
        self._local_close(ln)
        with self._lock:
            box, ln.outbox = ln.outbox, []
        rows_local = sum(m.rows for m in box)
        if box:
            leaves = [np.stack([m.leaves[j] for m in box]).sum(axis=0)
                      if len(box) > 1 else np.asarray(box[0].leaves[j])
                      for j in range(len(self._leaf_shapes))]
        else:
            leaves = [np.zeros(s, np.uint32) for s in self._leaf_shapes]
        per_host_leaves, per_host_rows = self.transport.exchange(
            tuple(leaves), rows_local)
        take = [h for h, r in enumerate(per_host_rows) if r > 0]
        out = None
        rows = 0
        if take:
            prog = self._merge_progs.get(len(take))
            if prog is None:
                prog = self._make_merge(len(take))
                self._merge_progs[len(take)] = prog
            stacked_leaves = [
                jnp.asarray(np.stack([per_host_leaves[h][j]
                                      for h in take]))
                for j in range(len(self._leaf_shapes))]
            merged_state, out = prog(jax.tree_util.tree_unflatten(
                self._treedef, stacked_leaves))
            rows = int(np.asarray(out.rows))
            with self._lock:
                ln.gmerged += rows_local
            tags = self._epoch_tags(ep, take, [], [], False, rows,
                                    [ln])
            self.bus.publish(merged_state, step=ep, wall_time=now,
                             to_disk=rows > 0, tags=tags)
        else:
            tags = {}
        with self._lock:
            self._epochs += 1
            if take:
                self._merges += 1
            self._last_merge_s = time.perf_counter() - t0
        self._epoch = ep + 1
        return EpochResult(ep, out, tags, take, [], [], [], rows, [],
                           False)

    # -- kill / rejoin (the model's `kill` / epoch-boundary rejoin) ---------
    def kill_host(self, idx: int) -> None:
        """Lose a whole host: its lane pod freezes (workers stopped, no
        final merge), its DCN agent exits, everything in its pipeline
        past the last local close stays in the dead pod's ledger until
        `rejoin_host` settles it.  Chaos drives this directly; the
        `host.lost` fault site fires it from inside the host agent."""
        ln = self._lanes[idx]
        with self._lock:
            if ln.status != ACTIVE:
                return
            ln.status = LOST
            self._hosts_killed += 1
            self._lossy_epoch = True
        if ln.stop_ev is not None:
            ln.stop_ev.set()
        if ln.handle is not None:
            ln.handle.stop()
        ln.pod.close(final_epoch=False)
        _LOG.warning("%s host %d LOST (outbox=%d entries held for "
                     "rejoin)", self.name, idx, len(ln.outbox))

    def rejoin_host(self, idx: int) -> bool:
        """Rejoin-by-snapshot at an epoch boundary: the dead
        incarnation's final ledger folds into the lane's base counters
        (its un-closed pipeline counted LOST — the model's
        `rows - snap`), its unshipped outbox — the snapbus snapshots a
        kill could not destroy — re-ships LATE so those rows DELIVER
        instead of vanishing, and a fresh PodFlowSuite incarnation
        (gen+1) takes over ingest."""
        ln = self._lanes[idx]
        with self._lock:
            if ln.status != LOST:
                return False
            box, ln.outbox = ln.outbox, []
        if ln.handle is not None and ln.handle.thread is not \
                threading.current_thread():
            ln.handle.join(timeout=2.0)
        fin = ln.pod.counters()
        with self._lock:
            ln.base_sent += fin["pod_rows_sent"]
            ln.base_delivered += fin["pod_rows_delivered"]
            ln.base_host += fin["pod_rows_host"]
            ln.base_lost += fin["pod_rows_lost"]
            ln.base_h2d += ln.pod.h2d_bytes
            ln.rejoin_lost += fin["pod_rows_pending"]
            ln.gen += 1
            ln.del_seen = 0
            self._host_rejoins += 1
        recovered = 0
        for m in box:
            self.transport.send_contribution(idx, m._replace(late=True))
            recovered += m.rows
        ln.pod = self._make_lane_pod(idx, ln.gen)
        ln.last_local = None
        with self._lock:
            ln.status = ACTIVE
        if not getattr(self.transport, "collective", False):
            self._spawn_agent(ln)
        _LOG.warning("%s host %d rejoined gen %d (%d rows re-shipped "
                     "from its snapshots, %d counted lost)", self.name,
                     idx, ln.gen, recovered,
                     fin["pod_rows_pending"])
        return True

    # -- lifecycle / observability ------------------------------------------
    def drain(self, timeout: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all(ln.status != ACTIVE or ln.pod.drain(timeout=0.1)
                   for ln in self._lanes):
                return True
            time.sleep(0.005)
        return False

    def close(self, final_epoch: bool = True) -> Optional[EpochResult]:
        """Final pod-wide merge (one extra epoch when stragglers or
        held-back traffic remain), then stop agents and lane pods."""
        self._epoch_stop.set()
        if self._epoch_handle is not None:
            self._epoch_handle.stop()
            self._epoch_handle.join(timeout=2.0)
        res = None
        if final_epoch and not self._closed:
            self.drain(timeout=10.0)
            res = self.close_epoch()
            leftovers = (not self.transport.quiet()
                         or any(ln.outbox for ln in self._lanes))
            if leftovers:
                time.sleep(0.01)
                res = self.close_epoch()
        self._closed = True
        for ln in self._lanes:
            if ln.stop_ev is not None:
                ln.stop_ev.set()
            if ln.handle is not None:
                ln.handle.stop()
        self.transport.close()
        for ln in self._lanes:
            if ln.handle is not None and ln.handle.thread is not \
                    threading.current_thread():
                ln.handle.join(timeout=2.0)
        for ln in self._lanes:
            ln.pod.close(final_epoch=False)
        return res

    def pending_rows(self) -> int:
        with self._lock:
            return self._pending_rows_locked()

    def _pending_rows_locked(self) -> int:
        n = 0
        for ln in self._lanes:
            pc = ln.pod.counters()
            residual = (ln.base_delivered + pc["pod_rows_delivered"]
                        - ln.gmerged - ln.glost)
            n += pc["pod_rows_pending"] + max(0, residual)
        return n

    def host_status(self) -> List[dict]:
        with self._lock:
            return [{"host": ln.idx, "status": ln.status,
                     "gen": ln.gen, "rows_merged": ln.gmerged,
                     "rows_dropped": ln.drop_rows,
                     "rows_lost_rejoin": ln.rejoin_lost,
                     "outbox": len(ln.outbox),
                     "link_up": (self.transport.link_up(ln.idx)
                                 if hasattr(self.transport, "link_up")
                                 else True)}
                    for ln in self._lanes]

    def shard_status(self) -> List[dict]:
        out = []
        base = 0
        for ln in self._lanes:
            for s in ln.pod.shard_status():
                row = dict(s)
                row["shard"] = base + int(s["shard"])
                row["host"] = ln.idx
                if ln.status == LOST:
                    row["status"] = LOST
                out.append(row)
            base += ln.pod.n_shards
        return out

    @property
    def h2d_bytes(self) -> int:
        """Host-to-device bytes every host's shard workers put, dead
        incarnations included."""
        return sum(ln.base_h2d + ln.pod.h2d_bytes for ln in self._lanes)

    def counters(self) -> dict:
        """The pod-WIDE ledger, one consistent snapshot: every term of
        the conservation equality reads under one lock, and each lane
        pod's own counters() is itself one locked snapshot — the
        identity `pod_rows_sent == pod_rows_delivered + pod_rows_host +
        pod_rows_lost + pod_rows_pending` holds off a single scrape
        (model-proven; ci.sh asserts it mid-chaos)."""
        with self._lock:
            sent = delivered = host = lost = pending = 0
            for ln in self._lanes:
                pc = ln.pod.counters()
                sent += ln.base_sent + pc["pod_rows_sent"] \
                    + ln.drop_rows
                delivered += ln.gmerged
                host += ln.base_host + pc["pod_rows_host"]
                lost += (ln.base_lost + pc["pod_rows_lost"]
                         + ln.drop_rows + ln.rejoin_lost + ln.glost)
                residual = (ln.base_delivered
                            + pc["pod_rows_delivered"]
                            - ln.gmerged - ln.glost)
                pending += pc["pod_rows_pending"] + max(0, residual)
            active = sum(1 for ln in self._lanes
                         if ln.status == ACTIVE)
            c = {"pod_hosts": self.n_hosts,
                 "pod_hosts_active": active,
                 "pod_hosts_lost": self.n_hosts - active,
                 "pod_hosts_killed": self._hosts_killed,
                 "pod_hosts_missed": self._hosts_missed,
                 "pod_host_rows_excluded": self._host_rows_excluded,
                 "pod_host_late_merges": self._host_late_merges,
                 "pod_host_rejoins": self._host_rejoins,
                 "pod_dup_contributions": self._dup_contribs,
                 "pod_shards": self.n_shards,
                 "pod_epochs": self._epochs,
                 "pod_merges": self._merges,
                 "pod_merge_epoch_s": round(self._last_merge_s, 6),
                 "pod_rows_sent": sent,
                 "pod_rows_delivered": delivered,
                 "pod_rows_host": host,
                 "pod_rows_lost": lost,
                 "pod_rows_pending": pending,
                 "dcn_markers_sent": self._markers_sent,
                 "dcn_markers_lost": self._markers_lost,
                 "pod_marker_errors": self._marker_errors}
            c.update(self.transport.counters())
        return c
