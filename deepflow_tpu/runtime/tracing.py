"""Pipeline flight recorder: spans, latency histograms, TPU attribution.

The framework's core claim is zero-instrumentation observability of OTHER
people's systems; this module is the half of self-observation the
Countable registry (runtime/stats.py) doesn't cover — *where a batch's
wall time goes*. Every hot-path stage (receiver dispatch, decode, queue
dwell, export, the feed's dispatch and starvation, window close)
records spans into:

- a fixed-size ring of completed spans (the "flight recorder": the last
  N spans survive for post-hoc inspection through the `spans` debug
  command even after the workload that produced them has moved on), and
- per-stage host-side DDSketch histograms (the pure-Python mirror of
  ops/ddsketch.py's quantile math: geometric buckets, bounded RELATIVE
  error), so p50/p95/p99 per stage are queryable at any time without
  keeping raw samples.

Batch causality rides a monotonically increasing `batch_id`: the
receiver stamps one on every frame, the decoder anchors its chunk to the
first frame's id and hands it to the exporter fan-out, and the sketch
exporters carry it onto the feed thread's dispatch span — so one slow
batch can be followed receiver -> decode -> export -> kernel from the
span ring.

Every context-manager span also lands in two more places while the
tracer is enabled:

- a `jax.profiler.TraceAnnotation` named exactly as the stage, so a
  device trace's host plane carries the program's own stages on the
  device's clock (an idle gap on the chip is then named by the host
  stage that overlapped it). The annotation class is resolved in
  `enable()`, so importing this module never imports JAX;
- the thread's CPU seconds (`time.thread_time`) under the companion
  stage `cpu.<stage>`, in the stage histograms only (not the ring, so
  the ring's horizon does not shrink). Wall minus CPU is the time the
  stage waited: for the interpreter lock, a lock, or a queue.

The `observe()` form records wall time only, with no annotation.

Cost discipline (the design constraint everything here bends around):

- DISABLED (default): `span()` returns a shared no-op context manager —
  zero allocations, no clock read, no annotation; hot call sites
  additionally guard on `tracer.enabled` so not even an argument tuple
  is built.
- ENABLED: one perf_counter pair, one thread_time pair, one annotation
  and two histogram adds + one ring store per span, a few microseconds
  against millisecond-scale batch stages. Spans are per *batch/frame*,
  never per record.

The ring is lock-free-ish: writers do an unsynchronized
reserve-and-store (`i = n; n = i + 1`), relying on the GIL for memory
safety. Two racing writers may very occasionally overwrite one another's
slot or skip one — an acceptable loss for a diagnostic buffer that must
never serialize the hot path. Reads snapshot under a lock.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Callable, Dict, List, Optional

__all__ = ["HostDDSketch", "Tracer", "default_tracer", "GAUGE_HELP",
           "gauge_help"]

# HELP strings for the well-known tracer gauges (rendered into the
# Prometheus exposition by runtime/promexpo.py, whose strict checker now
# FAILS any gauge without one — a scrape must explain itself). The
# ISSUE 5 feed gauges: transfers_per_batch is the coalescing-regression
# signal (a slide back to per-plane device_puts reads > 1),
# overlap_efficiency the device-busy proxy. The ISSUE 6 audit gauges
# are the accuracy observatory's window verdicts (runtime/audit.py).
GAUGE_HELP: Dict[str, str] = {
    "tpu_transfers_per_batch": "device_put calls per TensorBatch on the "
                               "sketch lane; the coalesced feed holds "
                               "this at <= 1",
    "tpu_h2d_coalesced_bytes": "bytes of the last sampled coalesced "
                               "staging transfer",
    "tpu_feed_overlap_efficiency": "fraction of feed-thread wall time "
                                   "spent waiting on the device fence "
                                   "(~1 = chip-bound, ~0 = host-bound)",
    "tpu_feed_inflight": "dispatched-but-unfenced updates in the "
                         "prefetch window",
    "mesh_h2d_mb_s": "sampled host->device transfer rate of the "
                     "sharded mesh lane (blocking measurement every "
                     "Nth put_batch)",
    "tpu_audit_cms_rel_error": "observed CMS point-estimate error on "
                               "audited heavy hitters, relative to the "
                               "window's row count (exact shadow)",
    "tpu_audit_cms_eps_headroom": "theoretical CMS epsilon (e/width) "
                                  "minus the observed error; negative "
                                  "= out of bound",
    "tpu_audit_hll_rel_error": "observed HLL cardinality error vs the "
                               "distinct-sampled exact shadow",
    "tpu_audit_hll_eps_headroom": "HLL error bound (sketch epsilon + "
                                  "shadow sampling noise) minus the "
                                  "observed error; negative = out of "
                                  "bound",
    "tpu_audit_entropy_abs_error": "max abs difference between device "
                                   "and exact-shadow normalized "
                                   "entropy across the 4 features",
    "tpu_audit_topk_recall": "fraction of the shadow's exact top "
                             "ceil(rate*K) sampled keys present in the "
                             "device top-K output",
    "tpu_audit_sampled_keys": "distinct flow keys in the exact shadow "
                              "at the last window close",
    "tpu_audit_degraded_window": "1 when the last audited window ran "
                                 "on the degraded host-fallback lane",
    "tpu_audit_detection_precision": "clean-window precision of the "
                                     "anomaly plane's entropy-DDoS "
                                     "verdict vs the exact shadow's "
                                     "twin scorer (advisory below "
                                     "full audit rate)",
    "tpu_audit_detection_recall": "clean-window recall of the anomaly "
                                  "plane's entropy-DDoS verdict vs "
                                  "the exact shadow's twin scorer",
    # the ISSUE 15 anomaly plane (deepflow_tpu/anomaly/): detection
    # lane health beside the sketch lane
    "anomaly_score": "max detector score at the last window close "
                     "(z units for entropy/PCA, z-normalized distance "
                     "for the matrix profile)",
    "anomaly_alerts_total": "cumulative alerts emitted across all "
                            "detectors since start",
    "anomaly_detect_latency_windows": "windows between the last "
                                      "alert's excursion onset and its "
                                      "first emission (> 0 only when "
                                      "unscored windows intervened)",
    "anomaly_active_flows": "active-flow working-set slots seen in the "
                            "last closed window (device-resident "
                            "table, LRU-by-window)",
    # the ISSUE 7 sketch-serving read path (serving/tables.py): read
    # traffic answered from the in-process snapshot cache — these are
    # the dashboard-QPS acceptance gauges
    "querier_read_qps": "sketch point queries answered per second "
                        "over the last gauge window (snapshot-cache "
                        "reads; never a device sync)",
    "querier_read_p99_s": "p99 latency of sketch point queries in "
                          "seconds (host DDSketch over all reads)",
    "sketch_snapshot_staleness_s": "age of the newest published sketch "
                                   "snapshot at the last read; the "
                                   "staleness-bounded-read contract is "
                                   "staleness <= max_staleness_s "
                                   "whenever ingest is flushing windows",
    # the ISSUE 10 pod fault-domain gauges (parallel/pod.py): epoch-
    # merge health of the sharded sketch plane
    "pod_shards_active": "shards on the device lane after the last "
                         "merge epoch (out of pod_shards; lower = "
                         "degraded/lost fault domains)",
    "pod_merge_epoch_s": "wall seconds the last deadline-bounded epoch "
                         "merge took (marker post -> merged publish)",
    "pod_merge_missed": "cumulative shard contributions that missed "
                        "their epoch's merge deadline (each counted "
                        "row rides pod_rows_excluded until it merges "
                        "late)",
}

# dynamically-named gauges get HELP by prefix (one entry documents the
# whole family; promexpo resolves through gauge_help below)
GAUGE_HELP_PREFIXES: Dict[str, str] = {
    "tpu_compile_s_": "first-call XLA compile seconds of the named "
                      "update program (cold compiles attributed apart "
                      "from steady-state kernel quantiles)",
}


def gauge_help(name: str) -> str:
    """HELP text for a tracer gauge: exact entry, then prefix family,
    else empty (which the strict exposition checker flags)."""
    text = GAUGE_HELP.get(name)
    if text is not None:
        return text
    for prefix, ptext in GAUGE_HELP_PREFIXES.items():
        if name.startswith(prefix):
            return ptext
    return ""


class HostDDSketch:
    """Host-side (pure Python + array-module-free) DDSketch mirror of
    ops/ddsketch.py: values land in geometric buckets
    (gamma = (1+alpha)/(1-alpha)); any quantile reads back with bounded
    relative error alpha; sketches merge by elementwise add. Sized for
    durations in SECONDS: with alpha=0.01 and 1024 buckets the range
    spans min_value=1us to ~770s, wider than any sane pipeline stage."""

    __slots__ = ("alpha", "min_value", "buckets", "gamma", "_inv_log_gamma",
                 "counts", "zeros", "count", "sum", "max")

    def __init__(self, alpha: float = 0.01, min_value: float = 1e-6,
                 buckets: int = 1024) -> None:
        self.alpha = alpha
        self.min_value = min_value
        self.buckets = buckets
        self.gamma = (1.0 + alpha) / (1.0 - alpha)
        self._inv_log_gamma = 1.0 / math.log(self.gamma)
        self.counts = [0] * buckets
        self.zeros = 0          # values below min_value
        self.count = 0
        self.sum = 0.0
        self.max = 0.0

    def add(self, v: float) -> None:
        self.count += 1
        self.sum += v
        if v > self.max:
            self.max = v
        if v < self.min_value:
            self.zeros += 1
            return
        i = int(math.ceil(math.log(v / self.min_value)
                          * self._inv_log_gamma))
        if i < 0:
            i = 0
        elif i >= self.buckets:
            i = self.buckets - 1
        self.counts[i] += 1

    def quantile(self, q: float) -> float:
        """q-quantile estimate (same bucket-midpoint readback as
        ops/ddsketch.quantile); 0.0 when empty or below min_value."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        if target <= self.zeros:
            return 0.0
        acc = self.zeros
        idx = self.buckets - 1
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= target:
                idx = i
                break
        g = self.gamma
        return self.min_value * (2.0 * g ** idx) / (g + 1.0)

    def merge(self, other: "HostDDSketch") -> None:
        """Exact union (DDSketch's defining property) — bucket layouts
        must match (same alpha/min_value/buckets)."""
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.zeros += other.zeros
        self.count += other.count
        self.sum += other.sum
        if other.max > self.max:
            self.max = other.max

    def cumulative_buckets(self, stride: int = 32) -> List[tuple]:
        """[(upper_bound_seconds, cumulative_count)] at every stride-th
        gamma boundary — the Prometheus `le` bucket series (the +Inf
        bucket is `count` and is the caller's to append). Values below
        min_value (zeros) sit under every boundary."""
        return self.snapshot(stride)[0]

    def snapshot(self, stride: int = 32) -> tuple:
        """(cumulative_buckets, total, sum) derived from ONE copy of
        the bucket array: writers add() concurrently without a lock,
        so a renderer that read buckets and `count` separately could
        emit a +Inf bucket that disagrees with _count and fail its own
        strict validator — everything here is internally consistent by
        construction (total == the last cumulative value)."""
        counts = list(self.counts)
        zeros = self.zeros
        sum_ = self.sum
        out = []
        acc = zeros
        g = self.gamma
        for i in range(0, self.buckets, stride):
            for j in range(i, min(i + stride, self.buckets)):
                acc += counts[j]
            out.append((self.min_value
                        * g ** min(i + stride - 1, self.buckets - 1), acc))
        return out, acc, sum_


class _NoopSpan:
    """Shared do-nothing context manager: the disabled-tracer fast path
    allocates NOTHING (one module-level instance serves every call)."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _NoopSpan()


def _annotation_class():
    """The device profiler's host-annotation context manager, or None
    where JAX is not installed. Looked up once, by `Tracer.enable()`."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


class _Span:
    __slots__ = ("_tracer", "stage", "stream", "batch_id", "rows", "t0",
                 "c0", "_ann")

    def __init__(self, tracer: "Tracer", stage: str, stream: str,
                 batch_id: int, rows: int) -> None:
        self._tracer = tracer
        self.stage = stage
        self.stream = stream
        self.batch_id = batch_id
        self.rows = rows
        self.t0 = 0.0
        self.c0 = 0.0
        self._ann = None

    def __enter__(self) -> "_Span":
        ann = self._tracer._annotation
        if ann is not None:
            self._ann = ann(self.stage)
            self._ann.__enter__()
        # wall clock outside the CPU clock on both ends, so the CPU
        # interval lies inside the wall interval
        self.t0 = time.perf_counter()
        self.c0 = time.thread_time()
        return self

    def __exit__(self, *exc) -> bool:
        cpu = time.thread_time() - self.c0
        wall = time.perf_counter() - self.t0
        tr = self._tracer
        tr.observe(self.stage, wall, stream=self.stream,
                   batch_id=self.batch_id, rows=self.rows, t0=self.t0)
        # the two clocks tick apart: a thread that ran the whole span
        # can read a hair more CPU than wall, which it cannot have used
        tr._add("cpu." + self.stage, min(cpu, wall))
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        return False


class Tracer:
    """Span recorder + per-stage latency histograms + gauges.

    Disabled by default; `Ingester` enables the process default when
    cfg.trace_enabled (the CLI `trace` family and the Prometheus
    endpoint read from it). One Tracer serves the whole process — the
    flight-recorder role is process-scoped, like the `stacks` debug
    command (a second in-process ingester's spans land in the same ring,
    distinguishable by stream labels)."""

    def __init__(self, ring: int = 4096, alpha: float = 0.01,
                 min_value_s: float = 1e-6, buckets: int = 1024) -> None:
        self.enabled = False
        self._ring: List[Optional[tuple]] = [None] * ring
        self._ring_cap = ring
        self._n = 0                     # total spans recorded (ever)
        self._alpha = alpha
        self._min_value_s = min_value_s
        self._buckets = buckets
        self._stages: Dict[str, HostDDSketch] = {}
        self._gauges: Dict[str, float] = {}
        self._gauge_stamps: Dict[str, float] = {}  # name -> wall time
        self._lock = threading.Lock()   # reads + stage/gauge creation
        self._batch_seq = 0
        self._tls = threading.local()
        self._annotation: Optional[Callable] = None
        # optional heartbeat hook (set by supervisor.default_supervisor):
        # every recorded span is proof of life for the recording thread,
        # feeding the deadman watchdog for free on traced hot paths
        self.heartbeat: Optional[Callable[[], None]] = None

    # -- lifecycle ---------------------------------------------------------
    def enable(self) -> None:
        if self._annotation is None:
            self._annotation = _annotation_class()
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._ring = [None] * self._ring_cap
            self._n = 0
            self._stages = {}
            self._gauges = {}
            self._gauge_stamps = {}

    # -- batch causality ---------------------------------------------------
    def next_batch(self) -> int:
        """Allocate a batch id (monotonic; GIL-atomic enough — a rare
        duplicate id degrades causality, never correctness)."""
        b = self._batch_seq + 1
        self._batch_seq = b
        return b

    def set_batch(self, batch_id: int) -> None:
        """Pin the calling thread's current batch id (consumed by spans
        recorded with batch_id=-1 — the implicit propagation hop across
        a queue boundary)."""
        self._tls.batch = batch_id

    def current_batch(self) -> int:
        return getattr(self._tls, "batch", -1)

    # -- recording ---------------------------------------------------------
    def span(self, stage: str, stream: str = "", batch_id: int = -1,
             rows: int = 0):
        """Context manager timing one stage execution: wall time into
        the ring and `stage`'s histogram, the thread's CPU time into
        `cpu.<stage>`'s, and a device-profiler annotation named `stage`
        around it. Returns a shared no-op when disabled (zero
        allocations)."""
        if not self.enabled:
            return _NOOP
        return _Span(self, stage, stream, batch_id, rows)

    def observe(self, stage: str, dur_s: float, stream: str = "",
                batch_id: int = -1, rows: int = 0,
                t0: Optional[float] = None) -> None:
        """Record one completed span (the non-context-manager form the
        hot call sites use behind their own `enabled` guard)."""
        if not self.enabled:
            return
        if self.heartbeat is not None:
            self.heartbeat()
        if batch_id < 0:
            batch_id = self.current_batch()
        self._add(stage, dur_s)
        # lock-free-ish reserve-and-store (see module docstring)
        i = self._n
        self._n = i + 1
        self._ring[i % self._ring_cap] = (
            stage, stream, batch_id,
            time.time() if t0 is None else time.time() - dur_s,
            dur_s, rows)

    def _add(self, stage: str, dur_s: float) -> None:
        """One sample into `stage`'s histogram (no ring entry)."""
        sk = self._stages.get(stage)
        if sk is None:
            with self._lock:
                sk = self._stages.setdefault(
                    stage, HostDDSketch(self._alpha, self._min_value_s,
                                        self._buckets))
        sk.add(dur_s)

    def gauge(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        # wall-stamped so the timeline/exposition can tell a live gauge
        # from a fossil: gauges refresh only on their own code path
        # (e.g. snapshot staleness updates only when a read happens), so
        # without the stamp the last value is served forever
        self._gauges[name] = float(value)
        self._gauge_stamps[name] = time.time()

    # -- readback ----------------------------------------------------------
    @property
    def spans_recorded(self) -> int:
        return self._n

    def gauges(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._gauges)

    def gauges_stamped(self) -> Dict[str, tuple]:
        """name -> (value, wall stamp of last write). Gauges written
        before stamping landed (or via direct dict poke in tests) get
        stamp 0.0 — maximally stale, which fails safe."""
        with self._lock:
            return {k: (v, self._gauge_stamps.get(k, 0.0))
                    for k, v in self._gauges.items()}

    def stages(self) -> Dict[str, HostDDSketch]:
        """Snapshot of the stage map (sketches themselves are live)."""
        with self._lock:
            return dict(self._stages)

    def latency(self) -> Dict[str, Dict[str, float]]:
        """{stage: {count, p50_ms, p95_ms, p99_ms, max_ms, mean_ms}} —
        the `trace latency` table."""
        out = {}
        for stage, sk in sorted(self.stages().items()):
            if sk.count == 0:
                continue
            out[stage] = {
                "count": sk.count,
                "p50_ms": sk.quantile(0.50) * 1e3,
                "p95_ms": sk.quantile(0.95) * 1e3,
                "p99_ms": sk.quantile(0.99) * 1e3,
                "max_ms": sk.max * 1e3,
                "mean_ms": (sk.sum / sk.count) * 1e3,
            }
        return out

    def recent(self, n: int = 32, stage: Optional[str] = None,
               slow_ms: Optional[float] = None) -> List[dict]:
        """Most recent completed spans, newest first; optionally only
        one stage, optionally only spans slower than slow_ms."""
        with self._lock:
            total = self._n
            ring = list(self._ring)
        out: List[dict] = []
        for k in range(total - 1, max(total - self._ring_cap, 0) - 1, -1):
            s = ring[k % self._ring_cap]
            if s is None:
                continue
            if stage is not None and s[0] != stage:
                continue
            if slow_ms is not None and s[4] * 1e3 < slow_ms:
                continue
            out.append({"stage": s[0], "stream": s[1], "batch_id": s[2],
                        "ts": s[3], "dur_ms": s[4] * 1e3, "rows": s[5]})
            if len(out) >= n:
                break
        return out

    def counters(self) -> dict:
        """Countable for the stats registry: scrape-friendly totals."""
        c = {"spans": self._n, "batches": self._batch_seq,
             "enabled": 1.0 if self.enabled else 0.0}
        for stage, sk in self.stages().items():
            key = stage.replace(".", "_")
            c[f"{key}_count"] = sk.count
            c[f"{key}_sum_s"] = sk.sum
        return c


_default: Optional[Tracer] = None
_default_lock = threading.Lock()


def default_tracer() -> Tracer:
    """The process flight recorder (mirrors stats.default_registry)."""
    global _default
    with _default_lock:
        if _default is None:
            _default = Tracer()
        return _default
