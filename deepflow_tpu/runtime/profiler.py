"""Continuous occupancy profiler: is the chip earning its keep?

ROADMAP item 2's acceptance bar is a *continuously measured* device-busy
fraction, and until now that number only existed as a one-shot ratio in
bench.py. This module keeps a bounded ring of feed/fence/dispatch/device
spans — fed from the overlapped feed's fence points (runtime/feed.py)
and the sharded-mesh wrappers — and reduces it into live gauges:

- ``tpu_device_busy_fraction``: union length of device-execution
  intervals over a sliding horizon / the horizon. On the feed path an
  interval spans dispatch -> fence retirement, which brackets the real
  execution: the fence can only retire after the program completes,
  and the feed retires every ready fence before it waits for work, so
  retirement follows completion within the feed's poll slice. The
  inline (feed-less) path records no intervals.
- ``tpu_feed_stall_seconds``: cumulative seconds the feed thread waited
  for work with NOTHING in flight — the device was starved by the host,
  the complement of busy that names the culprit.

The ring also exports as a Chrome-trace/Perfetto JSON timeline
(``to_chrome_trace``) through the `trace-export` debug route and
``df-ctl trace export`` — one loadable file showing feed packing, fence
waits and device execution on separate tracks.

Cost discipline mirrors runtime/tracing.py: recording is one tuple
store per *span* (batch/group granularity, never per record), writers
are lock-free-ish reserve-and-store under the GIL, readers snapshot
under a lock. The profiler never blocks on the device itself — it only
timestamps syncs that already exist (the feed fence), so enabling it
cannot change the pipeline's shape.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

__all__ = ["OccupancyProfiler", "default_profiler", "PROFILER_GAUGE_HELP"]

# HELP text for the gauges promexpo renders from this module (the
# strict exposition check fails any gauge without it)
PROFILER_GAUGE_HELP: Dict[str, str] = {
    "tpu_device_busy_fraction":
        "union of device-execution intervals over the sliding horizon "
        "(dispatch->fence retirement on the feed path). ROADMAP item "
        "2's continuously-measured device-busy number",
    "tpu_feed_stall_seconds":
        "cumulative seconds the device sat with an empty in-flight "
        "window before work ARRIVED (host starvation preceding real "
        "work, counted when the next batch arrives; a window with no "
        "batch at all accrues nothing)",
}

# canonical track order for the trace export (tid assignment)
_TRACKS = ("feed", "fence", "dispatch", "device", "h2d", "window")


class OccupancyProfiler:
    """Bounded span ring + occupancy reductions. Process-scoped like
    the Tracer (one chip, one feed — a second in-process exporter's
    spans land on the same tracks, distinguishable by name)."""

    def __init__(self, ring: int = 8192) -> None:
        self._ring: List[Optional[tuple]] = [None] * ring
        self._cap = ring
        self._n = 0                         # total spans recorded (ever)
        self._lock = threading.Lock()       # snapshot reads
        self.stall_s = 0.0                  # cumulative feed starvation
        self.busy_horizon_s = 10.0

    # -- recording ---------------------------------------------------------
    def record(self, track: str, name: str, dur_s: float,
               rows: int = 0, t_end: Optional[float] = None) -> None:
        """One completed span: wall-clock end (time.time) + duration.
        Lock-free-ish reserve-and-store (see runtime/tracing.py)."""
        if dur_s < 0:
            dur_s = 0.0
        i = self._n
        self._n = i + 1
        self._ring[i % self._cap] = (
            track, name, time.time() if t_end is None else t_end,
            dur_s, rows)

    def add_stall(self, dur_s: float) -> None:
        """Feed-thread starvation time (waiting for work with nothing
        in flight)."""
        if dur_s > 0:
            self.stall_s += dur_s

    # -- reductions --------------------------------------------------------
    def _snapshot(self) -> List[tuple]:
        with self._lock:
            total = self._n
            ring = list(self._ring)
        out = []
        for k in range(max(total - self._cap, 0), total):
            s = ring[k % self._cap]
            if s is not None:
                out.append(s)
        return out

    def busy_fraction(self, track: str = "device",
                      horizon_s: Optional[float] = None,
                      now: Optional[float] = None) -> float:
        """Union length of `track` intervals inside the sliding window
        / the window. The window shrinks to the observed span range so
        a short-lived run is not diluted by an idle horizon."""
        horizon = horizon_s if horizon_s is not None else self.busy_horizon_s
        now = time.time() if now is None else now
        lo = now - horizon
        ivals = []
        for tr, _name, t_end, dur, _rows in self._snapshot():
            if tr != track or t_end < lo:
                continue
            ivals.append((max(t_end - dur, lo), min(t_end, now)))
        if not ivals:
            return 0.0
        ivals.sort()
        window_lo = max(lo, min(a for a, _ in ivals))
        covered = 0.0
        cur_a, cur_b = ivals[0]
        for a, b in ivals[1:]:
            if a > cur_b:
                covered += cur_b - cur_a
                cur_a, cur_b = a, b
            elif b > cur_b:
                cur_b = b
        covered += cur_b - cur_a
        span = max(now - window_lo, 1e-9)
        return min(1.0, max(0.0, covered / span))

    def gauges(self) -> Dict[str, float]:
        """The continuous occupancy gauges (rendered on /metrics by
        promexpo, freshly computed per scrape). The monotonic span
        count is NOT here — it is a counter and promexpo renders it as
        one (a `_total`-suffixed gauge confuses every Prometheus
        linter and rate() query)."""
        return {
            "tpu_device_busy_fraction": round(self.busy_fraction(), 6),
            "tpu_feed_stall_seconds": round(self.stall_s, 6),
        }

    @property
    def spans_recorded(self) -> int:
        return self._n

    def occupancy(self) -> Dict[str, float]:
        """The `trace latency` occupancy columns: busy fraction +
        overlap efficiency (from the tracer gauge the feed maintains) +
        cumulative stall."""
        from deepflow_tpu.runtime.tracing import default_tracer
        g = default_tracer().gauges()
        return {
            "device_busy_fraction": round(self.busy_fraction(), 4),
            "feed_overlap_efficiency": round(
                g.get("tpu_feed_overlap_efficiency", 0.0), 4),
            "feed_stall_seconds": round(self.stall_s, 4),
        }

    # -- trace export ------------------------------------------------------
    def to_chrome_trace(self, limit: Optional[int] = None) -> dict:
        """The ring as a Chrome-trace / Perfetto JSON object (the
        `traceEvents` array of complete "X" events, microsecond
        timestamps, one tid per track). Loads directly in
        ui.perfetto.dev and chrome://tracing; schema-validated in
        tests/test_audit.py. `limit` keeps the newest N events (the
        debug route's single-datagram budget)."""
        spans = self._snapshot()
        if limit is not None and len(spans) > limit:
            # NOT spans[-limit:]: a limit of 0 would slice [-0:] and
            # return the whole ring instead of nothing
            spans = spans[len(spans) - max(0, limit):]
        tids = {t: i + 1 for i, t in enumerate(_TRACKS)}
        events: List[dict] = []
        for track in sorted({s[0] for s in spans},
                            key=lambda t: tids.get(t, 99)):
            events.append({
                "name": "thread_name", "ph": "M", "pid": 1,
                "tid": tids.setdefault(track, len(tids) + 1),
                "args": {"name": track},
            })
        for track, name, t_end, dur, rows in spans:
            events.append({
                "name": name,
                "cat": track,
                "ph": "X",
                "ts": (t_end - dur) * 1e6,
                "dur": dur * 1e6,
                "pid": 1,
                "tid": tids.setdefault(track, len(tids) + 1),
                "args": {"rows": rows},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def counters(self) -> dict:
        return {"spans": self._n,
                "dropped": max(0, self._n - self._cap),
                "stall_s": round(self.stall_s, 6),
                "busy_fraction": round(self.busy_fraction(), 4)}

    def reset(self) -> None:
        with self._lock:
            self._ring = [None] * self._cap
            self._n = 0
            self.stall_s = 0.0


_default: Optional[OccupancyProfiler] = None
_default_lock = threading.Lock()


def default_profiler() -> OccupancyProfiler:
    """The process occupancy profiler (mirrors tracing.default_tracer)."""
    global _default
    with _default_lock:
        if _default is None:
            _default = OccupancyProfiler()
        return _default
