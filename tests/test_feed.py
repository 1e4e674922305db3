"""ISSUE 5: the overlapped device feed — coalesced single-transfer
batches, double-buffered prefetch, multi-batch fused steps.

The contract under test everywhere: sketch state through the
coalesced+prefetched path is BIT-IDENTICAL to the inline unoverlapped
path on both wires; every row is delivered or counted (the PR 4
conservation invariant extended to the prefetch window); and every new
thread rides the PR 2 supervision tree."""

import heapq
import tempfile

import numpy as np
import pytest

from deepflow_tpu.batch.batcher import Batcher
from deepflow_tpu.batch.schema import L4_SCHEMA, SKETCH_L4_SCHEMA
from deepflow_tpu.models import flow_suite
from deepflow_tpu.runtime.faults import default_faults
from deepflow_tpu.runtime.supervisor import default_supervisor
from deepflow_tpu.runtime.tpu_sketch import TpuSketchExporter, _HostSketch


@pytest.fixture(autouse=True)
def _clean_faults():
    """default_faults() is process-global: whatever a test arms must
    not leak into the next one (the PR 2 discipline)."""
    default_faults().disarm()
    yield
    default_faults().disarm()


def _pool(seed=17, n=512, hi=1 << 16):
    rng = np.random.default_rng(seed)
    return rng, {name: rng.integers(0, hi, n).astype(dt)
                 for name, dt in L4_SCHEMA.columns}


def _chunks(rng, pool, n_chunks=5, rows=2000):
    n = len(next(iter(pool.values())))
    return [{k: v[rng.integers(0, n, rows)] for k, v in pool.items()}
            for _ in range(n_chunks)]


def _exporter(wire, depth, k, **kw):
    return TpuSketchExporter(store=None, window_seconds=3600,
                             batch_rows=1024, wire=wire,
                             prefetch_depth=depth, coalesce_batches=k,
                             **kw)


def _state_leaves(exp):
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(exp.state)]


@pytest.mark.parametrize("wire", ["lanes", "dict"])
def test_coalesced_prefetch_state_bit_identical(wire):
    """The acceptance bar: inline vs prefetch=2 vs prefetch+coalesce=3
    land the exact same FlowSuite state (EVERY leaf, ring included —
    the batch partition and application order are preserved)."""
    rng, pool = _pool()
    chunks = _chunks(rng, pool)
    exps = [_exporter(wire, 0, 1), _exporter(wire, 2, 1),
            _exporter(wire, 2, 3)]
    try:
        for c in chunks:
            for e in exps:
                e.process([("l4_flow_log", 0, c)])
        for e in exps[1:]:
            assert e._feed.drain(30)
        ref = _state_leaves(exps[0])
        for e in exps[1:]:
            for a, b in zip(ref, _state_leaves(e)):
                np.testing.assert_array_equal(a, b)
    finally:
        for e in exps:
            e.close()
    # and the window output (post-close final flush) agrees too
    rows = [int(np.asarray(e.last_output.rows)) for e in exps]
    assert rows[0] == rows[1] == rows[2] > 0


def test_transfers_and_dispatches_coalesce():
    """transfers-per-batch <= 1 on the coalesced path (one device_put
    per group), while the inline lanes path pays 5 (mask + 4 planes);
    coalesce_batches additionally amortizes dispatches below one per
    batch. Holds on both feed variants: the TensorBatch reference and
    the zero-copy stager (ISSUE 9), which batches at the stager."""
    rng, pool = _pool(seed=5, hi=1 << 12)
    chunks = _chunks(rng, pool, n_chunks=6, rows=3000)
    inline = _exporter("lanes", 0, 1)
    feed = _exporter("lanes", 2, 3, zero_copy=False)
    zc = _exporter("lanes", 2, 3)                 # zero-copy default
    try:
        for c in chunks:
            inline.process([("l4_flow_log", 0, c)])
            feed.process([("l4_flow_log", 0, c)])
            zc.process([("l4_flow_log", 0, c)])
        assert feed._feed.drain(30)
        assert zc._feed.drain(30)
        batches = inline.batcher.emitted_batches
        assert batches == feed.batcher.emitted_batches > 0
        assert zc.counters()["batches"] == batches    # stager batches
        assert inline.h2d_transfers == 5 * batches
        for e in (feed, zc):
            assert e.h2d_transfers <= batches         # <= 1 per batch
            assert e.dispatches < batches             # K-fused steps
            assert e.dispatches == e._feed.groups
    finally:
        inline.close()
        feed.close()
        zc.close()


def test_drain_ladder_flushes_prefetch_window():
    """Conservation with batches in flight: close() drains the window,
    and delivered + counted_loss == sent."""
    rng, pool = _pool(seed=3, n=256, hi=1 << 12)
    e = _exporter("dict", 3, 2)
    sent = 0
    for c in _chunks(rng, pool, n_chunks=7, rows=1300):
        e.process([("l4_flow_log", 0, c)])
        sent += 1300
    # the feed window is visible to the drain ladder while in flight
    assert e.pending_extra() >= 0
    e.close()
    assert e.rows_in == sent
    delivered = int(np.asarray(e.last_output.rows))
    assert delivered + e.lost_rows == sent
    assert e._feed.pending() == 0


def test_device_error_in_flight_restores_and_degrades():
    """A device-classified error on a dispatched superbatch rolls back
    to the checkpoint ladder exactly like the inline path; repeated
    errors hand the lane to the host fallback, and the per-window
    probe recovers it once the device heals — with a superbatch in
    flight throughout."""
    rng, pool = _pool(seed=7, n=256, hi=1 << 12)
    f = default_faults()
    sites = f.arm_spec("tpu.device_error:count=3,match=lanes;seed=5")
    ck = tempfile.mkdtemp(prefix="feed_ck_")
    try:
        e = _exporter("lanes", 2, 2, checkpoint_dir=ck)
        sent = 0
        for c in _chunks(rng, pool, n_chunks=8, rows=1024):
            e.process([("l4_flow_log", 0, c)])
            sent += 1024
        assert e._feed.drain(30)
        assert e.device_errors >= e.degrade_after and e.degraded
        assert e.host_rows > 0 and e.lost_rows > 0
    finally:
        for s in sites:
            f.disarm(s)
    e.flush_window()                 # probe runs with faults disarmed
    assert e.recoveries == 1 and not e.degraded
    # back on device: the restored lane keeps absorbing
    e.process([("l4_flow_log", 0, _chunks(rng, pool, 1, 1024)[0])])
    assert e._feed.drain(30)
    e.close()


def test_feed_thread_crash_supervisor_restart():
    """A crashing feed thread is a supervisor restart, not a dark
    lane: the mid-flight group is counted lost, device state restored,
    and the restarted thread keeps feeding without corruption."""
    rng, pool = _pool(seed=11, n=256, hi=1 << 12)
    e = _exporter("lanes", 2, 1)
    orig = e._feed._process_group
    boom = [True]

    def flaky(group):
        if boom[0]:
            boom[0] = False
            raise ValueError("injected feed crash")
        return orig(group)

    e._feed._process_group = flaky
    for c in _chunks(rng, pool, n_chunks=4, rows=1024):
        e.process([("l4_flow_log", 0, c)])
    assert e._feed.drain(30)
    rows = [t for t in default_supervisor().threads()
            if t["name"] == "tpu-sketch-feed"]
    assert rows and any(t["crashes"] >= 1 for t in rows)
    assert e._feed.crash_recoveries == 1
    assert e.lost_rows > 0
    e.process([("l4_flow_log", 0, _chunks(rng, pool, 1, 1024)[0])])
    assert e._feed.drain(30)
    e.close()
    assert int(np.asarray(e.last_output.rows)) > 0


def test_exporters_pending_counts_feed_window():
    """Exporters.pending() must see batches parked in the prefetch
    window (pending_extra), or the PR 4 drain ladder could declare
    victory with rows in flight."""
    from deepflow_tpu.runtime.exporters import Exporters

    class FakeFeedExporter:
        name = "fake"
        queue = None

        def pending_extra(self):
            return 3

        def is_export_data(self, stream, cols):
            return False

        def start(self):
            pass

        def close(self):
            pass

        def put(self, *a):
            pass

    ex = Exporters(breaker_cfg=None)
    ex.register(FakeFeedExporter())
    assert ex.pending() == 3


# -- satellite: Batcher recycle pool ---------------------------------------

def test_batcher_recycle_pool_reuses_buffers():
    b = Batcher(SKETCH_L4_SCHEMA, capacity=64)
    out = list(b.put({n: np.arange(64, dtype=d)
                      for n, d in SKETCH_L4_SCHEMA.columns}))
    assert len(out) == 1 and b.pool_hits == 0
    bufs = {id(v) for v in out[0].columns.values()}
    b.recycle(out[0])
    assert b.recycled == 1
    list(b.put({n: np.arange(64, dtype=d)
                for n, d in SKETCH_L4_SCHEMA.columns}))
    # the second emit took its replacement from the pool: the batcher
    # now fills the very arrays the first batch returned
    assert b.pool_hits == 1
    assert {id(v) for v in b._buf.values()} == bufs


def test_batcher_recycled_buffer_never_leaks_stale_rows():
    b = Batcher(SKETCH_L4_SCHEMA, capacity=32)
    full = {n: np.full(32, 7, dtype=d) for n, d in SKETCH_L4_SCHEMA.columns}
    (tb,) = b.put(full)
    b.recycle(tb)                       # buffer full of 7s goes back
    partial = {n: np.full(5, 9, dtype=d)
               for n, d in SKETCH_L4_SCHEMA.columns}
    assert list(b.put(partial)) == []
    (tb2,) = b.flush()
    assert tb2.valid == 5
    assert np.all(tb2.columns["ip_src"][:5] == 9)
    assert np.all(tb2.columns["ip_src"][5:] == 0)   # padding zeroed


def test_batcher_recycle_rejects_wrong_shape():
    b = Batcher(SKETCH_L4_SCHEMA, capacity=64)
    other = Batcher(SKETCH_L4_SCHEMA, capacity=32)
    (tb,) = other.put({n: np.zeros(32, dtype=d)
                       for n, d in SKETCH_L4_SCHEMA.columns})
    b.recycle(tb)                       # capacity mismatch: dropped
    assert b.recycled == 0 and not b._pool


# -- satellite: host-fallback perf fixes stay exact ------------------------

def test_host_sketch_bincount_matches_scatter_reference():
    """The np.bincount entropy accumulate and heapq top-K must produce
    exactly what the old np.add.at / full-sort path produced."""
    cfg = flow_suite.FlowSuiteConfig()
    rng = np.random.default_rng(23)
    cols = {name: rng.integers(0, 1 << 16, 4096).astype(dt)
            for name, dt in SKETCH_L4_SCHEMA.columns}
    hs = _HostSketch(cfg, stride=4)
    hs.update(cols)

    # reference: the pre-ISSUE-5 scatter accumulate
    ref = np.zeros_like(hs._ent)
    sl = slice(None, None, 4)
    sub = {k: np.asarray(v)[sl] for k, v in cols.items()}
    pkts = np.minimum(sub["packet_tx"].astype(np.int64)
                      + sub["packet_rx"].astype(np.int64), 0xFFFF)
    for i, f in enumerate(flow_suite.ENTROPY_FEATURES):
        np.add.at(ref[i], np.asarray(sub[f]).astype(np.uint32)
                  % np.uint32(hs._buckets), pkts)
    np.testing.assert_array_equal(hs._ent, ref)

    # reference: the old full-sort top-K (stable on ties)
    want = sorted(hs._counts.items(), key=lambda kv: -kv[1])[:cfg.top_k]
    got = heapq.nlargest(cfg.top_k, hs._counts.items(),
                         key=lambda kv: kv[1])
    assert want == got
    out = hs.flush(cfg)
    assert int(np.asarray(out.rows)) == 4096


# -- the mesh lane gets the same coalesced form ----------------------------

def test_sharded_coalesced_lanes_matches_column_update(rng):
    """ShardedFlowSuite.update_lanes (one (4,B) plane transfer + mask
    rebuilt on device from the global n) == the per-column sharded
    update on the same batch."""
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.parallel import ShardedFlowSuite, make_mesh

    cfg = flow_suite.FlowSuiteConfig(cms_log2_width=12, ring_size=256,
                                     hll_groups=64, hll_precision=8)
    mesh = make_mesh()
    suite = ShardedFlowSuite(cfg, mesh)
    s_cols = suite.init()
    s_lane = suite.init()
    rng_np = np.random.default_rng(41)
    B = 4096
    for _ in range(3):
        # IN-RANGE values (proto < 2^8, ports < 2^16): the lane wire
        # masks out-of-range values to range where the column path
        # hashes them raw (pack_lanes' documented difference), so the
        # equivalence claim only holds for values a real packet header
        # can produce
        cols = {k: rng_np.integers(0, 1 << 16, B).astype(np.uint32)
                for k in ("ip_src", "ip_dst", "port_src", "port_dst",
                          "proto", "packet_tx", "packet_rx")}
        cols["proto"] = rng_np.integers(0, 256, B).astype(np.uint32)
        n = B - 128                       # padded tail rows masked out
        mask = np.arange(B) < n
        dc, md = suite.put_batch(
            {k: jnp.asarray(v) for k, v in cols.items()},
            jnp.asarray(mask))
        s_cols = suite.update(s_cols, dc, md)
        plane = np.zeros((4, B), np.uint32)
        flow_suite.pack_lanes_into(cols, plane)
        s_lane = suite.update_lanes(s_lane, suite.put_lanes(plane), n)
    for a, b in zip(jax.tree.leaves(s_cols), jax.tree.leaves(s_lane)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- coalesced program builders standalone ---------------------------------

def test_make_coalesced_update_matches_sequential(rng):
    """flow_suite.make_coalesced_update(K): one staged transfer + scan
    == K separate update_packed calls, bit-exact."""
    import jax
    import jax.numpy as jnp

    cfg = flow_suite.FlowSuiteConfig(cms_log2_width=12, ring_size=256,
                                     hll_groups=64, hll_precision=8)
    K, C = 3, 1024
    rng_np = np.random.default_rng(29)
    cols = [{k: rng_np.integers(0, 1 << 16, C).astype(np.uint32)
             for k in ("ip_src", "ip_dst", "port_src", "port_dst",
                       "proto", "packet_tx", "packet_rx")}
            for _ in range(K)]
    ns = [C, C - 100, C - 999]

    # slot-contiguous layout (ISSUE 9): [n_k | plane_k] per slot
    flat = np.zeros(flow_suite.coalesced_lanes_words(K, C), np.uint32)
    for k in range(K):
        flat[k * flow_suite.slot_words(C)] = ns[k]
        flow_suite.pack_lanes_into(cols[k],
                                   flow_suite.slot_plane(flat, k, C))

    fused = flow_suite.make_coalesced_update(cfg, K, C)
    got, fence = fused(flow_suite.init(cfg), jnp.asarray(flat))
    assert int(fence) == sum(ns)

    ref = flow_suite.init(cfg)
    for k in range(K):
        lanes = {kk: jnp.asarray(v)
                 for kk, v in flow_suite.pack_lanes(cols[k]).items()}
        mask = jnp.asarray(np.arange(C) < ns[k])
        ref = flow_suite.update_packed(ref, lanes, mask, cfg)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------- starvation and fence retirement

class _Fence:
    """A device fence stand-in: ready once `ready` is set."""

    def __init__(self, ready: bool = True) -> None:
        import threading
        self.ready = threading.Event()
        if ready:
            self.ready.set()

    def is_ready(self) -> bool:
        return self.ready.is_set()

    def block_until_ready(self):
        self.ready.wait(10)
        return self


class _Batch:
    valid = 100


def _bare_feed(fences):
    """A DeviceFeed whose every group 'dispatches' the next fence."""
    from deepflow_tpu.runtime.feed import DeviceFeed, InFlight

    it = iter(fences)
    return DeviceFeed("test-feed",
                      lambda group: InFlight(next(it), 100), depth=2)


def _wait_for(pred, timeout=10.0):
    import time
    end = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < end, "condition never held"
        time.sleep(0.002)


def _recorder():
    from deepflow_tpu.runtime.profiler import default_profiler
    from deepflow_tpu.runtime.tracing import default_tracer

    tr = default_tracer()
    tr.reset()
    tr.enable()
    prof = default_profiler()
    prof.reset()
    return tr, prof


def test_feed_wait_with_every_fence_ready_is_starvation():
    """The regression: a fence that is ready but still inside the
    depth-2 window used to keep the window 'busy' through the whole
    wait, so the device read busy and the stall gauge read ~0. The feed
    now retires ready fences before it waits, and the wait lands in
    `feed.starved` and `tpu_feed_stall_seconds`."""
    import time

    tr, prof = _recorder()
    feed = _bare_feed([_Fence(), _Fence()])
    try:
        feed.put(_Batch())
        _wait_for(lambda: feed.fences == 1)
        assert not feed._inflight
        time.sleep(0.3)
        feed.put(_Batch())
        assert feed.drain(10)
        starved = tr.stages()["feed.starved"]
        assert starved.sum >= 0.25
        assert prof.gauges()["tpu_feed_stall_seconds"] >= 0.25
        # the device interval ended at retirement, not at the next batch
        dev = [s for s in prof._snapshot() if s[0] == "device"]
        assert len(dev) == 2 and all(s[3] < 0.2 for s in dev)
    finally:
        feed.close()


def test_feed_leaves_an_unready_fence_in_flight():
    """A fence that is not ready stays in flight (no blocking, no
    starvation: the device still has work); the feed retires it on its
    own once it is ready."""
    import time

    tr, prof = _recorder()
    slow = _Fence(ready=False)
    feed = _bare_feed([slow, _Fence()])
    try:
        feed.put(_Batch())
        _wait_for(lambda: feed.groups == 1)
        time.sleep(0.3)
        assert len(feed._inflight) == 1 and feed.fences == 0
        feed.put(_Batch())
        _wait_for(lambda: feed.groups == 2)
        assert feed.fences == 0
        starved = tr.stages().get("feed.starved")
        assert starved is None or starved.sum < 0.1
        assert prof.stall_s < 0.1
        slow.ready.set()
        _wait_for(lambda: feed.fences == 2 and not feed._inflight)
    finally:
        feed.close()
