"""ISSUE 9: saturate the chip — zero-copy decode->staging, flow-hash
sharded pack workers, and the fused Pallas unpack+sketch kernel.

The contract under test everywhere: the zero-copy stager, the sharded
pack pool and the fused kernel each produce sketch state BIT-IDENTICAL
to the seed TensorBatch path; every row is delivered or counted
(the PR 4 conservation invariant); and every new thread rides the PR 2
supervision tree. ISSUE 20 extends the same contract to the dict
wire: staged news/hits word groups must be bit-identical to the
inline dict path, LRU state included."""

import os
import tempfile

import numpy as np
import pytest

from deepflow_tpu.batch.batcher import Batcher
from deepflow_tpu.batch.schema import L4_SCHEMA, SKETCH_L4_SCHEMA
from deepflow_tpu.batch.staging import (LaneStager, PackPool, StagedGroup,
                                        StagingPackError, _GroupState)
from deepflow_tpu.models import flow_suite
from deepflow_tpu.runtime.faults import default_faults
from deepflow_tpu.runtime.supervisor import default_supervisor
from deepflow_tpu.runtime.tpu_sketch import TpuSketchExporter


@pytest.fixture(autouse=True)
def _clean_faults():
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


def _sketch_chunks(rng, n_chunks=5, rows=2000, hi=1 << 16):
    return [{name: rng.integers(0, hi, rows).astype(dt)
             for name, dt in SKETCH_L4_SCHEMA.columns}
            for _ in range(n_chunks)]


def _exporter(**kw):
    kw.setdefault("wire", "lanes")
    kw.setdefault("prefetch_depth", 2)
    kw.setdefault("coalesce_batches", 3)
    return TpuSketchExporter(store=None, window_seconds=3600,
                             batch_rows=1024, **kw)


def _state_leaves(exp):
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(exp.state)]


# -- the stager mirrors Batcher's partition, byte for byte ------------------

def _staged_bytes(groups, C):
    """Flatten emitted groups to a list of (n, plane-bytes) per slot."""
    out = []
    for g in groups:
        s = flow_suite.slot_words(C)
        for k in range(g.k):
            out.append((int(g.flat[k * s]),
                        g.flat[k * s + 1:(k + 1) * s].tobytes()))
    return out


def _tb_reference_bytes(chunks, C):
    """The seed path's staged bytes: Batcher partition + pack_lanes_into
    of each emitted TensorBatch (padding zeroed, exactly one slot)."""
    b = Batcher(SKETCH_L4_SCHEMA, capacity=C)
    out = []
    plane = np.zeros((4, C), np.uint32)
    for c in chunks:
        for tb in list(b.put(c)):
            plane[:] = 0
            flow_suite.pack_lanes_into(tb.columns, plane)
            out.append((tb.valid, plane.tobytes()))
    for tb in b.flush():
        plane[:] = 0
        flow_suite.pack_lanes_into(tb.columns, plane)
        plane[:, tb.valid:] = 0
        out.append((tb.valid, plane.tobytes()))
    return out


@pytest.mark.parametrize("group_batches", [1, 3])
def test_stager_partition_matches_batcher(group_batches):
    """LaneStager slot partition + staged bytes == Batcher partition +
    pack_lanes_into, including the padded flush remainder — the batch
    boundaries (and therefore ring phase) cannot drift."""
    rng = np.random.default_rng(7)
    chunks = _sketch_chunks(rng, n_chunks=4, rows=1700)
    C = 1024
    st = LaneStager(C, group_batches=group_batches)
    groups = []
    for c in chunks:
        groups += st.put(c)
    groups += st.flush()
    got = _staged_bytes(groups, C)
    want = _tb_reference_bytes(chunks, C)
    assert [n for n, _ in got] == [n for n, _ in want]
    for (na, ba), (nb, bb) in zip(got, want):
        assert ba == bb
    assert st.total_rows == 4 * 1700
    assert st.staged_batches == len(want)


def test_pack_pool_sharded_bytes_identical():
    """The flow-hash sharded pack lands byte-identical buffers: pack
    destinations are pre-assigned, so worker timing can't reorder."""
    rng = np.random.default_rng(11)
    chunks = _sketch_chunks(rng, n_chunks=6, rows=900)
    C = 512
    pool = PackPool(3, name="test-stage-pack")
    try:
        st_pool = LaneStager(C, group_batches=2, pool=pool)
        st_ref = LaneStager(C, group_batches=2)
        got, want = [], []
        for c in chunks:
            got += st_pool.put(c)
            want += st_ref.put(c)
        got += st_pool.flush()
        want += st_ref.flush()
        for g in got:
            g.wait_ready(timeout=30.0)
        assert _staged_bytes(got, C) == _staged_bytes(want, C)
        assert pool.tasks > 0 and pool.task_errors == 0
    finally:
        pool.close()


def test_pack_error_poisons_group_not_worker():
    """A raising pack task poisons ITS group (StagingPackError out of
    wait_ready); the pool worker survives and keeps serving."""
    pool = PackPool(2, name="test-poison-pack")
    try:
        bad = _GroupState()
        pool.submit(0, lambda: 1 / 0, bad)
        g = StagedGroup(np.zeros(1, np.uint32), np.zeros(1, np.uint32),
                        1, 0, 0, bad)
        with pytest.raises(StagingPackError):
            g.wait_ready(timeout=10.0)
        # the worker is alive: a later task on the same shard completes
        ok = _GroupState()
        done = []
        pool.submit(0, lambda: done.append(1), ok)
        g2 = StagedGroup(np.zeros(1, np.uint32), np.zeros(1, np.uint32),
                         1, 0, 0, ok)
        g2.wait_ready(timeout=10.0)
        assert done == [1]
        assert pool.task_errors == 1
    finally:
        pool.close()


def test_stager_recycle_reuses_buffers():
    rng = np.random.default_rng(13)
    C = 256
    st = LaneStager(C, group_batches=1, pool_cap=2)
    (g1,) = st.put(_sketch_chunks(rng, 1, C)[0])
    buf_id = id(g1.buffer)
    st.recycle(g1)
    assert st.recycled == 1
    (g2,) = st.put(_sketch_chunks(rng, 1, C)[0])
    assert id(g2.buffer) == buf_id and st.pool_hits == 1
    # wrong-geometry buffer (from another stager) is dropped, not pooled
    other = LaneStager(C // 2, group_batches=1)
    (go,) = other.put(_sketch_chunks(rng, 1, C // 2)[0])
    st.recycle(go)
    assert st.recycled == 1


def test_prefix_flush_is_valid_smaller_group():
    """Slot-contiguity: a flush with k complete slots + a partial ships
    a PREFIX of the same backing buffer — no repack, padding zeroed."""
    rng = np.random.default_rng(19)
    C = 512
    st = LaneStager(C, group_batches=4)
    groups = st.put(_sketch_chunks(rng, 1, int(2.5 * C))[0])
    assert groups == []          # 2 complete slots + half of slot 3: open
    (g,) = st.flush()
    assert g.k == 3 and g.valid == int(2.5 * C)
    assert g.flat.size == flow_suite.coalesced_lanes_words(3, C)
    assert g.flat.base is g.buffer or g.flat is g.buffer
    s = flow_suite.slot_words(C)
    assert int(g.flat[2 * s]) == C // 2
    tail = flow_suite.slot_plane(g.flat, 2, C)[:, C // 2:]
    assert not tail.any()


# -- unpack twin ------------------------------------------------------------

def test_unpack_lanes_np_matches_device_unpack():
    """The host twin consumes the same staged plane the device would:
    identical column split (tx carries the capped sum, rx zero)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(23)
    cols = {k: rng.integers(0, 1 << 16, 128).astype(np.uint32)
            for k in ("ip_src", "ip_dst", "port_src", "port_dst",
                      "proto", "packet_tx", "packet_rx")}
    plane = np.zeros((4, 128), np.uint32)
    flow_suite.pack_lanes_into(cols, plane)
    n = 100
    host = flow_suite.unpack_lanes_np(plane, n)
    dev = flow_suite.unpack_lanes(
        {"ip_src": jnp.asarray(plane[0]), "ip_dst": jnp.asarray(plane[1]),
         "ports": jnp.asarray(plane[2]),
         "proto_pkts": jnp.asarray(plane[3])})
    for k, v in host.items():
        np.testing.assert_array_equal(v, np.asarray(dev[k])[:n], err_msg=k)


# -- exporter end-to-end: bit-identity, conservation, degraded --------------

def test_zero_copy_state_bit_identical():
    """The acceptance bar: inline vs TensorBatch-feed vs zero-copy vs
    zero-copy+sharded-pack land the exact same FlowSuite state (every
    leaf, ring included) and the same window rows. The stream here
    fills whole stager groups (10000 rows = 9 batches + remainder,
    coalesce 3), so even the mid-stream drained states align; the
    unaligned case is the window-output test below."""
    rng, pool = _pool()
    chunks = _chunks(rng, pool)
    exps = [_exporter(prefetch_depth=0, coalesce_batches=1),
            _exporter(zero_copy=False),
            _exporter(),
            _exporter(pack_workers=3)]
    assert exps[2].zero_copy and exps[3].zero_copy
    assert not exps[0].zero_copy and not exps[1].zero_copy
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
    rows = [int(np.asarray(e.last_output.rows)) for e in exps]
    assert len(set(rows)) == 1 and rows[0] == 5 * 2000


def test_zero_copy_window_output_identical_unaligned():
    """The consistency contract at the WINDOW boundary: mid-stream the
    stager may park complete slots in its open group buffer (a feed
    drain alone is not a complete-batch barrier there), but every
    window flush ships the open prefix — so the batch partition, and
    therefore every window-output leaf, is bit-identical to the
    TensorBatch path even when the stream doesn't align with group
    boundaries. Two consecutive windows, so carry-over (ring phase,
    remainder rows) is covered too."""
    import jax

    rng, pool = _pool(seed=9, hi=1 << 12)
    exps = [_exporter(zero_copy=False, coalesce_batches=2),
            _exporter(coalesce_batches=2),
            _exporter(coalesce_batches=2, pack_workers=2)]
    try:
        for _ in range(2):
            # 6 x 3000 rows: 17 full batches + 592 remainder — never a
            # whole number of 2-slot groups
            for c in _chunks(rng, pool, n_chunks=6, rows=3000):
                for e in exps:
                    e.process([("l4_flow_log", 0, c)])
            outs = [e.flush_window() for e in exps]
            for o in outs[1:]:
                for a, b in zip(jax.tree.leaves(outs[0]),
                                jax.tree.leaves(o)):
                    np.testing.assert_array_equal(
                        np.asarray(a), np.asarray(b))
    finally:
        for e in exps:
            e.close()


def test_zero_copy_gating():
    """zero_copy arms on the lanes AND dict wires WITH a feed; the
    inline (no-feed) and explicitly-off paths keep their seed shape.
    On the dict wire the stager owns the packer, so the inline packer
    slot stays empty — there is exactly one LRU authority."""
    from deepflow_tpu.batch.staging import DictWireStager

    e_dict = _exporter(wire="dict")
    e_dict_inline = _exporter(wire="dict", prefetch_depth=0,
                              coalesce_batches=1)
    e_inline = _exporter(prefetch_depth=0, coalesce_batches=1)
    e_off = _exporter(zero_copy=False)
    try:
        assert e_dict.zero_copy
        assert isinstance(e_dict._stager, DictWireStager)
        assert e_dict._dict_packer is None
        assert e_dict_inline._stager is None and not e_dict_inline.zero_copy
        assert e_dict_inline._dict_packer is not None
        assert e_inline._stager is None and not e_inline.zero_copy
        assert e_off._stager is None and not e_off.zero_copy
    finally:
        for e in (e_dict, e_dict_inline, e_inline, e_off):
            e.close()


def test_zero_copy_drain_conservation():
    """delivered + counted_loss == sent with staged groups in flight
    through the close() drain ladder."""
    rng, pool = _pool(seed=3, n=256, hi=1 << 12)
    e = _exporter(pack_workers=2)
    sent = 0
    for c in _chunks(rng, pool, n_chunks=7, rows=1300):
        e.process([("l4_flow_log", 0, c)])
        sent += 1300
    assert e.pending_extra() >= 0
    e.close()
    assert e.rows_in == sent
    delivered = int(np.asarray(e.last_output.rows))
    assert delivered + e.lost_rows == sent
    assert e._feed.pending() == 0
    c = e.counters()
    assert c["zero_copy"] == 1 and c["staged_rows"] == sent
    assert c["pack_task_errors"] == 0


def test_zero_copy_degraded_absorbs_staged_lanes():
    """Device loss with staged groups in flight: rollback + host
    fallback consume the staged lanes via the unpack twin (no
    TensorBatch exists any more), probe recovery works, and every row
    is delivered or counted."""
    rng, pool = _pool(seed=7, n=256, hi=1 << 12)
    f = default_faults()
    sites = f.arm_spec("tpu.device_error:count=3,match=lanes;seed=5")
    ck = tempfile.mkdtemp(prefix="stage_ck_")
    try:
        e = _exporter(coalesce_batches=2, checkpoint_dir=ck)
        assert e.zero_copy
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
    e.process([("l4_flow_log", 0, _chunks(rng, pool, 1, 1024)[0])])
    assert e._feed.drain(30)
    e.close()


def test_pack_pool_threads_supervised():
    """Every pack worker rides the PR 2 supervision tree with deadman
    beats — no raw threads in the decode plane."""
    e = _exporter(pack_workers=2)
    try:
        names = {t["name"] for t in default_supervisor().threads()}
        assert {"stage-pack-0", "stage-pack-1"} <= names
    finally:
        e.close()


# -- dict-wire zero-copy parity (ISSUE 20) ----------------------------------

def test_dict_staged_window_output_identical_unaligned():
    """Dict-wire staged groups == the inline dict path, bit for bit:
    the stager cuts batch_rows exactly where the inline partition
    would, runs the SAME one-pack-per-cut LRU protocol, and the window
    flush ships the open k<K prefix — so every window-output leaf AND
    every dict-table word agree even when the stream never aligns with
    group boundaries. Two consecutive windows cover LRU carry-over."""
    import jax

    rng, pool = _pool(seed=9, hi=1 << 12)
    exps = [_exporter(wire="dict", zero_copy=False, coalesce_batches=2),
            _exporter(wire="dict", coalesce_batches=2),
            _exporter(wire="dict", coalesce_batches=2, pack_workers=2)]
    assert not exps[0].zero_copy
    assert exps[1].zero_copy and exps[2].zero_copy
    try:
        for _ in range(2):
            # 6 x 3000 rows: 17 full batches + 592 remainder — never a
            # whole number of 2-slot groups
            for c in _chunks(rng, pool, n_chunks=6, rows=3000):
                for e in exps:
                    e.process([("l4_flow_log", 0, c)])
            outs = [e.flush_window() for e in exps]
            for o in outs[1:]:
                for a, b in zip(jax.tree.leaves(outs[0]),
                                jax.tree.leaves(o)):
                    np.testing.assert_array_equal(
                        np.asarray(a), np.asarray(b))
            ref = [np.asarray(x)
                   for x in jax.tree.leaves(exps[0]._dict_state)]
            for e in exps[1:]:
                for a, b in zip(ref, jax.tree.leaves(e._dict_state)):
                    np.testing.assert_array_equal(a, np.asarray(b))
    finally:
        for e in exps:
            e.close()


def test_dict_staged_drain_conservation():
    """delivered + counted_loss == sent with staged dict groups in
    flight through the close() drain ladder — the PR 4 invariant holds
    on the wire-word path too."""
    rng, pool = _pool(seed=3, n=256, hi=1 << 12)
    e = _exporter(wire="dict", pack_workers=2)
    sent = 0
    for c in _chunks(rng, pool, n_chunks=7, rows=1300):
        e.process([("l4_flow_log", 0, c)])
        sent += 1300
    assert e.pending_extra() >= 0
    e.close()
    assert e.rows_in == sent
    delivered = int(np.asarray(e.last_output.rows))
    assert delivered + e.lost_rows == sent
    assert e._feed.pending() == 0
    c = e.counters()
    assert c["zero_copy"] == 1 and c["staged_rows"] == sent
    assert c["pack_task_errors"] == 0
    assert c["dict_epoch_drops"] == 0      # no rollback, no stale drops


def test_zero_copy_degraded_absorbs_staged_dict():
    """Device loss with staged dict groups in flight: rollback swaps
    the packer (epoch bump), groups staged against the DEAD epoch are
    counted loss — their wire indexes a table that no longer exists —
    while live-epoch groups are absorbed on the host via the mirror
    gather twin. Probe recovery works and rows_in stays accounted."""
    rng, pool = _pool(seed=7, n=256, hi=1 << 12)
    f = default_faults()
    sites = f.arm_spec("tpu.device_error:count=3,match=dict;seed=5")
    ck = tempfile.mkdtemp(prefix="stage_dict_ck_")
    try:
        e = _exporter(wire="dict", coalesce_batches=2, checkpoint_dir=ck)
        assert e.zero_copy
        sent = 0
        for c in _chunks(rng, pool, n_chunks=8, rows=1024):
            e.process([("l4_flow_log", 0, c)])
            sent += 1024
        assert e._feed.drain(30)
        assert e.device_errors >= e.degrade_after and e.degraded
        assert e.lost_rows > 0
        assert e.counters()["dict_epoch_drops"] >= 1
        # host absorb needs live-epoch traffic: only groups staged
        # AFTER the last rollback gather against the rebuilt mirror
        for c in _chunks(rng, pool, n_chunks=4, rows=1024):
            e.process([("l4_flow_log", 0, c)])
            sent += 1024
        assert e._feed.drain(30)
        assert e.host_rows > 0
        assert e.rows_in == sent
    finally:
        for s in sites:
            f.disarm(s)
    e.flush_window()                 # probe runs with faults disarmed
    assert e.recoveries == 1 and not e.degraded
    e.process([("l4_flow_log", 0, _chunks(rng, pool, 1, 1024)[0])])
    assert e._feed.drain(30)
    e.close()


# -- fused Pallas unpack+sketch kernel --------------------------------------

@pytest.fixture
def interpret(monkeypatch):
    """Run the Pallas kernels through the interpreter: the one switch
    the program reads (it never picks interpret mode itself)."""
    from deepflow_tpu.ops import pallas_hist
    monkeypatch.setattr(pallas_hist, "INTERPRET", True)


def _fused_cfg(**kw):
    kw.setdefault("cms_log2_width", 12)
    kw.setdefault("ring_size", 256)
    kw.setdefault("hll_groups", 64)
    kw.setdefault("hll_precision", 8)
    kw.setdefault("entropy_log2_buckets", 10)
    return flow_suite.FlowSuiteConfig(**kw)


def _lane_batch(rng, C):
    cols = {k: rng.integers(0, 1 << 16, C).astype(np.uint32)
            for k in ("ip_src", "ip_dst", "port_src", "port_dst",
                      "proto", "packet_tx", "packet_rx")}
    plane = np.zeros((4, C), np.uint32)
    flow_suite.pack_lanes_into(cols, plane)
    return plane


def test_fused_hists_state_bit_identical(interpret):
    """update_lanes_fused (interpret mode off-TPU) == the unfused
    update on the same staged plane: every leaf, every batch. This
    stream keeps every histogram cell's per-batch sum below 2^24 —
    the regime where f32 accumulation order can't split the two (the
    exactness bound is documented in ops/pallas_sketch.py; past it
    entropy cells may round apart)."""
    import jax
    import jax.numpy as jnp

    C = 1024
    rng = np.random.default_rng(3)
    cfg = _fused_cfg(fused_hists=True)
    cfg_ref = _fused_cfg()
    fused = flow_suite.init(cfg)
    ref = flow_suite.init(cfg_ref)
    for n in (C, C - 37, 1):
        plane = _lane_batch(rng, C)
        nn = jnp.uint32(n)
        fused = flow_suite.update_lanes_fused(
            fused, jnp.asarray(plane), nn, cfg)
        lanes = {"ip_src": plane[0], "ip_dst": plane[1],
                 "ports": plane[2], "proto_pkts": plane[3]}
        ref = flow_suite.update(
            ref, flow_suite.unpack_lanes(
                {k: jnp.asarray(v) for k, v in lanes.items()}),
            jnp.arange(C) < nn, cfg_ref)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(fused)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_coalesced_program_bit_identical(interpret):
    """The full staged program (make_coalesced_update) with the fused
    kernel forced == the unfused program on the same coalesced buffer."""
    import jax
    import jax.numpy as jnp

    C, K = 512, 3
    rng = np.random.default_rng(31)
    flat = np.zeros(flow_suite.coalesced_lanes_words(K, C), np.uint32)
    ns = [C, C - 100, 25]
    for k in range(K):
        flat[k * flow_suite.slot_words(C)] = ns[k]
        flow_suite.slot_plane(flat, k, C)[:] = _lane_batch(rng, C)
    cfg_f = _fused_cfg(fused_hists=True)
    cfg_u = _fused_cfg(fused_hists=False)
    got_f, fence_f = flow_suite.make_coalesced_update(cfg_f, K, C)(
        flow_suite.init(cfg_f), jnp.asarray(flat))
    got_u, fence_u = flow_suite.make_coalesced_update(cfg_u, K, C)(
        flow_suite.init(cfg_u), jnp.asarray(flat))
    assert int(fence_f) == int(fence_u) == sum(ns)
    for a, b in zip(jax.tree.leaves(got_u), jax.tree.leaves(got_f)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_dispatch_posture(monkeypatch):
    """Auto dispatch is conservative: off-TPU (or under conservative
    CMS) the fused kernel never engages on its own, env opt-in or not;
    True forces it. A forced kernel off a TPU is refused at lowering —
    the program never falls back to the interpreter by itself."""
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.ops import pallas_hist

    assert jax.default_backend() != "tpu"
    assert pallas_hist.INTERPRET is False
    assert flow_suite.use_fused_hists(_fused_cfg()) is False
    monkeypatch.setenv("DEEPFLOW_SKETCH_PALLAS", "1")
    # env opt-in alone is not enough off-TPU
    assert flow_suite.use_fused_hists(_fused_cfg()) is False
    cfg = _fused_cfg(fused_hists=True)
    assert flow_suite.use_fused_hists(cfg) is True
    assert flow_suite.use_fused_hists(
        _fused_cfg(fused_hists=True, conservative=True)) is False
    assert flow_suite.use_fused_hists(_fused_cfg(fused_hists=False)) is False
    plane = jnp.asarray(_lane_batch(np.random.default_rng(0), 1024))
    with pytest.raises(ValueError, match="interpret"):
        flow_suite.update_lanes_fused(flow_suite.init(cfg), plane,
                                      jnp.uint32(5), cfg)


def test_fused_lane_hists_deltas_match_sketch_deltas():
    """The kernel's raw (cms_hist, ent_hist) deltas equal the state
    deltas the unfused ops produce — the in-kernel hash twins
    (fmix32, 5-tuple fold, multiply-shift bucket) are op-for-op."""
    import jax.numpy as jnp

    from deepflow_tpu.ops import pallas_sketch

    C = 512
    cfg = _fused_cfg()
    rng = np.random.default_rng(41)
    plane = _lane_batch(rng, C)
    n = C - 7
    state = flow_suite.init(cfg)
    cms_h, ent_h = pallas_sketch.fused_lane_hists(
        jnp.asarray(plane), jnp.uint32(n), state.sketch.seeds,
        state.ent.seeds, cms_log2_width=cfg.cms_log2_width,
        ent_log2_buckets=cfg.entropy_log2_buckets, interpret=True)
    lanes = {"ip_src": plane[0], "ip_dst": plane[1],
             "ports": plane[2], "proto_pkts": plane[3]}
    after = flow_suite.update(
        state, flow_suite.unpack_lanes(
            {k: jnp.asarray(v) for k, v in lanes.items()}),
        jnp.arange(C) < n, cfg)
    np.testing.assert_array_equal(
        np.asarray(cms_h).astype(np.int32),
        np.asarray(after.sketch.counts) - np.asarray(state.sketch.counts))
    np.testing.assert_array_equal(
        np.asarray(ent_h).astype(np.int32),
        np.asarray(after.ent.hist) - np.asarray(state.ent.hist))


def test_fused_dict_wire_state_bit_identical(interpret):
    """The dict wire's news/hits updates with the fused kernel forced
    (interpret mode off-TPU) == the unfused updates on the same packed
    wire: every sketch leaf and every dict-table word. The stream sits
    well inside the documented 2^24 per-cell exactness bound."""
    import jax

    from deepflow_tpu.models import flow_dict

    rng, pool = _pool(seed=57, n=256, hi=1 << 12)
    # row-coherent sampling (one index array for ALL columns) so the
    # 256 pooled 5-tuples actually repeat — that is what fills the
    # hits lane (_chunks resamples per column: fresh combos, all news)
    chunks = []
    for _ in range(3):
        idx = rng.integers(0, 256, 1500)
        chunks.append({k: v[idx] for k, v in pool.items()})
    p = flow_dict.FlowDictPacker(capacity=1 << 13, hits_batch=512)
    batches = []
    for c in chunks:
        batches += p.pack(c)
    batches += p.flush()
    assert {k for k, _, _ in batches} == {"news", "hits"}
    cfg_f = _fused_cfg(fused_hists=True)
    cfg_u = _fused_cfg()
    sf, df = flow_dict.apply_batches(
        flow_suite.init(cfg_f), flow_dict.init_dict(1 << 13),
        batches, cfg_f)
    su, du = flow_dict.apply_batches(
        flow_suite.init(cfg_u), flow_dict.init_dict(1 << 13),
        batches, cfg_u)
    for a, b in zip(jax.tree.leaves((su, du)), jax.tree.leaves((sf, df))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- satellite: decode string-hash LRU --------------------------------------

def test_hash_cache_hits_and_determinism():
    """The bounded FNV LRU returns exactly what the uncached hash
    returns, and repeat strings count as hits on the Countable."""
    from deepflow_tpu.decode import columnar

    for s in (b"", b"/api/v1/items", b"svc.example.com", b"x" * 300):
        assert columnar._fnv1a32_cached(s) == columnar._fnv1a32(s)
    before = columnar.hash_cache_counters()
    columnar._fnv1a32_cached(b"repeat-me")
    columnar._fnv1a32_cached(b"repeat-me")
    after = columnar.hash_cache_counters()
    assert after["hash_cache_hits"] >= before["hash_cache_hits"] + 1
    assert after["hash_cache_size"] <= columnar._HASH_CACHE_CAP


def test_hash_cache_skips_tag_dict_codes():
    """TagDict codes stay on the dict's own reversible map — the LRU
    only memoizes the pure FNV path, so a dict reset can't serve stale
    codes."""
    from deepflow_tpu.decode import columnar

    class FakeDict:
        def __init__(self):
            self.calls = 0

        def encode_one(self, s):
            self.calls += 1
            return 42

    d = FakeDict()
    assert columnar._hash_str("endpoint", d) == 42
    assert columnar._hash_str("endpoint", d) == 42
    assert d.calls == 2              # never short-circuited by the LRU
    assert columnar._hash_str("endpoint") == columnar._fnv1a32(
        b"endpoint")
