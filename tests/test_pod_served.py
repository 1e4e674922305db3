"""The 4-shard pod on the served path: built by `Server` from YAML, held
to a plain numpy reference, and observable (its spans, its named shard
programs, its transfer count).

Runs on the virtual CPU devices conftest pins. The reference below is
written out here and imports nothing of the program: the flow key is the
deployment's key definition (a murmur3-finalized hash_combine of the
5-tuple); everything else is counting.
"""

import numpy as np
import pytest
import yaml

from deepflow_tpu.batch.schema import L4_SCHEMA
from deepflow_tpu.models import FlowSuiteConfig, flow_suite
from deepflow_tpu.runtime.tpu_sketch import TpuSketchExporter
from deepflow_tpu.runtime.tracing import default_tracer

CFG = FlowSuiteConfig(cms_log2_width=10, ring_size=128, top_k=20,
                      hll_groups=32, hll_precision=6,
                      entropy_log2_buckets=8)
B = 2048
SHARDS = 4
_U32 = np.uint32
_GOLDEN = _U32(0x9E3779B9)


def _mix32(x):
    x = x ^ (x >> _U32(16))
    x = x * _U32(0x85EBCA6B)
    x = x ^ (x >> _U32(13))
    x = x * _U32(0xC2B2AE35)
    return x ^ (x >> _U32(16))


def _flow_keys(cols):
    with np.errstate(over="ignore"):
        h = np.full(len(cols["ip_src"]), _GOLDEN, _U32)
        for k in ("ip_src", "ip_dst", "port_src", "port_dst", "proto"):
            c = cols[k].astype(_U32)
            h = _mix32(h ^ (c + _GOLDEN + (h << _U32(6))
                            + (h >> _U32(2))))
    return h


def _stream(seed, n):
    """n records drawn Zipf-skewed from a pool of 600 flows, as decoded
    l4 columns; small packet counts."""
    rng = np.random.default_rng(seed)
    pool = {"ip_src": rng.integers(1, 1 << 32, 600, dtype=np.uint64),
            "ip_dst": rng.integers(1, 1 << 32, 600, dtype=np.uint64),
            "port_src": rng.integers(1024, 1 << 16, 600),
            "port_dst": rng.choice([53, 80, 443, 8080], 600),
            "proto": rng.choice([6, 17], 600)}
    pick = np.minimum(rng.zipf(1.3, n) - 1, 599)
    cols = {name: np.zeros(n, dt) for name, dt in L4_SCHEMA.columns}
    for k, v in pool.items():
        cols[k] = v[pick].astype(cols[k].dtype)
    for k, low in (("packet_tx", 1), ("packet_rx", 0)):
        cols[k] = rng.integers(low, 40, n).astype(cols[k].dtype)
    return cols


def _windows(seed):
    """Three windows of chunks, the middle one ending in a partial
    batch."""
    return [[_stream(seed + 10 * w + c, n) for c, n in enumerate(sizes)]
            for w, sizes in enumerate([(3000, 5192), (1500, 700),
                                       (4096,)])]


def _drive(exp, windows):
    """Each window's chunks through the lane's exporter contract, then a
    window close; returns the published snapshots' leaves."""
    snaps = []
    unsubscribe = exp.snapshot_bus.subscribe(
        lambda s: snaps.append(s.leaves))
    try:
        for chunks in windows:
            exp.process([("l4_flow_log", i, cols)
                         for i, cols in enumerate(chunks)])
            exp.flush_window()
    finally:
        unsubscribe()
    return snaps


@pytest.mark.parametrize("keys,shards,deadline", [
    ({"tpu_sketch_pod_shards": SHARDS, "pod_merge_deadline_s": 2.5},
     SHARDS, 2.5),
    ({}, None, None),
])
def test_server_yaml_selects_the_pod_or_the_single_chip_lane(
        tmp_path, keys, shards, deadline):
    from deepflow_tpu.server import Server

    doc = {"controller": {"enabled": False},
           "ingester": dict({"port": 0, "tpu_sketch_window_s": 3600.0},
                            **keys),
           "querier": {"enabled": False}, "self_telemetry": False}
    path = tmp_path / "server.yaml"
    path.write_text(yaml.safe_dump(doc))
    srv = Server(str(path))
    try:
        sk = srv.ingester.tpu_sketch
        if shards is None:
            assert sk.pod is None and sk.state is not None
            assert sk.wire == "dict"
        else:
            assert sk.pod is not None and sk.state is None
            assert sk.pod.n_shards == shards
            assert sk.pod.merge_deadline_s == deadline
            assert sk.wire == "lanes"
            assert len({d for s in sk.pod.shard_devices() for d in s}) \
                == shards
            assert sk.snapshot_bus is sk.pod.bus
    finally:
        srv.close()


def test_pod_lane_matches_the_reference_and_the_single_chip_lane():
    windows = _windows(2 ** 31 + 5)
    pod = TpuSketchExporter(store=None, cfg=CFG, window_seconds=3600,
                            batch_rows=B, pod_shards=SHARDS)
    one = TpuSketchExporter(store=None, cfg=CFG, window_seconds=3600,
                            batch_rows=B)
    try:
        got = _drive(pod, windows)
        want = _drive(one, windows)
        counters = pod.counters()
    finally:
        pod.close()
        one.close()
    cols = {k: np.concatenate([c[k] for w in windows for c in w])
            for k in windows[0][0]}
    keys, counts = np.unique(_flow_keys(cols), return_counts=True)
    packets = int(cols["packet_tx"].astype(np.int64).sum()
                  + cols["packet_rx"].astype(np.int64).sum())

    # one merged publish per window, holding exactly its records
    assert [int(s[7]) for s in got] == \
        [sum(len(c["ip_src"]) for c in w) for w in windows]
    assert sum(int(s[7]) for s in got) == len(cols["ip_src"])
    # the sketch is linear: each merged window's Count-Min is the
    # single-chip lane's, counter for counter
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert np.array_equal(g[0], w[0])
    union = list(got[0])
    union[0] = sum(s[0].astype(np.int64) for s in got)
    union[5] = sum(s[5].astype(np.int64) for s in got)
    from deepflow_tpu.runtime.snapbus import SketchSnapshot
    from deepflow_tpu.serving.tables import _SketchView

    view = _SketchView(SketchSnapshot(step=-1, seq=-1, wall_time=0.0,
                                      leaves=tuple(union)))
    est = view.cms_points(keys)
    assert (est >= counts).all()                 # never under
    assert (np.asarray(union[5]).sum(axis=1) == packets).all()
    for k in ("pod_merge_missed", "pod_rows_lost", "pod_rows_host",
              "pod_rows_excluded", "pod_device_errors"):
        assert counters[k] == 0, (k, counters)


def test_pod_spans_program_names_and_transfer_count():
    import jax.numpy as jnp

    from deepflow_tpu.models import flow_dict
    from deepflow_tpu.parallel.pod import PodFlowSuite

    tr = default_tracer()
    was = tr.enabled
    tr.enable()
    stages = ("pod.update", "pod.contribute", "pod.merge_wait",
              "pod.merge", "cpu.pod.merge")

    def counts():
        st = tr.stages()
        return {k: st[k].count if k in st else 0 for k in stages}

    before = counts()
    exp = TpuSketchExporter(store=None, cfg=CFG, window_seconds=3600,
                            batch_rows=B, pod_shards=SHARDS)
    try:
        windows = _windows(7)
        for chunks in windows:
            exp.process([("l4_flow_log", i, cols)
                         for i, cols in enumerate(chunks)])
            exp.flush_window()
        exp.flush_window()              # an idle window: no merge
        batches = exp.batcher.emitted_batches
        h2d = exp.h2d_bytes
        c = exp.counters()
        lanes = exp.pod._upd_lanes.lower(
            exp.pod._shards[0].state,
            jnp.zeros((4, B // SHARDS), jnp.uint32), jnp.uint32(0),
            jnp.uint32(B)).as_text()
    finally:
        exp.close()
        if not was:
            tr.disable()
    after = counts()
    delta = {k: after[k] - before[k] for k in stages}
    epochs = len(windows)               # every one carried rows
    assert delta["pod.merge"] == epochs
    assert delta["cpu.pod.merge"] == epochs
    assert delta["pod.merge_wait"] == epochs
    assert delta["pod.contribute"] == epochs * SHARDS
    assert delta["pod.update"] == batches * SHARDS
    # every shard worker's put, counted: four uint32 lanes per column
    assert h2d == c["h2d_bytes"] == batches * B * 4 * 4
    assert "module @jit_sketch_update_pod_lanes " in lanes

    dpod = PodFlowSuite(CFG, n_shards=2, wire="dict", dict_capacity=1024)
    try:
        packer = flow_dict.FlowDictPacker(capacity=1024, hits_batch=256)
        cols = _stream(3, 300)
        wire = packer.pack(cols) + packer.flush()
        wire += packer.pack(cols) + packer.flush()
        sh = dpod._shards[0]
        for kind, plane, n in wire:
            if kind == "news":
                prog, args = dpod._upd_news, (
                    sh.state, sh.dtable, plane, jnp.uint32(n),
                    jnp.uint32(0))
            else:
                prog, args = dpod._upd_hits, (
                    sh.state, sh.dtable, plane[:, :plane.shape[1] // 2],
                    jnp.uint32(0), jnp.uint32(n))
            text = prog.lower(*args).as_text()
            assert f"module @jit_sketch_update_pod_{kind} " in text
    finally:
        dpod.close(final_epoch=False)
    assert {k for k, _, _ in wire} == {"news", "hits"}


def test_single_chip_lane_keeps_its_own_transfer_count():
    """Outside pod mode the counter is `_to_device`'s, as before."""
    exp = TpuSketchExporter(store=None, cfg=CFG, window_seconds=3600,
                            batch_rows=B, wire="lanes")
    try:
        exp.process([("l4_flow_log", 0, _stream(11, 3 * B))])
        exp.flush_window()
        assert exp.pod is None
        assert exp.h2d_bytes == exp.counters()["h2d_bytes"] > 0
        assert exp.h2d_bytes >= 3 * B * 16    # at least the lane planes
    finally:
        exp.close()

