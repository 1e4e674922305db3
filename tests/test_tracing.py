"""Flight recorder (runtime/tracing.py) + Prometheus exposition
(runtime/promexpo.py): span mechanics, ring eviction, host-DDSketch
quantile accuracy, batch causality through a miniature
receiver->decode->export run, and the strict text-format contract."""

import socket
import time
import urllib.request

import numpy as np
import pytest

from deepflow_tpu.runtime.promexpo import (PrometheusExporter,
                                           render_metrics,
                                           validate_exposition)
from deepflow_tpu.runtime.stats import StatsRegistry
from deepflow_tpu.runtime.tracing import (HostDDSketch, Tracer,
                                          default_tracer)


# ------------------------------------------------------------- tracer core

def test_disabled_span_is_shared_noop():
    """Disabled tracing must allocate nothing on the hot path: every
    span() call returns the SAME no-op object and records nothing."""
    tr = Tracer()
    assert tr.span("a") is tr.span("b")
    with tr.span("a"):
        pass
    tr.observe("a", 1.0)
    assert tr.latency() == {}
    assert tr.spans_recorded == 0


def test_span_records_cpu_time_beside_wall_time():
    """A context-manager span adds the thread's CPU seconds to
    `cpu.<stage>`: never negative, never more than the wall time, in
    the histograms only (the ring keeps wall spans)."""
    tr = Tracer()
    tr.enable()
    with tr.span("work"):
        end = time.perf_counter() + 0.01
        while time.perf_counter() < end:      # on the CPU
            pass
        time.sleep(0.02)                      # off it
    st = tr.stages()
    wall, cpu = st["work"].sum, st["cpu.work"].sum
    assert 0.0 <= cpu <= wall
    assert cpu >= 0.005 and wall - cpu >= 0.015
    assert [s["stage"] for s in tr.recent(10)] == ["work"]
    tr.observe("queued", 0.01)                # the observe form: wall only
    assert "cpu.queued" not in tr.stages()


def test_disabled_tracer_reads_no_cpu_clock_and_opens_no_annotation(
        monkeypatch):
    """The disabled path keeps its contract — the shared no-op, no clock
    read, no annotation — and the enabled path opens one annotation
    named exactly as the stage (a recording factory stands in for the
    device profiler's; no profiler session is started)."""
    from deepflow_tpu.runtime import tracing

    opened, clock = [], []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            opened.append("/" + self.name)

    real = time.thread_time
    monkeypatch.setattr(tracing, "_annotation_class", lambda: Annotation)
    monkeypatch.setattr(tracing.time, "thread_time",
                        lambda: clock.append(1) or real())
    tr = Tracer()
    with tr.span("decode"):
        pass
    assert tr.span("decode") is tr.span("export")
    assert opened == [] and clock == []
    tr.enable()
    with tr.span("decode"):
        with tr.span("decode.parse"):
            pass
    tr.observe("feed.starved", 0.001)
    assert opened == ["decode", "decode.parse", "/decode.parse", "/decode"]
    assert len(clock) == 4


def test_tracing_module_imports_no_jax():
    import subprocess
    import sys

    code = ("import sys; import deepflow_tpu.runtime.tracing; "
            "sys.exit(any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules))")
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0


@pytest.mark.parametrize("name", [
    "sketch_update_dict", "sketch_update_lanes", "sketch_update_hits",
    "sketch_update_news", "sketch_flush", "pod_merge", "anomaly_window"])
def test_served_programs_carry_stable_xla_names(name):
    """Every jitted program on the served path lowers to an XLA module
    of a stable name, so a device trace's per-program time reads as
    jit_<name> (not jit_prog or jit__lambda_)."""
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp

    from deepflow_tpu.anomaly import AnomalyConfig, detectors
    from deepflow_tpu.batch.schema import SKETCH_L4_SCHEMA
    from deepflow_tpu.models import flow_dict, flow_suite
    from deepflow_tpu.parallel.pod import PodFlowSuite
    from deepflow_tpu.runtime.tpu_sketch import TpuSketchExporter

    cfg = flow_suite.FlowSuiteConfig(cms_log2_width=10, ring_size=64,
                                     top_k=8, hll_groups=16,
                                     entropy_log2_buckets=6)
    state = flow_suite.init(cfg)
    rng = np.random.default_rng(0)
    cols = {n: rng.integers(0, 50, 300).astype(dt)
            for n, dt in SKETCH_L4_SCHEMA.columns}
    packer = flow_dict.FlowDictPacker(capacity=1024, hits_batch=256)
    news = packer.pack(cols) + packer.flush()
    hits = packer.pack(cols) + packer.flush()
    dstate = flow_dict.init_dict(packer.capacity)
    if name == "sketch_update_dict":
        sig = flow_dict.wire_signature(news)
        flat = np.empty(flow_dict.wire_words(sig), np.uint32)
        flow_dict.stage_wire(news, flat)
        prog, args = flow_dict.make_wire_update(cfg, sig), (state, dstate,
                                                            flat)
    elif name == "sketch_update_lanes":
        flat = np.zeros(flow_suite.coalesced_lanes_words(1, 256), np.uint32)
        prog, args = flow_suite.make_coalesced_update(cfg, 1, 256), (state,
                                                                     flat)
    elif name == "pod_merge":
        prog = PodFlowSuite._make_merge(SimpleNamespace(cfg=cfg), 2)
        args = (jax.tree.map(lambda x: jnp.stack([x, x]), state),)
    elif name == "anomaly_window":
        acfg = AnomalyConfig()
        out = jax.eval_shape(lambda s: flow_suite.flush(s, cfg), state)[1]
        prog = detectors.make_window_step(acfg)
        args = (detectors.init(acfg), out.entropies, out.topk_counts,
                out.service_cardinality, out.rows)
    else:
        exp = TpuSketchExporter(store=None, cfg=cfg, batch_rows=256,
                                wire="dict", prefetch_depth=0)
        kind = {"sketch_update_hits": "hits",
                "sketch_update_news": "news"}.get(name)
        if kind is None:
            prog, args = exp._flush_fn, (exp.state,)
        else:
            _, plane, n = next(w for w in news + hits if w[0] == kind)
            prog = exp._update_hits if kind == "hits" else exp._update_news
            args = (exp.state, exp._dict_state, plane, np.uint32(n))
    text = prog.lower(*args).as_text()
    assert f"module @jit_{name} " in text, text[:200]


def test_span_nesting_records_both_stages():
    tr = Tracer()
    tr.enable()
    with tr.span("outer", stream="s", batch_id=7):
        time.sleep(0.002)
        with tr.span("inner", batch_id=7):
            time.sleep(0.001)
    lat = tr.latency()
    assert set(lat) == {"outer", "inner", "cpu.outer", "cpu.inner"}
    assert lat["outer"]["max_ms"] >= lat["inner"]["max_ms"]
    assert lat["inner"]["max_ms"] >= 1.0
    spans = tr.recent(10)
    # completion order: inner closes first, newest-first listing
    assert [s["stage"] for s in spans] == ["outer", "inner"]
    assert all(s["batch_id"] == 7 for s in spans)


def test_ring_eviction_keeps_newest():
    tr = Tracer(ring=8)
    tr.enable()
    for i in range(20):
        tr.observe("s", 0.001, batch_id=i)
    got = tr.recent(100)
    assert len(got) == 8
    assert [s["batch_id"] for s in got] == list(range(19, 11, -1))
    # histograms saw every span, the ring only the last 8
    assert tr.latency()["s"]["count"] == 20


def test_span_rows_settable_inside_block():
    tr = Tracer()
    tr.enable()
    with tr.span("decode") as sp:
        sp.rows = 123
    assert tr.recent(1)[0]["rows"] == 123


def test_thread_local_batch_propagation():
    tr = Tracer()
    tr.enable()
    tr.set_batch(42)
    tr.observe("x", 0.001)          # batch_id=-1 -> thread-local
    assert tr.recent(1)[0]["batch_id"] == 42


# ------------------------------------------------- host DDSketch accuracy

@pytest.mark.parametrize("dist", ["lognormal", "uniform"])
def test_host_sketch_quantiles_vs_numpy(rng, dist):
    """p50/p95/p99 must come back within the sketch's RELATIVE error
    bound (alpha, plus one bucket of slack) against the exact numpy
    quantile over the same samples — the ops/ddsketch.py guarantee,
    mirrored host-side."""
    sk = HostDDSketch(alpha=0.01)
    if dist == "lognormal":
        xs = rng.lognormal(-6.0, 1.5, 20000)     # ~ms-scale durations
    else:
        xs = rng.uniform(1e-5, 0.5, 20000)
    for x in xs:
        sk.add(float(x))
    for q in (0.5, 0.95, 0.99):
        exact = float(np.quantile(xs, q))
        got = sk.quantile(q)
        assert abs(got - exact) / exact < 3 * sk.alpha + 0.01, (q, got,
                                                                exact)
    assert sk.count == len(xs)
    assert abs(sk.sum - xs.sum()) / xs.sum() < 1e-6
    assert sk.max == pytest.approx(xs.max())


def test_host_sketch_zeros_and_merge():
    a = HostDDSketch()
    b = HostDDSketch()
    for v in (0.0, 1e-9, 0.001):
        a.add(v)
    b.add(0.002)
    a.merge(b)
    assert a.count == 4 and a.zeros == 2
    assert a.quantile(0.2) == 0.0           # inside the zeros mass
    assert a.quantile(0.99) == pytest.approx(0.002, rel=0.05)


def test_cumulative_buckets_are_monotonic_and_total():
    sk = HostDDSketch(alpha=0.02, buckets=128)
    rng = np.random.default_rng(3)
    for x in rng.uniform(1e-6, 1.0, 5000):
        sk.add(float(x))
    buckets = sk.cumulative_buckets(stride=16)
    counts = [c for _, c in buckets]
    assert counts == sorted(counts)
    assert counts[-1] == sk.count           # top boundary covers all
    bounds = [le for le, _ in buckets]
    assert bounds == sorted(bounds)


# ------------------------------------------------------- exposition format

def test_render_metrics_is_strictly_valid():
    reg = StatsRegistry()
    reg.register("queue.in", lambda: {"in": 5, "pending": 0,
                                      "mode": "local"},
                 tags={"idx": "0"})
    tr = Tracer()
    tr.enable()
    for i in range(100):
        tr.observe("decode", 0.001 * (i + 1), stream="l4")
    tr.gauge("tpu_transfers_per_batch", 2.5)
    text = render_metrics(reg, tr)
    assert validate_exposition(text) == []
    assert "deepflow_queue_in_in" in text
    assert 'stage="decode"' in text
    assert 'le="+Inf"' in text
    assert "deepflow_trace_tpu_transfers_per_batch 2.5" in text
    # non-numeric countable values ride an info sample, never a bare
    # unparseable value
    assert 'mode="local"' in text


def test_validator_rejects_malformed_documents():
    assert validate_exposition("") != []
    assert validate_exposition("no value line\n") != []
    assert validate_exposition("ok 1")  # missing trailing newline
    bad_hist = ("# TYPE h histogram\n"
                'h_bucket{le="1.0"} 5\n'        # no +Inf bucket
                "h_count 5\n"
                "h_sum 1.0\n")
    assert any("+Inf" in p for p in validate_exposition(bad_hist))
    inconsistent = ("# TYPE h histogram\n"
                    'h_bucket{le="1.0"} 5\n'
                    'h_bucket{le="+Inf"} 5\n'
                    "h_count 7\n"
                    "h_sum 1.0\n")
    assert any("_count" in p for p in validate_exposition(inconsistent))
    decreasing = ("# TYPE h histogram\n"
                  'h_bucket{le="1.0"} 5\n'
                  'h_bucket{le="2.0"} 3\n'
                  'h_bucket{le="+Inf"} 5\n'
                  "h_count 5\n")
    assert any("decrease" in p for p in validate_exposition(decreasing))


def test_prometheus_http_endpoint_serves_valid_exposition():
    tr = Tracer()
    tr.enable()
    tr.observe("kernel", 0.003)
    exp = PrometheusExporter(stats=None, tracer=tr, port=0)
    exp.start()
    try:
        url = f"http://127.0.0.1:{exp.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as resp:
            assert resp.status == 200
            assert "version=0.0.4" in resp.headers["Content-Type"]
            text = resp.read().decode()
        assert validate_exposition(text) == []
        assert 'stage="kernel"' in text
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/nope", timeout=5)
    finally:
        exp.close()


# ----------------------------------------- miniature end-to-end causality

def _l4_frame(n=500, seed=0, seq=1):
    from deepflow_tpu.batch.schema import L4_SCHEMA
    from deepflow_tpu.wire import columnar_wire
    from deepflow_tpu.wire.framing import (FlowHeader, MessageType,
                                           encode_frame)
    r = np.random.default_rng(seed)
    cols = {name: (r.integers(-100, 100, n).astype(dt)
                   if np.dtype(dt) == np.int32
                   else r.integers(0, 1 << 20, n).astype(dt))
            for name, dt in L4_SCHEMA.columns}
    return encode_frame(MessageType.COLUMNAR_FLOW,
                        columnar_wire.encode_columnar(cols),
                        FlowHeader(sequence=seq, vtap_id=3))


def test_batch_id_propagates_receiver_to_exporter(tmp_path):
    """One frame's receiver-stamped batch id must reappear on the
    decode span and on the export span (causality across two thread
    hops), and `trace latency` / the Prometheus endpoint must expose
    non-zero receiver/decode/export/kernel/window stages, beside the
    child stages, the feed's starvation and the CPU companions."""
    from deepflow_tpu.enrich.platform_data import PlatformDataManager
    from deepflow_tpu.pipelines import Ingester, IngesterConfig
    from deepflow_tpu.runtime.debug import debug_request

    tracer = default_tracer()
    tracer.reset()
    ing = Ingester(IngesterConfig(listen_port=0, debug_port=0,
                                  prom_port=0,
                                  tpu_sketch_window_s=0.2),
                   platform=PlatformDataManager())
    ing.start()
    # a listener on the snapshot bus, as the querier's cache is, so
    # each window close publishes
    unsubscribe = ing.tpu_sketch.snapshot_bus.subscribe(lambda snap: None)
    try:
        assert tracer.enabled
        with socket.create_connection(("127.0.0.1", ing.port),
                                      timeout=5) as s:
            for i in range(4):
                s.sendall(_l4_frame(seed=i, seq=i + 1))
        deadline = time.time() + 30
        needed = {"receiver", "decode", "export", "kernel", "window"}
        children = {"decode.parse", "export.lock_wait", "window.drain",
                    "window.publish", "feed.starved", "cpu.decode"}
        while time.time() < deadline:
            if needed | children <= set(tracer.latency()):
                break
            time.sleep(0.1)
        lat = tracer.latency()
        assert needed | children <= set(lat), sorted(lat)
        for stage in needed:
            assert lat[stage]["p99_ms"] > 0.0, stage
            assert lat[stage]["p50_ms"] <= lat[stage]["p95_ms"] \
                <= lat[stage]["p99_ms"] + 1e-9, stage
        # causality: some batch id observed at the receiver flows
        # through decode AND export spans
        by_stage = {}
        for s_ in tracer.recent(512):
            by_stage.setdefault(s_["stage"], set()).add(s_["batch_id"])
        linked = (by_stage["receiver"] & by_stage["decode"]
                  & by_stage["export"])
        assert linked, by_stage
        assert all(b > 0 for b in by_stage["receiver"])
        # the debug protocol serves the same data
        out = debug_request("latency", port=ing.debug.port)
        assert out["ok"] and needed <= set(out["data"]["stages"])
        spans = debug_request("spans", port=ing.debug.port,
                              count=50)["data"]["spans"]
        assert spans and all("dur_ms" in s_ for s_ in spans)
        rrt = debug_request("rrt", port=ing.debug.port)["data"]
        assert "tpu_transfers_per_batch" in rrt["gauges"]
        assert any(k.startswith("tpu_compile_s_") for k in rrt["gauges"])
        assert {"kernel", "kernel.compile"} <= set(rrt["kernel_stages"])
        # the live Prometheus endpoint serves the histograms, strictly
        # valid, with the kernel stage present
        with urllib.request.urlopen(
                f"http://127.0.0.1:{ing.prom_port}/metrics",
                timeout=5) as resp:
            text = resp.read().decode()
        assert validate_exposition(text) == []
        assert 'stage="kernel"' in text
        assert "deepflow_receiver_rx_frames" in text
    finally:
        unsubscribe()
        ing.close()


def test_trace_cli_latency_renders_table(capsys):
    """`python -m deepflow_tpu.cli trace latency` against a live
    debug server prints the per-stage quantile table."""
    from deepflow_tpu.cli import main
    from deepflow_tpu.runtime.debug import DebugServer

    tr = Tracer()
    tr.enable()
    for _ in range(10):
        tr.observe("receiver", 0.002)
        tr.observe("decode", 0.004)
    srv = DebugServer(StatsRegistry(), port=0, tracer=tr)
    srv.start()
    try:
        rc = main(["--debug-port", str(srv.port), "trace", "latency"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "STAGE" in out and "P99_MS" in out
        assert "receiver" in out and "decode" in out
        rc = main(["--debug-port", str(srv.port), "trace", "spans"])
        assert rc == 0
        assert "BATCH" in capsys.readouterr().out
        rc = main(["--debug-port", str(srv.port), "trace", "rrt"])
        assert rc == 0
    finally:
        srv.close()
