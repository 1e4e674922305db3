#!/usr/bin/env python3
"""The served sketch path, end to end, on a TPU.

One process, no children. With no arguments it needs one chip:

1. builds `deepflow_tpu.server.Server` from a config in a temp dir
   (ingester on port 0, the tpu_sketch lane on, a store, the querier on,
   the controller off; every other setting at its default);
2. sends a warm-up window and then 4 windows of 2^21 (`--records`)
   l4 records each over a loopback TCP socket: protobuf TaggedFlow
   records from `replay.generator.SyntheticAgent(seed)`, in
   BaseHeader/FlowHeader frames of at most 512,000 B, decoded by the
   native decoder. Half the records are fresh 5-tuples (random source
   ports, Zipf(1.25) services: the 131,072-slot dict table churns),
   half come from a Zipf pool of 4,096 flows, so a top-100 exists. One
   frame pool, built before the clock starts, is resent every window;
3. reads each window back through the querier's HTTP SQL API
   (`sketch.topk(100)`, `sketch.cms_point(k)` for the 20 heaviest keys,
   `sketch.hll_card()`) and holds the answers to a plain numpy
   reference over the records sent: exact rows, top-100 recall >= 0.99,
   each CMS point in [exact, exact + e*N/2^17], HLL within 3 standard
   errors;
4. fails on any silent fallback: device errors, degraded windows, host
   or lost rows, lost windows, supervisor crashes, no native decoder;
5. runs the fused Pallas kernels (FlowSuiteConfig(fused_hists=True)) on
   both wires over the same batches and holds their state to the XLA
   path's, leaf for leaf.

`--chips 4` runs only the pod path and what it is compared with (see
`pod_phase`). The last stdout line is the contract line:
{"ok": ..., "device": {"platform", "kind", "count"}}. Without a TPU the
script says so, runs no full-size phase (pass --records N to rehearse
at a small size on the CPU) and exits nonzero.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import socket
import sys
import tempfile
import time
import urllib.parse
import urllib.request

import numpy as np
import yaml

from deepflow_tpu.utils import compile_cache

HEAVY_POOL = 4096        # distinct heavy flows in the Zipf half
TOP_K = 100
CMS_KEYS = 20
WINDOW_S = 10.0          # tpu_sketch_window_s of the served config
WINDOWS = {1: 4, 4: 2}   # measured windows by --chips


def say(name: str, **fields) -> None:
    print(f"{name}: {json.dumps(fields, default=float)}", flush=True)


class CompileClock:
    """XLA compile seconds of this process, from JAX's own compile
    events (a persistent-cache hit counts its lookup): every program,
    the pod's shard lanes included."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.seconds, self.programs = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += secs
            self.programs += 1


# -- traffic ---------------------------------------------------------------

def make_stream(seed: int, n: int):
    """(columns, frames): n l4 records as SyntheticAgent columns and the
    wire frames that carry them, in order."""
    from deepflow_tpu.replay.generator import SyntheticAgent
    from deepflow_tpu.wire import MessageType
    from deepflow_tpu.wire.framing import (FLOW_HEADER_LEN,
                                           MESSAGE_FRAME_SIZE_MAX,
                                           MESSAGE_HEADER_LEN)

    agent = SyntheticAgent(seed=seed)
    fresh = agent.l4_columns(n - n // 2)
    heavy = agent.l4_columns_pooled(n // 2, pool=HEAVY_POOL)
    order = np.random.default_rng(seed).permutation(n)
    cols = {k: np.concatenate([fresh[k], heavy[k]])[order] for k in fresh}
    cols["flow_id"] = np.arange(n, dtype=np.uint64) + np.uint64(1)
    recs = [agent.l4_record(cols, i) for i in range(n)]
    # as many records per frame as fit 512,000 B at the longest record
    per_frame = (MESSAGE_FRAME_SIZE_MAX - MESSAGE_HEADER_LEN
                 - FLOW_HEADER_LEN) // (4 + max(map(len, recs)))
    return cols, list(agent.frames(recs, MessageType.TAGGEDFLOW,
                                   per_frame=per_frame))


def reference(cols, cfg) -> dict:
    """The plain numpy answers for one window of `cols`."""
    from deepflow_tpu.utils.u32 import fold_columns_np

    u32 = {k: cols[k].astype(np.uint32) for k in
           ("ip_src", "ip_dst", "port_src", "port_dst", "proto")}
    keys = fold_columns_np([u32["ip_src"], u32["ip_dst"], u32["port_src"],
                            u32["port_dst"], u32["proto"]])
    uniq, counts = np.unique(keys, return_counts=True)
    order = np.argsort(-counts, kind="stable")
    # hll_card() sums the per-service distinct client counts: services
    # hash (ip_dst, port_dst, proto) into hll_groups groups
    group = fold_columns_np([u32["ip_dst"], u32["port_dst"], u32["proto"]]) \
        % np.uint32(cfg.hll_groups)
    pairs = (group.astype(np.uint64) << np.uint64(32)) | u32["ip_src"]
    return {"n": len(keys),
            "exact": dict(zip(uniq.tolist(), counts.tolist())),
            "top": uniq[order[:TOP_K]].tolist(),
            "kth": int(counts[order[TOP_K - 1]]),
            "distinct_keys": len(uniq),
            "hll_exact": int(len(np.unique(pairs)))}


# -- the served path ------------------------------------------------------

class Client:
    """The agent's side of one ingester: a loopback TCP connection, plus
    the tpu_sketch lane's counters to pace windows by."""

    def __init__(self, ingester) -> None:
        self.sketch = ingester.tpu_sketch
        self.sent = 0
        self.last_flush = time.monotonic()
        self.last_absorb = None
        self.sock = socket.create_connection(("127.0.0.1", ingester.port))

    def counters(self) -> dict:
        return self.sketch.counters()

    def wait(self, pred, timeout: float, what: str) -> dict:
        end = time.monotonic() + timeout
        while True:
            c = self.counters()
            if pred(c):
                return c
            if time.monotonic() > end:
                raise TimeoutError(f"{what}: {c['rows_in']} rows in, "
                                   f"{self.sent} sent, window "
                                   f"{c['windows']}")
            time.sleep(0.005)

    def send(self, frames, n: int, timeout: float) -> float:
        """Send one window; seconds until every record is in the lane."""
        t0 = time.perf_counter()
        for fr in frames:
            self.sock.sendall(fr)
        self.sent += n
        self.wait(lambda c: c["rows_in"] >= self.sent, timeout, "absorb")
        self.last_absorb = time.perf_counter() - t0
        return self.last_absorb

    def flushed(self, timeout: float) -> int:
        """Wait out the next window flush; returns the window count."""
        w = self.counters()["windows"]
        w = self.wait(lambda c: c["windows"] > w, timeout,
                      "window flush")["windows"]
        self.last_flush = time.monotonic()
        return w


class Served(Client):
    """A Server built from a config file, and its agent and reader."""

    def __init__(self, workdir: str) -> None:
        from deepflow_tpu.server import Server

        cfg = {"controller": {"enabled": False},
               "ingester": {"port": 0,
                            "store_path": os.path.join(workdir, "store"),
                            "tpu_sketch_window_s": WINDOW_S},
               "querier": {"enabled": True, "port": 0}}
        path = os.path.join(workdir, "server.yaml")
        with open(path, "w") as f:
            yaml.safe_dump(cfg, f)
        self.server = Server(path)
        self.server.start()
        super().__init__(self.server.ingester)

    def close(self) -> None:
        self.sock.close()
        self.server.close()

    def sql(self, stmt: str) -> dict:
        body = urllib.parse.urlencode({"sql": stmt}).encode()
        url = f"http://127.0.0.1:{self.server.querier.port}/v1/query"
        with urllib.request.urlopen(url, data=body, timeout=30) as r:
            return json.loads(r.read())["result"]

    def window_answers(self, step: int, cms_keys, timeout: float = 30.0):
        """The three answers for published window `step`."""
        end = time.monotonic() + timeout
        while True:
            topk = self.sql(f"SELECT sketch.topk({TOP_K}) FROM sketch")
            if topk["values"] and topk["values"][0][1] == step:
                break
            if time.monotonic() > end:
                raise TimeoutError(f"window {step} never served")
            time.sleep(0.01)
        points = {}
        for k in cms_keys:
            row = self.sql(f"SELECT sketch.cms_point({k}) FROM sketch")
            row = row["values"][0]
            assert row[1] == step, (row, step)
            points[k] = row[3]
        hll = self.sql("SELECT sketch.hll_card() FROM sketch")["values"][0]
        assert hll[1] == step, (hll, step)
        return ([(r[3], r[4]) for r in topk["values"]], points, hll[3])


def run_window(cl: Client, frames, n: int, tries: int = 3):
    """Send one window so that it lands alone in one published window:
    start right after a flush, and check that no flush fell inside the
    send. Returns (step, absorb seconds, attempts)."""
    for attempt in range(1, tries + 1):
        # start now if the last send fits in what is left of this
        # window with room to spare, else right after the next flush
        left = WINDOW_S - (time.monotonic() - cl.last_flush)
        if attempt == 1 and cl.last_absorb is not None \
                and 1.5 * cl.last_absorb + 0.5 < left:
            w0 = cl.counters()["windows"]
        else:
            w0 = cl.flushed(4 * WINDOW_S + 60)
        secs = cl.send(frames, n, timeout=600)
        if cl.counters()["windows"] == w0:
            return cl.flushed(4 * WINDOW_S + 60), secs, attempt
    raise RuntimeError(f"window split by a flush {tries} times: the send "
                       f"({secs:.1f} s) outlasts tpu_sketch_window_s")


def warmup_window(cl: Client, frames, n: int, clock: CompileClock) -> dict:
    """The first window pays the compiles, so a flush may split it; it
    counts in rows_in but its answers are not checked."""
    t0 = time.perf_counter()
    absorb = cl.send(frames, n, timeout=900)
    cl.flushed(4 * WINDOW_S + 300)
    return {"absorb_s": absorb, "until_flushed_s": time.perf_counter() - t0,
            "compile_s": clock.seconds, "programs_compiled": clock.programs,
            "compile_scope": "every XLA compile in the process so far"}


def check_window(answers, ref: dict) -> dict:
    """Hold one window's served answers to the exact reference."""
    top, points, card = answers
    exact = ref["exact"]
    # a reported key is a hit when its exact count reaches the exact
    # 100th count (ties at the boundary are all correct answers)
    hits = sum(1 for k, _ in top[:TOP_K] if exact.get(k, 0) >= ref["kth"])
    strict = len({k for k, _ in top[:TOP_K]} & set(ref["top"]))
    bound = math.e * ref["n"] / (1 << 17)
    cms_bad = {k: (exact[k], est) for k, est in points.items()
               if not exact[k] <= est <= exact[k] + bound}
    se = 1.04 / math.sqrt(1024)
    hll_err = abs(card - ref["hll_exact"]) / ref["hll_exact"]
    out = {"recall": hits / TOP_K, "recall_strict": strict / TOP_K,
           "cms_points": len(points), "cms_out_of_bound": len(cms_bad),
           "cms_bound": bound,
           "cms_max_over": max(est - exact[k] for k, est in points.items()),
           "hll_card": card, "hll_exact": ref["hll_exact"],
           "hll_rel_err": hll_err, "hll_limit": 3 * se}
    out["ok"] = bool(out["recall"] >= 0.99 and not cms_bad
                     and hll_err <= 3 * se and len(top) >= TOP_K)
    return out


def served_phase(cols, frames, clock: CompileClock) -> bool:
    from deepflow_tpu.decode import native
    from deepflow_tpu.models import flow_suite

    ok = True
    err = native.build_error()
    say("phase native_decoder", ok=err is None, build_key=native.build_key(),
        error=err)
    ok &= err is None
    ref = reference(cols, flow_suite.FlowSuiteConfig())
    say("reference", records=ref["n"], distinct_flows=ref["distinct_keys"],
        kth_count=ref["kth"], hll_exact=ref["hll_exact"],
        frames=len(frames), frame_bytes_max=max(map(len, frames)))
    cms_keys = ref["top"][:CMS_KEYS]
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    srv = Served(work)
    try:
        say("phase warmup_window",
            **warmup_window(srv, frames, ref["n"], clock))
        total_s = 0.0
        for i in range(WINDOWS[1]):
            step, secs, tries = run_window(srv, frames, ref["n"])
            check = check_window(srv.window_answers(step, cms_keys), ref)
            total_s += secs
            say(f"phase window_{i}", step=step, absorb_s=secs,
                attempts=tries, records_per_s=ref["n"] / secs, **check)
            ok &= check["ok"]
        fine, faults = fallbacks(srv, srv.server.ingester)
        say("phase served", ok=fine, measured_windows=WINDOWS[1],
            records_per_s=ref["n"] * WINDOWS[1] / total_s,
            rate_scope="send start to rows_in, measured windows",
            wire=srv.sketch.wire, **faults)
        ok &= fine
    finally:
        srv.close()
    return bool(ok)


def fallbacks(cl: Client, ingester):
    """(ok, counters): every row sent is in the lane, and no fallback
    absorbed any of it."""
    c = cl.counters()
    faults = {k: c[k] for k in ("device_errors", "degraded", "host_rows",
                                "lost_rows", "lost_windows")}
    faults["supervisor_crashes"] = ingester.supervisor.counters()["crashes"]
    faults["decode_errors"] = sum(
        d.counters()["decode_errors"] for d in ingester.flow_log.decoders
        if d.stream == "l4_flow_log")
    conserved = c["rows_in"] == cl.sent
    return (conserved and not any(faults.values()),
            dict(faults, rows_in=c["rows_in"], records_sent=cl.sent,
                 windows_published=c["windows"]))


# -- fused kernels --------------------------------------------------------

def _batches(cols, C: int):
    keep = ("ip_src", "ip_dst", "port_src", "port_dst", "proto",
            "packet_tx", "packet_rx")
    n = len(cols["ip_src"])
    for s in range(0, n, C):
        yield {k: cols[k][s:s + C].astype(np.uint32) for k in keep}


def fused_phase(cols) -> bool:
    """FlowSuiteConfig(fused_hists=True) against the XLA path over the
    same batches, on both wires: every state leaf equal."""
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.models import flow_dict, flow_suite

    C = 1 << 15
    cfgs = {f: flow_suite.FlowSuiteConfig(fused_hists=f)
            for f in (False, True)}
    t0 = time.perf_counter()
    lanes = {}
    for f, cfg in cfgs.items():
        prog = flow_suite.make_coalesced_update(cfg, 1, C)
        st = flow_suite.init(cfg)
        for b in _batches(cols, C):
            flat = np.zeros(flow_suite.coalesced_lanes_words(1, C), np.uint32)
            n = len(b["ip_src"])
            flat[0] = n
            flow_suite.pack_lanes_into(
                b, flow_suite.slot_plane(flat, 0, C)[:, :n])
            st, _ = prog(st, jnp.asarray(flat))
        lanes[f] = jax.device_get(st)
    packer = flow_dict.FlowDictPacker(capacity=1 << 17, hits_batch=C)
    wire = []
    for b in _batches(cols, C):
        wire += packer.pack(b)
    wire += packer.flush()
    dicts = {}
    for f, cfg in cfgs.items():
        news = jax.jit(lambda s, d, p, n, cfg=cfg:
                       flow_dict.update_news(s, d, p, n, cfg),
                       donate_argnums=(0, 1))
        hits = jax.jit(lambda s, d, p, n, cfg=cfg:
                       flow_dict.update_hits(s, d, p, n, cfg),
                       donate_argnums=0)
        dicts[f] = jax.device_get(flow_dict.apply_batches(
            flow_suite.init(cfg), flow_dict.init_dict(1 << 17), wire, cfg,
            news_fn=news, hits_fn=hits))
    diff = {}
    for name, got in (("lanes", lanes), ("dict", dicts)):
        a, b = jax.tree.leaves(got[False]), jax.tree.leaves(got[True])
        diff[name] = [i for i, (x, y) in enumerate(zip(a, b))
                      if not np.array_equal(x, y)]
    ok = not diff["lanes"] and not diff["dict"]
    # inside the kernel's stated exactness bound: every per-batch cell
    # sum of the entropy histogram stays below 2^24
    say("phase fused", ok=ok, unequal_leaves=diff,
        leaves=len(jax.tree.leaves(lanes[True])),
        lane_batches=-(-len(cols["ip_src"]) // C),
        dict_planes=len(wire), rows=int(lanes[True].rows_seen),
        seconds=time.perf_counter() - t0)
    return ok


# -- four chips -------------------------------------------------------------

def pod_phase(cols, frames, clock: CompileClock) -> bool:
    """Four chips: an Ingester running the tpu_sketch lane as a pod of
    4 single-device shards, over the seeded stream. Each window's merged
    output is held leaf for leaf to the ShardedFlowSuite mesh lane on a
    4-device mesh over the same batches, and its merged CMS, HLL,
    entropy and rows_seen leaves to a one-chip lanes-wire lane (these
    sketches merge by integer sums and maxima). The shards' state must
    sit on four distinct devices."""
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.models import flow_suite
    from deepflow_tpu.parallel import ShardedFlowSuite, make_mesh
    from deepflow_tpu.pipelines import Ingester, IngesterConfig

    C = 1 << 15                       # TpuSketchExporter batch_rows
    cfg = flow_suite.FlowSuiteConfig()
    n = len(cols["ip_src"])
    # the pod lane's batches for one window: one decoder keeps frame
    # order, and the exporter's batcher cuts every C rows and flushes
    # the remainder at the window close
    planes = []
    for b in _batches(cols, C):
        plane = np.zeros((4, C), np.uint32)
        flow_suite.pack_lanes_into(b, plane[:, :len(b["ip_src"])])
        planes.append((plane, len(b["ip_src"])))
    mesh = ShardedFlowSuite(cfg, make_mesh(4))
    one_chip = flow_suite.make_coalesced_update(cfg, 1, C)
    ing = Ingester(IngesterConfig(listen_port=0, n_decoders=1,
                                  tpu_sketch_window_s=WINDOW_S,
                                  tpu_sketch_pod_shards=4))
    tsk = ing.tpu_sketch
    merged = []                        # the pod's merged-state publishes
    unsubscribe = tsk.snapshot_bus.subscribe(merged.append)
    ing.start()
    cl = Client(ing)
    ok = True
    try:
        say("phase pod_warmup", **warmup_window(cl, frames, n, clock))
        for w in range(WINDOWS[4]):
            before, k = tsk.last_output, len(merged)
            step, secs, tries = run_window(cl, frames, n)
            cl.wait(lambda c: tsk.last_output is not before
                    and len(merged) > k, 60, "pod window output")
            pod_out, pod_state = tsk.last_output, merged[-1].leaves
            t0 = time.perf_counter()
            st = mesh.init()
            for plane, nv in planes:
                st = mesh.update_lanes(st, mesh.put_lanes(plane), nv)
            _, mesh_out = mesh.flush(st)
            mesh_eq = [bool(np.array_equal(a, b)) for a, b in
                       zip(jax.device_get(mesh_out), jax.device_get(pod_out))]
            st = flow_suite.init(cfg)
            for plane, nv in planes:
                flat = np.empty(flow_suite.coalesced_lanes_words(1, C),
                                np.uint32)
                flat[0] = nv
                flow_suite.slot_plane(flat, 0, C)[:] = plane
                st, _ = one_chip(st, jnp.asarray(flat))
            lane = jax.tree.leaves(jax.device_get(st))
            # FlowSuiteState leaf order: 0 cms counts, 4 hll registers,
            # 5 entropy hist, 7 rows_seen (serving/tables._SketchView)
            one_eq = {name: bool(np.array_equal(lane[i], pod_state[i]))
                      for name, i in (("cms", 0), ("hll", 4),
                                      ("entropy", 5), ("rows_seen", 7))}
            good = all(mesh_eq) and all(one_eq.values()) \
                and int(pod_state[7]) == n
            say(f"phase pod_window_{w}", ok=good, step=step, absorb_s=secs,
                attempts=tries, records_per_s=n / secs,
                rows_merged=int(pod_state[7]), mesh_equal=mesh_eq,
                one_chip_equal=one_eq,
                reference_s=time.perf_counter() - t0)
            ok &= good
        devs = tsk.pod.shard_devices()
        distinct = all(len(d) == 1 for d in devs) \
            and len(set().union(*devs)) == 4
        say("phase pod_devices", ok=distinct,
            shards=[sorted(str(x) for x in d) for d in devs])
        fine, faults = fallbacks(cl, ing)
        pc = tsk.pod.counters()
        pod_faults = {k: pc[k] for k in (
            "pod_rows_host", "pod_rows_lost", "pod_rows_excluded",
            "pod_rows_pending", "pod_merge_missed", "pod_device_errors",
            "pod_shards_degraded", "pod_shards_lost")}
        fine &= not any(pod_faults.values()) \
            and pc["pod_rows_delivered"] == cl.sent
        say("phase pod", ok=fine, pod_rows_sent=pc["pod_rows_sent"],
            pod_rows_delivered=pc["pod_rows_delivered"], **faults,
            **pod_faults)
        ok &= distinct and fine
    finally:
        unsubscribe()
        cl.sock.close()
        ing.close()
    return bool(ok)


# -- main -----------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0x5EED)
    ap.add_argument("--records", type=int, default=None,
                    help="records per window (default 2^21; required "
                    "off a TPU, where it rehearses at a small size)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    cache_dir = compile_cache.configure()
    import jax

    clock = CompileClock()

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_tpu = device["platform"] == "tpu"
    say("device", cache_dir=cache_dir, jax=jax.__version__, **device)
    ok = on_tpu and device["count"] >= args.chips
    if not ok:
        say("phase device", ok=False,
            error=f"need {args.chips} TPU chip(s), found {device}")
    # the pod's check is bit-exact; half the records keep 4 chips short
    records = args.records or (1 << 21 if args.chips == 1 else 1 << 20)
    if on_tpu or args.records:
        t0 = time.perf_counter()
        cols, frames = make_stream(args.seed, records)
        say("stream", seed=args.seed, records=records,
            frames=len(frames), seconds=time.perf_counter() - t0)
        if args.chips == 4:
            ok &= pod_phase(cols, frames, clock)
        else:
            ok &= served_phase(cols, frames, clock)
            if on_tpu:
                ok &= fused_phase(cols)
            else:
                say("phase fused", ok=False,
                    error="the Pallas kernels compile for a TPU only")
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
