"""Per-kernel microbenches (reference role: agent/benches/ criterion suite).

Times each sketch/analytics kernel at fixed shapes on whatever backend JAX
resolves (the driver's real chip, or CPU under JAX_PLATFORMS=cpu) plus the
native C++ decoder, and prints one JSON line per kernel:

    {"bench": "cms_update", "rows_per_sec": ..., "ms_per_iter": ...,
     "shape": "...", "backend": "cpu"}

Run:  python benches/kernel_bench.py [--batch 1048576] [--iters 20]
      [--only cms_update,hll_update]

Each timed fn is jitted with donated state where the real pipelines donate,
warmed twice, then timed over `iters` calls, each window closed by
block_until_ready; --fetch-close closes it with a 4-byte result fetch
instead, minus a separately-measured fetch round-trip.
"""

from __future__ import annotations

import argparse
import os
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1 << 20)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--fetch-close", action="store_true",
                    help="close every timed window with a 4-byte result "
                    "fetch (minus its measured round trip) instead of "
                    "block_until_ready")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepflow_tpu.ops import cms, entropy, hll, mxu_hist, pca, topk

    backend = jax.default_backend()
    n = args.batch
    rng = np.random.default_rng(0xBE7C)
    keys = jnp.asarray(rng.integers(0, 1 << 20, n, dtype=np.uint32))
    groups = jnp.asarray(rng.integers(0, 64, n, dtype=np.uint32))
    mask = jnp.ones(n, jnp.bool_)

    results = []

    def bench(name, shape, fn, state_factory, *xs, rows=None):
        """Time state = fn(state, *xs) over iters (donated state, fresh
        per bench so donation can't free a buffer another bench holds)."""
        if args.only and name not in args.only.split(","):
            return
        step = jax.jit(fn, donate_argnums=0)

        def drain(state):
            """Wait for the device to really finish `state`."""
            if args.fetch_close:
                # 4-byte fetch of the first leaf: the only wait this
                # runtime cannot ack early (bench.py close_with_fetch)
                leaf = jax.tree_util.tree_leaves(state)[0]
                np.asarray(jnp.ravel(leaf)[0])
            else:
                jax.block_until_ready(state)

        s = state_factory()
        for _ in range(2):
            s = step(s, *xs)
        drain(s)
        # the closing fetch's own round-trip rides INSIDE the timed
        # window; measure it on the already-drained state and subtract
        # (a fetch round trip can be the same order as a kernel call)
        fetch_ms = 0.0
        if args.fetch_close:
            t0 = time.perf_counter()
            drain(s)
            fetch_ms = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.iters):
            s = step(s, *xs)
        drain(s)
        dt = max(time.perf_counter() - t0 - fetch_ms, 1e-9)
        r = {"bench": name, "shape": shape, "backend": backend,
             "ms_per_iter": round(1e3 * dt / args.iters, 3),
             "fetch_closed": bool(args.fetch_close)}
        if args.fetch_close:
            r["fetch_rtt_ms"] = round(1e3 * fetch_ms, 3)
        if rows is not None:
            r["rows_per_sec"] = round(rows * args.iters / dt)
        results.append(r)
        print(json.dumps(r), flush=True)

    # -- cms ---------------------------------------------------------------
    def cms_init():
        return cms.init(depth=4, log2_width=16)

    bench("cms_update", f"[{n}] keys, 4x2^16",
          lambda s, k: cms.update(s, k), cms_init, keys, rows=n)
    bench("cms_update_conservative", f"[{n}] keys, 4x2^16",
          lambda s, k: cms.update_conservative(s, k), cms_init, keys,
          rows=n)
    bench("cms_query", f"[{n}] keys, 4x2^16",
          lambda s, k: s._replace(
              seeds=s.seeds + (cms.query(s, k) > (1 << 30)).astype(
                  s.seeds.dtype).sum()),   # keep state-shaped for donate
          cms_init, keys, rows=n)

    # -- hll ---------------------------------------------------------------
    bench("hll_update", f"[{n}] keys, 64 groups, p=12",
          lambda s, g, k: hll.update(s, g, k),
          lambda: hll.init(groups=64, precision=12), groups, keys, rows=n)

    # -- entropy / mxu hist -----------------------------------------------
    feats = jnp.stack([keys, keys ^ 0x5A5A, keys >> 3, keys << 1])
    bench("entropy_update_mxu", f"[4,{n}] -> 2^12 buckets",
          lambda s, f, m: entropy.update(s, f, None, m),
          lambda: entropy.init(features=4, log2_buckets=12), feats,
          mask, rows=n)

    idx = jnp.asarray(rng.integers(0, 1 << 12, (4, n), dtype=np.uint32))

    def hist_step(acc, ix):
        return acc + mxu_hist.hist(ix, 1 << 12).astype(acc.dtype)

    bench("mxu_hist", f"[4,{n}] -> 2^12", hist_step,
          lambda: jnp.zeros((4, 1 << 12), jnp.int32), idx, rows=n)

    # Pallas VMEM-resident accumulator vs the XLA scan carry, at the
    # CMS shape (the BENCH kernel hot path). Real TPUs only: the Mosaic
    # interpreter would measure nothing real, and the kernel's TPU
    # compiler params don't lower on GPU.
    if backend == "tpu":
        from deepflow_tpu.ops.pallas_hist import hist_pallas

        idx16 = jnp.asarray(rng.integers(0, 1 << 16, (4, n),
                                         dtype=np.int32))

        for name, fn in (
                ("hist_xla_2e16",
                 lambda ix, w: mxu_hist.hist(ix, w, method="xla")),
                ("hist_pallas_2e16",
                 lambda ix, w: hist_pallas(ix, w))):
            bench(name, f"[4,{n}] -> 2^16",
                  lambda acc, ix, f=fn: acc + f(ix, 1 << 16),
                  lambda: jnp.zeros((4, 1 << 16), jnp.float32), idx16,
                  rows=n)

        # ISSUE 9 whole-step A/B at the staged-lane production shape:
        # update over one staged plane with the CMS+entropy histogram
        # half unfused (XLA) vs fused into the single Pallas kernel
        # (ops/pallas_sketch.py) — the on-silicon verdict its STATUS
        # note calls for. Bit-identical outputs within the 2^24
        # cell-sum bound (tests/test_staging.py, ops/pallas_sketch.py);
        # this measures only the dispatch/residency difference.
        from deepflow_tpu.models import flow_suite as fs

        lane_plane = jnp.asarray(
            rng.integers(0, 1 << 32, (4, n), dtype=np.uint32))
        lane_n = jnp.uint32(n)
        cfg_u = fs.FlowSuiteConfig(fused_hists=False)
        cfg_f = fs.FlowSuiteConfig(fused_hists=True)

        def lanes_step_unfused(s, p, m):
            lanes = {"ip_src": p[0], "ip_dst": p[1],
                     "ports": p[2], "proto_pkts": p[3]}
            mask = jnp.arange(p.shape[1]) < m
            return fs.update(s, fs.unpack_lanes(lanes), mask, cfg_u)

        bench("lanes_step_unfused", f"[4,{n}] staged plane, prod cfg",
              lanes_step_unfused, lambda: fs.init(cfg_u),
              lane_plane, lane_n, rows=n)
        bench("lanes_step_fused_pallas",
              f"[4,{n}] staged plane, prod cfg",
              lambda s, p, m: fs.update_lanes_fused(s, p, m, cfg_f),
              lambda: fs.init(cfg_f), lane_plane, lane_n, rows=n)

    # -- topk admission ----------------------------------------------------
    # populated, NON-donated sketch shared by the ring benches
    query_sketch = jax.jit(cms.update)(cms_init(), keys)
    jax.block_until_ready(query_sketch)
    bench("topk_offer_sampled", f"[{n}] keys, ring 512, 1/16 sample",
          lambda s, k, sk: topk.offer(s, k, sk, sample_log2=4),
          lambda: topk.init(ring_size=512), keys, query_sketch, rows=n)
    bench("topk_offer_full", f"[{n}] keys, ring 512",
          lambda s, k, sk: topk.offer(s, k, sk),
          lambda: topk.init(ring_size=512), keys, query_sketch, rows=n)

    # -- ddsketch ----------------------------------------------------------
    from deepflow_tpu.ops import ddsketch

    dd_cfg = ddsketch.DDSketchConfig()
    rrt = jnp.asarray(rng.integers(1, 1_000_000, n).astype(np.uint32))
    bench("ddsketch_update",
          f"[{n}] values, {dd_cfg.groups}x{dd_cfg.buckets}",
          lambda s, g, v: ddsketch.update(s, g, v, cfg=dd_cfg),
          lambda: ddsketch.init(dd_cfg),
          (groups % np.uint32(dd_cfg.groups)).astype(jnp.int32), rrt,
          rows=n)

    # -- pca ---------------------------------------------------------------
    x = jnp.asarray(rng.normal(size=(min(n, 1 << 17), 12)), jnp.float32)
    bench("pca_update", f"[{x.shape[0]},12] k=3",
          lambda s, xx: pca.update(s, xx), lambda: pca.init(12, 3), x,
          rows=x.shape[0])

    only = args.only.split(",") if args.only else None

    def host_bench(name, shape, fn, rows, iters, bench_backend="host"):
        """Plain-callable timing (warmup once, time `iters`) with the
        same JSON emit as the jitted benches."""
        if only is not None and name not in only:
            return
        fn()
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        dt = time.perf_counter() - t0
        r = {"bench": name, "shape": shape, "backend": bench_backend,
             "ms_per_iter": round(1e3 * dt / iters, 3),
             "rows_per_sec": round(rows * iters / dt)}
        results.append(r)
        print(json.dumps(r), flush=True)

    # -- device GROUP BY vs host group-ids --------------------------------
    from deepflow_tpu.store.rollup import group_reduce

    gcols = {"ip": rng.integers(0, 4096, n).astype(np.uint32),
             "port": rng.integers(0, 64, n).astype(np.uint32),
             "bytes": rng.integers(0, 1500, n).astype(np.uint32)}
    for method in ("host", "device"):
        host_bench(
            f"group_reduce_{method}", f"[{n}] rows, 2 keys",
            lambda m=method: group_reduce(gcols, ["ip", "port"],
                                          {"bytes": "sum"}, method=m),
            rows=n, iters=max(4, args.iters // 4), bench_backend=backend)

    # -- sketch-lane pack (host) ------------------------------------------
    from deepflow_tpu.models import flow_suite

    pcols = {k: rng.integers(0, 2**31, n, dtype=np.uint64).astype(np.uint32)
             for k in ("ip_src", "ip_dst", "port_src", "port_dst",
                       "proto", "packet_tx", "packet_rx")}
    host_bench("pack_lanes", f"[{n}] rows -> 4 planes",
               lambda: flow_suite.pack_lanes(pcols), rows=n,
               iters=args.iters)

    # -- native decoder (host C++, no jit) --------------------------------
    if only is None or "native_decode" in only:
        from deepflow_tpu.decode import native
        from deepflow_tpu.replay.generator import SyntheticAgent
        from deepflow_tpu.wire.codec import pack_pb_records

        if native.available():
            agent = SyntheticAgent()
            nrec = 1 << 16
            cols, records = agent.l4_batch(nrec)
            payload = pack_pb_records(records)
            out32 = np.empty((len(native.L4_COLS32), nrec), np.uint32)
            out64 = np.empty((len(native.L4_COLS64), nrec), np.uint64)
            # MT speedup is bounded by the cores this cgroup actually
            # grants (the build container exposes ONE); report it so a
            # flat mt number on a 1-core box reads as expected, not
            # broken. The pool's correctness is gated by the ci.sh TSAN
            # step at 1-8 threads regardless of core count.
            n_cores = len(os.sched_getaffinity(0))
            for threads in (1, 0):   # 0 = all cores
                native.decode_l4_into(payload, out32, out64,
                                      n_threads=threads)
                t0 = time.perf_counter()
                iters = max(4, args.iters // 2)
                for _ in range(iters):
                    rows, bad, _ = native.decode_l4_into(
                        payload, out32, out64, n_threads=threads)
                dt = time.perf_counter() - t0
                r = {"bench": "native_decode_mt" if threads == 0
                     else "native_decode",
                     "shape": f"[{nrec}] TaggedFlow, "
                     f"{len(native.L4_COLS32) + len(native.L4_COLS64)} cols",
                     "backend": "host",
                     "ms_per_iter": round(1e3 * dt / iters, 3),
                     "rows_per_sec": round(nrec * iters / dt)}
                if threads == 0:
                    r["cores_available"] = n_cores
                results.append(r)
                print(json.dumps(r), flush=True)

    print(json.dumps({"bench": "summary", "backend": backend,
                      "kernels": len(results)}))


if __name__ == "__main__":
    main()
