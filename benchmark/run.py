#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`benchmark/configs/<name>.json`, the deployment to build) and a traffic
mix (`benchmark/traffic/<name>.json`, the parameters the one generator
reads). Each metric is a reader of its own, `benchmark/metrics/<name>.py`,
found by the metric's name. A cell, a mix or a metric is added by adding
files.

A run builds the system, starts the load generator in a child process
that never imports JAX, warms up every program shape the traffic uses,
then measures for `--seconds`: with `--trace 0` the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics from the program's spans
and counters and a device trace. Afterwards it compares what the run
published with the plain reference (harness/check.py) and prints each
compared number beside its limit, on standard error and under `checks`
in the result, which is the last line of standard output.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 1.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import sender  # noqa: E402

# the dict wire's partial batches at a window close take power-of-two
# plane widths; sending these record counts alone and closing a window
# after each compiles every width before the measured window starts
PARTIAL_SIZES = sorted({100} | {int(2 ** (8 + j / 4)) for j in range(28)})
OPEN_WARM_S = 15.0


def say(name: str, **fields) -> None:
    print(f"{name}: {json.dumps(fields, default=float)}", file=sys.stderr,
          flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> SimpleNamespace:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return SimpleNamespace(
        name=name, chips=int(cell["chips"]),
        config=load_json(os.path.join(ROOT, config["file"])),
        mix=load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def reader(metric: str):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """XLA compiles of this process (a persistent-cache hit counts)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.seconds, self.programs = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += secs
            self.programs += 1


class Observer:
    """The harness's own view of the served path: every published window
    (as the bus delivers it), the absorbed-record watermark over time
    (which also paces a closed loop), and the window-close spans."""

    def __init__(self, served, ctl: sender.Control) -> None:
        from deepflow_tpu.runtime.tracing import default_tracer

        self.sketch = served.sketch
        self.ctl = ctl
        self.tracer = default_tracer()
        self.snaps = []                 # (delivered wall time, snap)
        self.timeline = [(time.monotonic(), self.sketch.rows_in)]
        self.window_spans = {}          # span wall time -> ms
        self._unsubscribe = self.sketch.snapshot_bus.subscribe(self._on_snap)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)
        self._thread.start()

    def _on_snap(self, snap) -> None:
        self.snaps.append((time.time(), snap))

    def _poll(self) -> None:
        last, spans_due = -1, 0.0
        while not self._stop.wait(0.001):
            r = self.sketch.rows_in
            now = time.monotonic()
            if r != last:
                self.timeline.append((now, r))
                self.ctl.absorbed.value = r
                last = r
            if now >= spans_due:
                for sp in self.tracer.recent(n=64, stage="window"):
                    self.window_spans[sp["ts"]] = sp["dur_ms"]
                spans_due = now + 0.5

    def published_rows(self) -> int:
        return sum(int(s.leaves[7]) for _, s in self.snaps)

    def wait(self, pred, timeout: float, what: str) -> None:
        end = time.monotonic() + timeout
        while not pred():
            if time.monotonic() > end:
                raise TimeoutError(f"{what}: rows_in {self.sketch.rows_in}, "
                                   f"sent {self.ctl.sent.value}, published "
                                   f"{self.published_rows()}")
            time.sleep(0.005)

    def drain(self, target, timeout: float, what: str,
              stall: float = 2.0) -> None:
        """Wait until `rows_in` reaches `target()`, or stops moving for
        `stall` seconds (rows the lane lost never arrive; the checks
        count them)."""
        end = time.monotonic() + timeout
        last, since = -1, time.monotonic()
        while self.sketch.rows_in < target():
            now = time.monotonic()
            if self.sketch.rows_in != last:
                last, since = self.sketch.rows_in, now
            elif now - since > stall:
                return
            if now > end:
                raise TimeoutError(f"{what}: rows_in {self.sketch.rows_in}, "
                                   f"sent {self.ctl.sent.value}")
            time.sleep(0.005)

    def stage_sums(self) -> dict:
        return {k: (sk.count, sk.sum) for k, sk in self.tracer.stages().items()}

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
        self._unsubscribe()


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def warm_up(served, obs: Observer, ctl: sender.Control, conn,
            clock: CompileClock, open_loop: bool) -> dict:
    """Closed-loop traffic until three windows closed with records in
    them and no compile in the last 1.5 s; then every partial-batch
    width alone, a window closed after each; then closed-loop traffic
    again for two windows (an open loop then drains before its window
    starts)."""
    t0 = time.monotonic()
    sk = served.sketch
    ctl.phase.value = sender.CLOSED
    obs.wait(lambda: sk.rows_in > 0, 900, "first records")
    w0, quiet_since, last = sk.windows, time.monotonic(), clock.programs
    while True:
        time.sleep(0.1)
        if clock.programs != last:
            last, quiet_since = clock.programs, time.monotonic()
        if sk.windows >= w0 + 3 and time.monotonic() - quiet_since > 1.5:
            break
        if time.monotonic() - t0 > 900:
            raise TimeoutError("warm-up never settled")
    ctl.phase.value = sender.PAUSE
    sent = lambda: ctl.sent.value  # noqa: E731
    obs.drain(sent, 120, "warm-up drain")
    for n in PARTIAL_SIZES:
        conn.send(("send", n))
        msg = conn.recv()
        assert msg[0] == "sent", msg
        obs.drain(sent, 60, "partial batch", stall=0.5)
        served.sketch.flush_window()
    ctl.phase.value = sender.CLOSED
    w = served.sketch.windows
    obs.wait(lambda: served.sketch.windows >= w + 2, 60, "rewarm")
    if open_loop:
        # the open loop's own rate cuts batches where the closed loop
        # does not; it runs OPEN_WARM_S before the window starts
        ctl.phase.value = sender.PAUSE
        obs.drain(sent, 120, "drain before an open loop")
        ctl.origin.value = time.monotonic()
        ctl.phase.value = sender.MEASURE
        time.sleep(OPEN_WARM_S)
    return {"seconds": time.monotonic() - t0, "compile_s": clock.seconds,
            "programs_compiled": clock.programs}


def run(args, require_tpu: bool = True, patch=None):
    """One run; prints its result and returns it, or returns None where
    there is no chip to run on. `patch(served)` breaks the built system
    on purpose (harness/faults.py: the control and the planted faults)."""
    cell = load_cell(args.workload)
    mix = dict(cell.mix)
    ctx = multiprocessing.get_context("spawn")
    ctl = sender.Control(ctx)
    conn, child_conn = ctx.Pipe()
    child = ctx.Process(target=sender.main, name="benchmark-load",
                        args=(mix, args.seed, args.seconds, ctl, child_conn))
    child.start()
    workdir = tempfile.mkdtemp(prefix="deepflow-bench-")
    served = obs = None
    try:
        import jax

        jax.config.update("jax_compilation_cache_dir",
                          os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          or os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        clock = CompileClock()
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        if require_tpu and (device["platform"] != "tpu"
                            or device["count"] < cell.chips):
            say("device", error=f"cell {cell.name} needs {cell.chips} TPU "
                f"chip(s); JAX found {device}")
            return None
        from harness import served as served_mod

        served = served_mod.Served(cell.config, workdir)
        if patch is not None:
            patch(served)
        conn.send(("port", served.port, served.query_port))
        msg = conn.recv()
        assert msg[0] == "ready", msg
        obs = Observer(served, ctl)
        warm = warm_up(served, obs, ctl, conn, clock, mix["loop"] == "open")
        say("warm_up", **warm)

        stages0, rows0, h2d0 = (obs.stage_sums(), served.sketch.rows_in,
                                served.sketch.h2d_bytes)
        programs0 = clock.programs
        trace_dir = os.path.join(workdir, "trace")
        if args.trace:
            jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
        t0, t0_wall = time.monotonic(), time.time()
        setup_s = t0 - T_PROCESS
        if not ctl.origin.value:
            ctl.origin.value = t0
        ctl.t0.value = t0
        ctl.phase.value = sender.MEASURE
        time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
        t1, t1_wall = time.monotonic(), time.time()
        stages1, rows1, h2d1 = (obs.stage_sums(), served.sketch.rows_in,
                                served.sketch.h2d_bytes)
        compiles_in_window = clock.programs - programs0
        ctl.phase.value = sender.STOP
        if args.trace:
            jax.profiler.stop_trace()
        if not conn.poll(120):
            raise TimeoutError("the load generator never reported")
        _, res = conn.recv()
        sent = res["sent"]
        obs.drain(lambda: sent, 120, "final drain")
        try:
            obs.wait(lambda: obs.published_rows() >= sent, 10,
                     "final windows")
        except TimeoutError as e:       # the checks count what is missing
            say("final_windows", error=str(e))
        fallbacks = served.fallbacks()
        device["memory_peak_bytes"] = memory_peak(devices[:cell.chips])
        obs.close()
        served.close()
        served = None
        say("window", seconds=t1 - t0, records_absorbed=rows1 - rows0,
            compiles_in_window=compiles_in_window,
            generator_late_p99_ms=res["late_p99_ms"], records_sent=sent)

        trace = None
        if args.trace:
            from harness import xplane

            trace = xplane.reduce(xplane.find_trace(trace_dir))
            trace["window_s"] = t1 - t0
        snaps = [s for _, s in obs.snaps]
        publishes = [{"delivered": d, "wall_time": s.wall_time,
                      "step": s.step, "rows": int(s.leaves[7])}
                     for d, s in obs.snaps
                     if t0_wall <= s.wall_time <= t1_wall]
        reads = [r for r in res["reads"] if r["due"] < t1]
        observed = SimpleNamespace(
            setup_s=setup_s, seconds=t1 - t0, publishes=publishes,
            frames=res["frames"], timeline=obs.timeline, reads=reads,
            records=rows1 - rows0, h2d_bytes=h2d1 - h2d0,
            stages={k: {"count": v[0] - stages0.get(k, (0, 0.0))[0],
                        "sum_s": v[1] - stages0.get(k, (0, 0.0))[1]}
                    for k, v in stages1.items()},
            window_spans=[ms for ts, ms in obs.window_spans.items()
                          if t0_wall <= ts <= t1_wall],
            trace=trace, device_kind=device["kind"])

        checks = correctness(cell, mix, args.seed, sent, snaps, reads,
                             fallbacks)
        metrics = {}
        for m in (cell.per_layer if args.trace else cell.end_to_end):
            value = reader(m["name"])(observed)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if trace is not None:
            device["busy_s"] = sum(d["busy_s"] for d in trace["devices"]) \
                / max(1, len(trace["devices"]))
            device["window_s"] = trace["window_s"]
        failed_reads = sum(1 for r in reads if "error" in r)
        out = {"correct": all(c["value"] <= c["limit"]
                              for c in checks.values()),
               "attempted": sent + len(reads),
               "failed": max(0, sent - obs.published_rows()) + failed_reads,
               "metrics": metrics, "device": device}
        if trace is not None:
            out["breakdown"] = {"device_ops": trace["device_ops"],
                                "idle_gaps": trace["idle_gaps"]}
        out["checks"] = checks
        for name, c in checks.items():
            print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
                  file=sys.stderr, flush=True)
        print(json.dumps(out), flush=True)
        return out
    finally:
        ctl.phase.value = sender.STOP
        conn.close()
        if obs is not None:
            obs.close()
        if served is not None:
            served.close()
        child.join(timeout=30)
        if child.is_alive():
            child.terminate()
            child.join(timeout=10)
        shutil.rmtree(workdir, ignore_errors=True)


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def correctness(cell, mix: dict, seed: int, sent: int, snaps, reads,
                fallbacks: dict) -> dict:
    from harness import check, reference, traffic

    cols = traffic.mix_columns(mix, seed)
    ref = reference.Reference(cols, sent, cell.config["sketch"]["hll_groups"])
    checks = check.compare(snaps, ref, cell.config["sketch"], seed, fallbacks)
    if mix.get("reads_per_s"):
        checks["read_mismatch"] = {
            "value": float(check.read_mismatches(reads, snaps)), "limit": 0.0}
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return 0 if run(ap.parse_args(argv)) is not None else 1


if __name__ == "__main__":
    sys.exit(main())
