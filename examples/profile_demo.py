"""Continuous-profiling demo: sample a real CPU burner end to end.

Drives the whole OnCPU loop on live perf events (no fixtures):
compile a C burner with a known hot function -> sample it with
agent/profiler.py (per-task perf_event_open, /proc+ELF symbolization)
-> ship folded stacks as Profile records over the firehose -> ingester
profile pipeline -> querier flame graph, and print the flame with the
burner's function dominating.

    JAX_PLATFORMS=cpu PYTHONPATH=. \
        python examples/profile_demo.py
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import tempfile
import time

BURNER_C = r"""
#include <stdint.h>
#include <stdio.h>
volatile uint64_t sink;
__attribute__((noinline)) uint64_t burn_cycles(uint64_t n) {
    uint64_t acc = 1;
    for (uint64_t i = 0; i < n; i++)
        acc = acc * 2862933555777941757ULL + 3037000493ULL;
    return acc;
}
int main(void) {
    fprintf(stderr, "ready\n");
    for (;;) sink += burn_cycles((1 << 20) + (sink & 1));
    return 0;
}
"""


def main() -> int:
    from deepflow_tpu.agent import profiler
    from deepflow_tpu.agent.profiler import (OnCpuProfiler,
                                             folded_to_profile_records)
    from deepflow_tpu.pipelines import Ingester, IngesterConfig
    from deepflow_tpu.querier.profile import ProfileQuery
    from deepflow_tpu.wire.codec import pack_pb_records
    from deepflow_tpu.wire.framing import (FlowHeader, MessageType,
                                           encode_frame)

    if not profiler.available():
        print("perf_event_open unsupported on this platform")
        return 2

    with tempfile.TemporaryDirectory() as d:
        src = os.path.join(d, "burner.c")
        exe = os.path.join(d, "burner")
        with open(src, "w") as f:
            f.write(BURNER_C)
        subprocess.run(["gcc", "-O1", "-fno-omit-frame-pointer",
                        "-no-pie", "-o", exe, src], check=True)
        burner = subprocess.Popen([exe], stderr=subprocess.PIPE)
        burner.stderr.readline()
        try:
            print("sampling burner pid", burner.pid, "at 199Hz for 1s…")
            prof = OnCpuProfiler(burner.pid, freq_hz=199)
            try:
                folded = prof.run(1.0)
            finally:
                prof.close()
        finally:
            burner.kill()
            burner.wait()

    total = sum(folded.values())
    print(f"captured {total} samples, {len(folded)} distinct stacks")
    records = folded_to_profile_records(folded, app_service="burner",
                                        pid=0, vtap_id=1)

    ing = Ingester(IngesterConfig(listen_port=0,
                                  store_path=os.path.join(
                                      tempfile.mkdtemp(), "store")))
    ing.start()
    try:
        frame = encode_frame(MessageType.PROFILE,
                             pack_pb_records(records),
                             FlowHeader(sequence=1, vtap_id=1))
        with socket.create_connection(("127.0.0.1", ing.port),
                                      timeout=5) as s:
            s.sendall(frame)
        deadline = time.time() + 10
        while time.time() < deadline and ing.profile.profiles < len(
                records):
            time.sleep(0.05)
        ing.flush()
        flame = ProfileQuery(ing.store, ing.tag_dicts).flame(
            app_service="burner", event_type="on-cpu")

        def render(node, depth=0):
            pct = 100.0 * node["total_value"] / max(
                flame["total_value"], 1)
            print(f"  {'  ' * depth}{node['name']:<28} "
                  f"{node['total_value']:>6}  {pct:5.1f}%")
            for c in node["children"]:
                render(c, depth + 1)

        print("\nflame graph (samples, % of total):")
        render(flame)
        hot = sum(v for k, v in folded.items() if "burn_cycles" in k)
        ok = total > 0 and hot / total >= 0.5
        print(f"\nburn_cycles share: {100.0 * hot / max(total, 1):.1f}%"
              f"  ->  {'demo OK' if ok else 'UNEXPECTED: not dominant'}")
        return 0 if ok else 1
    finally:
        ing.close()


if __name__ == "__main__":
    sys.exit(main())
