"""Encrypted-traffic tracing demo: LIVE kernel uprobes end to end.

Drives the whole TLS-visibility story with no fixtures anywhere:
compile a stand-in libssl + a client binary that makes "TLS" calls ->
the agent attaches the in-tree SSL uprobe programs (verifier-loaded,
uprobe PMU) -> the kernel captures the plaintext at the SSL boundary
and runs the trace-id discipline in-program -> records stream through
the perf rings into the EbpfTracer -> merged l7 records ship to the
ingester -> a SQL query returns the decrypted endpoints flagged
is_tls=1.

    JAX_PLATFORMS=cpu PYTHONPATH=. \
        python examples/tls_uprobe_demo.py

Requires the uprobe PMU (/sys/bus/event_source/devices/uprobe) — the
demo prints the capability probe and exits 0 with a notice where it's
masked (the replay path remains; see tests/test_uprobe_trace.py).
"""

from __future__ import annotations

import shutil
import subprocess
import sys
import tempfile
import time

FAKESSL_C = r"""
int SSL_read(void *s, void *b, int n) { return n > 0 ? n : -1; }
int SSL_write(void *s, const void *b, int n) { return n; }
"""

CLIENT_C = r"""
#include <string.h>
#include <unistd.h>
extern int SSL_write(void*, const void*, int);
extern int SSL_read(void*, void*, int);
int main(void) {
    char req1[] = "GET /api/accounts/42 HTTP/1.1\r\nHost: bank.internal\r\n"
                  "traceparent: 00-feedfacefeedfacefeedfacefeedface-aaaa"
                  "bbbbccccdddd-01\r\nContent-Length: 0\r\n\r\n";
    char resp1[] = "HTTP/1.1 200 OK\r\nContent-Length: 2\r\n\r\nok";
    char req2[] = "POST /api/transfer HTTP/1.1\r\nHost: bank.internal\r\n"
                  "Content-Length: 0\r\n\r\n";
    char resp2[] = "HTTP/1.1 403 Forbidden\r\nContent-Length: 0\r\n\r\n";
    for (int i = 0; i < 3; i++) {
        SSL_write((void*)0, req1, (int)strlen(req1));
        SSL_read((void*)0, resp1, (int)strlen(resp1));
        SSL_write((void*)0, req2, (int)strlen(req2));
        SSL_read((void*)0, resp2, (int)strlen(resp2));
        usleep(5000);
    }
    return 0;
}
"""


def main() -> int:
    from deepflow_tpu.agent import bpf, uprobe_trace
    from deepflow_tpu.agent.trident import Agent, AgentConfig
    from deepflow_tpu.pipelines import Ingester, IngesterConfig
    from deepflow_tpu.querier.engine import QueryEngine

    ok, why = uprobe_trace.attach_available()
    print(f"bpf(2): {bpf.available()}   uprobe attach: {ok} ({why})")
    if not bpf.available() or not ok:
        print("uprobe attach masked here - the kernel datapath needs "
              "the uprobe PMU; replay tests still cover the suite.")
        return 0
    cc = shutil.which("gcc") or shutil.which("cc")
    if cc is None:
        print("no C toolchain; skipping")
        return 0

    with tempfile.TemporaryDirectory() as d:
        so, drv = f"{d}/libfakessl.so", f"{d}/client"
        open(f"{d}/ssl.c", "w").write(FAKESSL_C)
        open(f"{d}/client.c", "w").write(CLIENT_C)
        subprocess.run([cc, "-O2", "-shared", "-fPIC", f"{d}/ssl.c",
                        "-o", so], check=True)
        subprocess.run([cc, "-O2", f"{d}/client.c", f"-L{d}",
                        "-lfakessl", "-o", drv, f"-Wl,-rpath,{d}"],
                       check=True)

        ing = Ingester(IngesterConfig(listen_port=0,
                                      store_path=f"{d}/store"))
        ing.start()
        agent = Agent(AgentConfig(
            ingester_addr=f"127.0.0.1:{ing.port}", l7_enabled=True))
        agent.vtap_id = 1
        try:
            got = agent.enable_tls_uprobes(paths=[so])
            print(f"attached: {got['probes_attached']} probes on "
                  f"{so.split('/')[-1]}")
            tset = shutil.which("taskset")
            cmd = [tset, "-c", "0", drv] if tset else [drv]
            subprocess.run(cmd, check=True)
            time.sleep(0.3)
            sent = agent.tick()
            print(f"agent tick shipped l7={sent['l7']} records "
                  f"(pumped {agent.tls_uprobes.records_pumped} "
                  "kernel records)")
            table = ing.store.table("flow_log", "l7_flow_log")
            deadline = time.time() + 10
            while time.time() < deadline:
                ing.flush()
                if table.row_count() >= 2:
                    break
                time.sleep(0.1)
            r = QueryEngine(ing.store).execute(
                "SELECT endpoint_hash, status, is_tls "
                "FROM l7_flow_log WHERE is_tls = 1", db="flow_log")
            print("\ndecrypted l7 rows (SQL, WHERE is_tls = 1):")
            for ep, st, tls in sorted(set(map(tuple, r.values))):
                print(f"  endpoint_hash={int(ep):>10}  "
                      f"status={int(st)}  is_tls={int(tls)}")
            assert len(r.values) >= 2, r.values
            assert {v[1] for v in r.values} == {200, 403}
            tracer = agent.ebpf_tracer
            print(f"\ntrace ids chained in kernel: "
                  f"{tracer.counters()['records_in']} records in, "
                  "sessions merged with syscall trace ids")
        finally:
            agent.close()
            ing.close()
    print("\ndemo OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
