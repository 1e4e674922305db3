"""The load generator: agents that stream a mix's frames to the ingester,
and the dashboard readers. Runs in a child process that never imports JAX
(the parent holds the chip), on a schedule that does not slow when the
system does.

The parent steers it through `Control`: a phase, the measured window's
start, the origin of the open loop's schedule (set before the window so
that the loop warms up at its own rate), and the ingester's
absorbed-record watermark, which a closed loop uses to keep at most
`in_flight` records outstanding.
"""

from __future__ import annotations

import json
import socket
import threading
import time
import urllib.parse
import urllib.request
from typing import Dict, List

import numpy as np

from harness import reference, traffic

PAUSE, CLOSED, MEASURE, STOP = 0, 1, 2, 3


class Control:
    """State the parent and the generator share (spawn-safe)."""

    def __init__(self, ctx) -> None:
        self.phase = ctx.Value("i", PAUSE, lock=False)
        self.absorbed = ctx.Value("q", 0, lock=False)
        self.sent = ctx.Value("q", 0, lock=False)
        self.t0 = ctx.Value("d", 0.0, lock=False)        # window start
        self.origin = ctx.Value("d", 0.0, lock=False)    # open-loop clock


class Agents:
    """`agents` TCP connections, one vtap id and one sequence each; the
    pool goes out in order, cyclically, frames round-robin."""

    def __init__(self, pool: traffic.Pool, port: int, agents: int) -> None:
        self.pool = pool
        self.socks = [socket.create_connection(("127.0.0.1", port))
                      for _ in range(agents)]
        self.seq = [0] * agents
        self.turn = 0
        self.cursor = 0
        self.sent = 0

    def send(self, count: int) -> int:
        """One frame of up to `count` records; returns how many went."""
        n = min(count, self.pool.per_frame, self.pool.n - self.cursor)
        c = self.turn
        self.turn = (c + 1) % len(self.socks)
        self.seq[c] += 1
        body = self.pool.payload(self.cursor, n)
        sock = self.socks[c]
        sock.sendall(traffic.frame_header(len(body), self.seq[c], c + 1))
        sock.sendall(body)
        self.cursor = (self.cursor + n) % self.pool.n
        self.sent += n
        return n

    def close(self) -> None:
        for s in self.socks:
            s.close()


class Readers:
    """Open-loop dashboard reads: read i is due at t0 + i / rate and
    cycles through `queries`; each is timed from its due time to the
    full HTTP response."""

    def __init__(self, port: int, queries: List[str], rate: float,
                 t0: float, seconds: float, threads: int) -> None:
        self.url = f"http://127.0.0.1:{port}/v1/query"
        self.queries = queries
        self.rate = rate
        self.t0 = t0
        self.end = t0 + seconds
        self.lock = threading.Lock()
        self.next = 0
        self.done: List[dict] = []
        self.threads = [threading.Thread(target=self._run, daemon=True)
                        for _ in range(threads)]
        for t in self.threads:
            t.start()

    def _run(self) -> None:
        while True:
            with self.lock:
                i = self.next
                self.next += 1
            due = self.t0 + i / self.rate
            if due >= self.end:
                return
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            stmt = self.queries[i % len(self.queries)]
            start = time.monotonic()
            rec = {"i": i, "sql": stmt, "due": due, "start": start}
            try:
                body = urllib.parse.urlencode({"sql": stmt}).encode()
                with urllib.request.urlopen(self.url, data=body,
                                            timeout=30) as r:
                    rec["result"] = json.loads(r.read())["result"]
            except Exception as e:          # a failed read is counted
                rec["error"] = repr(e)
            rec["end"] = time.monotonic()
            with self.lock:
                self.done.append(rec)

    def join(self) -> List[dict]:
        for t in self.threads:
            t.join(timeout=60)
        return sorted(self.done, key=lambda r: r["i"])


def read_queries(mix: dict, cols: Dict[str, np.ndarray]) -> List[str]:
    """The dashboard panels: `{heavy_key}` names the pool's heaviest flow."""
    keys = reference.flow_keys(cols)
    uniq, counts = np.unique(keys, return_counts=True)
    heavy = int(uniq[np.argmax(counts)])
    return [q.format(heavy_key=heavy) for q in mix["read_queries"]]


def main(mix: dict, seed: int, seconds: float, ctl: Control, conn) -> None:
    """Builds the pool, waits for ("port", ingester, querier), connects
    the agents, says ("ready", n), then follows the phase until STOP and
    answers ("done", results)."""
    cols = traffic.mix_columns(mix, seed)
    pool = traffic.Pool(cols, mix["records_per_frame"])
    queries = read_queries(mix, cols) if mix.get("reads_per_s") else []
    del cols
    try:
        msg = conn.recv()
    except EOFError:                    # the parent stopped before serving
        return
    if msg[0] != "port":
        return
    _, port, query_port = msg
    agents = Agents(pool, port, int(mix["agents"]))
    conn.send(("ready", pool.n))
    cap = int(mix.get("in_flight", 0))
    open_loop = mix["loop"] == "open"
    rate = float(mix.get("rate", 0.0))
    frames: List[tuple] = []           # (records through it, due, sent at)
    lateness: List[float] = []
    readers = None
    loop_sent = 0                      # open loop, since its origin
    try:
        while True:
            if conn.poll():
                cmd = conn.recv()
                if cmd[0] == "send":            # exactly this many records
                    left = int(cmd[1])
                    while left:
                        left -= agents.send(left)
                    ctl.sent.value = agents.sent
                    conn.send(("sent", agents.sent))
                    continue
            ph = ctl.phase.value
            if ph == STOP:
                break
            if ph == MEASURE and open_loop:
                t0 = ctl.t0.value
                if readers is None and queries and t0:
                    readers = Readers(query_port, queries,
                                      float(mix["reads_per_s"]), t0,
                                      seconds,
                                      int(mix.get("read_threads", 8)))
                n = min(pool.per_frame, pool.n - agents.cursor)
                due = ctl.origin.value + (loop_sent + n) / rate
                if t0 and due > t0 + seconds:
                    time.sleep(0.001)
                    continue
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(min(wait, 0.002))
                    continue
                loop_sent += agents.send(n)
                ctl.sent.value = agents.sent
                if t0 and due >= t0:
                    now = time.monotonic()
                    frames.append((agents.sent, due, now))
                    lateness.append(now - due)
            elif ph in (CLOSED, MEASURE):
                if agents.sent - ctl.absorbed.value + pool.per_frame > cap:
                    time.sleep(0.0005)
                    continue
                agents.send(pool.per_frame)
                ctl.sent.value = agents.sent
            else:
                time.sleep(0.001)
    finally:
        reads = readers.join() if readers is not None else []
        agents.close()
    conn.send(("done", {
        "sent": agents.sent, "frames": frames, "reads": reads,
        "late_p99_ms": float(np.percentile(lateness, 99) * 1e3)
        if lateness else 0.0}))
