"""deepflow-lint (deepflow_tpu/analysis/): per-rule positive / negative /
pragma fixtures, the baseline machinery, the CLI gate, and the repo
self-scan that keeps the shipped tree at zero non-baselined findings."""

import json
from collections import Counter
from pathlib import Path

import pytest

from deepflow_tpu import analysis
from deepflow_tpu.cli import main as cli_main

REPO_ROOT = Path(__file__).resolve().parent.parent


def rules_of(findings):
    return [f.rule for f in findings]


# ------------------------------------------------- unsupervised-thread

THREAD_SRC = "import threading\nt = threading.Thread(target=print)\n"


def test_unsupervised_thread_positive():
    fs = analysis.run_on_sources({"pkg/mod.py": THREAD_SRC})
    assert rules_of(fs) == ["unsupervised-thread"]
    assert "Supervisor.spawn" in fs[0].message


def test_unsupervised_thread_catches_import_aliases():
    src = "from threading import Thread as T\nt = T(target=print)\n"
    assert rules_of(analysis.run_on_sources({"m.py": src})) \
        == ["unsupervised-thread"]
    # module-alias spelling must not bypass the gate
    src = "import threading as th\nt = th.Thread(target=print)\n"
    assert rules_of(analysis.run_on_sources({"m.py": src})) \
        == ["unsupervised-thread"]


def test_unsupervised_thread_negative_in_supervisor_and_pragma():
    assert analysis.run_on_sources({
        # the one sanctioned construction site
        "runtime/supervisor.py": THREAD_SRC,
        "pkg/ok.py": ("import threading\nt = threading.Thread(target=print)"
                      "  # lint: disable=unsupervised-thread\n"),
    }) == []


# ----------------------------------------------------- emit-under-lock

LOCKED_EMIT = """\
import threading
class Q:
    def __init__(self):
        self._lock = threading.Lock()
    def go(self, sink, x):
        with self._lock:
            sink.emit(x)
"""

CONDVAR_EMIT = """\
import threading
class Q:
    def __init__(self):
        self._ready = threading.Condition(threading.Lock())
    def go(self, sink, x):
        with self._ready:
            sink.put(x)
"""

SWAP_UNDER_LOCK = """\
import threading
class Q:
    def __init__(self):
        self._lock = threading.Lock()
        self._batch = []
    def go(self, sink, x):
        with self._lock:
            self._batch.append(x)
            batch, self._batch = self._batch, []
        sink.send(batch)
"""


def test_emit_under_lock_positive_lock_and_condition():
    assert rules_of(analysis.run_on_sources({"a.py": LOCKED_EMIT})) \
        == ["emit-under-lock"]
    # `with self._ready:` where _ready = threading.Condition(...)
    assert rules_of(analysis.run_on_sources({"b.py": CONDVAR_EMIT})) \
        == ["emit-under-lock"]


def test_emit_under_lock_positive_locked_suffix_function():
    src = ("class S:\n"
           "    def _flush_locked(self, sink):\n"
           "        sink.send(self._batch)\n")
    fs = analysis.run_on_sources({"s.py": src})
    assert rules_of(fs) == ["emit-under-lock"]
    assert "_flush_locked" in fs[0].message


def test_emit_under_lock_negative_swap_pattern_and_pragma():
    assert analysis.run_on_sources({"a.py": SWAP_UNDER_LOCK}) == []
    suppressed = LOCKED_EMIT.replace(
        "sink.emit(x)", "sink.emit(x)  # lint: disable=emit-under-lock")
    assert analysis.run_on_sources({"a.py": suppressed}) == []


def test_emit_under_lock_ignores_nested_defs_under_lock():
    # defining a closure under the lock is not emitting under the lock
    src = ("import threading\n"
           "class Q:\n"
           "    def go(self, sink):\n"
           "        with self._lock:\n"
           "            def later():\n"
           "                sink.send(1)\n"
           "            self._cb = later\n")
    assert analysis.run_on_sources({"a.py": src}) == []


# -------------------------------------------- host-sync-in-device-path

DEVICE_SYNC = """\
import jax
class E:
    def process(self, x):
        x.block_until_ready()
        return jax.device_get(x)
"""


def test_host_sync_positive_in_device_path_files():
    for path in ("runtime/tpu_sketch.py", "runtime/app_red.py",
                 "parallel/sharded.py"):
        fs = analysis.run_on_sources({path: DEVICE_SYNC})
        assert rules_of(fs) == ["host-sync-in-device-path"] * 2, path


def test_host_sync_negative_outside_device_path_and_in_helpers():
    # other modules may sync freely (checkpointing does, by design)
    assert analysis.run_on_sources({"runtime/checkpoint.py": DEVICE_SYNC}) \
        == []
    sanctioned = DEVICE_SYNC.replace("def process", "def _to_device")
    assert analysis.run_on_sources(
        {"runtime/tpu_sketch.py": sanctioned}) == []


def test_host_sync_device_state_materialization():
    src = ("import numpy as np\n"
           "class E:\n"
           "    def process(self, tb):\n"
           "        return np.asarray(self.state)\n"
           "    def host_side(self, cols):\n"
           "        return np.asarray(cols['ip_src'])\n")
    fs = analysis.run_on_sources({"runtime/tpu_sketch.py": src})
    # the state fetch is flagged; plain host-array asarray is not
    assert rules_of(fs) == ["host-sync-in-device-path"]
    assert "device state" in fs[0].message and fs[0].line == 4


def test_host_sync_item_call():
    src = ("class E:\n"
           "    def process(self, x):\n"
           "        return x.sum().item()\n")
    fs = analysis.run_on_sources({"runtime/app_red.py": src})
    assert rules_of(fs) == ["host-sync-in-device-path"]


# -------------------------------------------------- trace-unsafe-jit

def test_trace_unsafe_jit_positive_named_function():
    src = ("import time, jax\n"
           "def step(x):\n"
           "    return x * time.time()\n"
           "f = jax.jit(step)\n")
    fs = analysis.run_on_sources({"ops/m.py": src})
    assert rules_of(fs) == ["trace-unsafe-jit"]
    assert "time.time" in fs[0].message


def test_trace_unsafe_jit_positive_lambda_and_decorator():
    lam = ("import jax, numpy as np\n"
           "f = jax.jit(lambda x: np.asarray(x))\n")
    assert rules_of(analysis.run_on_sources({"a.py": lam})) \
        == ["trace-unsafe-jit"]
    dec = ("import functools, jax, random\n"
           "@functools.partial(jax.jit, static_argnames=())\n"
           "def step(x):\n"
           "    return x + random.random()\n")
    assert rules_of(analysis.run_on_sources({"b.py": dec})) \
        == ["trace-unsafe-jit"]


def test_trace_unsafe_jit_negative_unjitted_static_np_and_pragma():
    # host effects in NEVER-jitted code are someone else's business
    src = "import time\ndef step(x):\n    return x * time.time()\n"
    assert analysis.run_on_sources({"a.py": src}) == []
    # dtype constructors are compile-time static, not hazards
    ok = ("import jax, numpy as np\n"
          "f = jax.jit(lambda x: x.astype(np.float32))\n")
    assert analysis.run_on_sources({"b.py": ok}) == []
    suppressed = ("import time, jax\n"
                  "def step(x):\n"
                  "    return x * time.time()  # lint: disable=trace-unsafe-jit\n"
                  "f = jax.jit(step)\n")
    assert analysis.run_on_sources({"c.py": suppressed}) == []


def test_trace_unsafe_jit_follows_module_local_helpers():
    src = ("import time, jax\n"
           "def helper(x):\n"
           "    return x * time.time()\n"
           "@jax.jit\n"
           "def step(x):\n"
           "    return helper(x)\n")
    fs = analysis.run_on_sources({"a.py": src})
    assert rules_of(fs) == ["trace-unsafe-jit"]
    assert "via helper()" in fs[0].message
    # self.<method> helpers too, with cycle tolerance
    src2 = ("import time, jax\n"
            "class M:\n"
            "    def _helper(self, x):\n"
            "        return self._helper(x) + time.time()\n"
            "    def build(self):\n"
            "        return jax.jit(lambda x: self._helper(x))\n")
    assert rules_of(analysis.run_on_sources({"b.py": src2})) \
        == ["trace-unsafe-jit"]


def test_trace_unsafe_jit_shard_map():
    src = ("from jax.experimental.shard_map import shard_map\n"
           "def body(x):\n"
           "    print(x)\n"
           "    return x\n"
           "f = shard_map(body, mesh=None, in_specs=(), out_specs=())\n")
    fs = analysis.run_on_sources({"parallel/m.py": src})
    assert "trace-unsafe-jit" in rules_of(fs)


# ------------------------------------- countable-missing-counters

def test_countable_missing_counters_positive_self():
    src = ("class P:\n"
           "    def __init__(self, stats):\n"
           "        stats.register('p', self.counters)\n")
    fs = analysis.run_on_sources({"a.py": src})
    assert rules_of(fs) == ["countable-missing-counters"]


def test_countable_missing_counters_positive_member_object():
    src = ("class Sink:\n"
           "    pass\n"
           "class P:\n"
           "    def __init__(self, stats):\n"
           "        self.sink = Sink()\n"
           "        stats.register('p', self.sink.counters)\n")
    fs = analysis.run_on_sources({"a.py": src})
    assert rules_of(fs) == ["countable-missing-counters"]
    assert "'Sink'" in fs[0].message


def test_countable_missing_counters_negative_inherited_and_external():
    inherited = ("class Base:\n"
                 "    def counters(self):\n"
                 "        return {}\n"
                 "class P(Base):\n"
                 "    def __init__(self, stats):\n"
                 "        stats.register('p', self.counters)\n")
    assert analysis.run_on_sources({"a.py": inherited}) == []
    # an unresolvable (external) base: absence is NOT proven -> silent
    external = ("from somewhere import Base\n"
                "class P(Base):\n"
                "    def __init__(self, stats):\n"
                "        stats.register('p', self.counters)\n")
    assert analysis.run_on_sources({"b.py": external}) == []


def test_countable_missing_counters_cross_file_base():
    files = {
        "base.py": "class Base:\n    def counters(self):\n        return {}\n",
        "sub.py": ("class Sub(Base):\n"
                   "    def __init__(self, stats):\n"
                   "        stats.register('s', self.counters)\n"),
    }
    assert analysis.run_on_sources(files) == []


def test_countable_missing_counters_import_aware():
    # an IMPORTED repo-local base resolves through the import's module
    resolved = {
        "pkg/base.py": ("class Base:\n"
                        "    def counters(self):\n"
                        "        return {}\n"),
        "pkg/sub.py": ("from pkg.base import Base\n"
                       "class Sub(Base):\n"
                       "    def __init__(self, stats):\n"
                       "        stats.register('s', self.counters)\n"),
    }
    assert analysis.run_on_sources(resolved) == []
    # a homonym class elsewhere in the repo must NOT stand in for an
    # EXTERNAL import of the same name (would be a false 'proven
    # absence' — the external Base may well define counters)
    homonym = {
        "pkg/base.py": "class Base:\n    pass\n",
        "pkg/sub.py": ("from external_lib import Base\n"
                       "class Sub(Base):\n"
                       "    def __init__(self, stats):\n"
                       "        stats.register('s', self.counters)\n"),
    }
    assert analysis.run_on_sources(homonym) == []


# ------------------------------------------------- fault-site-drift

FAULTS_SRC = ('FAULT_USED = "queue.stall"\n'
              'FAULT_ORPHAN = "ghost.site"\n')


def test_fault_site_drift_orphan_and_unknown():
    fs = analysis.run_on_sources({
        "runtime/faults.py": FAULTS_SRC,
        "runtime/queues.py": ("from deepflow_tpu.runtime.faults import "
                              "FAULT_USED, FAULT_MISSING\n"
                              "def f(r):\n"
                              "    r.maybe_stall(FAULT_USED)\n"
                              "    r.maybe_stall(FAULT_MISSING)\n"),
    })
    assert sorted(rules_of(fs)) == ["fault-site-drift", "fault-site-drift"]
    msgs = " | ".join(f.message for f in fs)
    assert "ghost.site" in msgs and "FAULT_MISSING" in msgs
    assert "FAULT_USED" not in msgs


def test_fault_site_drift_spec_string_counts_as_reference():
    # arming via a spec/site string is a live injection point too
    fs = analysis.run_on_sources({
        "runtime/faults.py": 'FAULT_X = "exporter.raise"\n',
        "chaos.py": 'SPEC = "exporter.raise"\n',
    })
    assert fs == []


def test_fault_site_drift_silent_without_faults_file():
    # partial scans (faults.py out of scope) must not cry drift
    src = "from deepflow_tpu.runtime.faults import FAULT_USED\nx = FAULT_USED\n"
    assert analysis.run_on_sources({"runtime/queues.py": src}) == []


# ---------------------------------------------------- lock-order-cycle

TWO_LOCK_CYCLE = {
    # the seeded deadlock: A.m1 holds _la then asks B for _lb, while
    # B.m3 holds _lb then asks A for _la — classic inversion
    "runtime/locks_a.py": (
        "import threading\n"
        "from runtime.locks_b import B\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._la = threading.Lock()\n"
        "        self.b = B()\n"
        "    def m1(self):\n"
        "        with self._la:\n"
        "            self.b.m2()\n"
        "    def m4(self):\n"
        "        with self._la:\n"
        "            pass\n"),
    "runtime/locks_b.py": (
        "import threading\n"
        "from runtime.locks_a import A\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        self._lb = threading.Lock()\n"
        "        self.a = A()\n"
        "    def m2(self):\n"
        "        with self._lb:\n"
        "            pass\n"
        "    def m3(self):\n"
        "        with self._lb:\n"
        "            self.a.m4()\n"),
}


def test_lock_order_cycle_two_lock_fixture():
    fs = analysis.run_on_sources(TWO_LOCK_CYCLE)
    assert rules_of(fs) == ["lock-order-cycle"]
    # ONE finding per cycle, naming the full ring deterministically
    assert "A._la -> B._lb -> A._la" in fs[0].message


def test_lock_order_cycle_negative_consistent_order():
    # same two locks, both paths acquire A-then-B: acyclic, silent
    ok = {k: v.replace("self.a.m4()", "pass") for k, v in
          TWO_LOCK_CYCLE.items()}
    assert analysis.run_on_sources(ok) == []


def test_lock_order_cycle_self_deadlock_through_helper():
    src = ("import threading\n"
           "class Q:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "    def put(self, x):\n"
           "        with self._lock:\n"
           "            self._flush()\n"
           "    def _flush(self):\n"
           "        with self._lock:\n"
           "            pass\n")
    fs = analysis.run_on_sources({"runtime/q.py": src})
    assert rules_of(fs) == ["lock-order-cycle"]
    assert "non-reentrant" in fs[0].message
    # the same nesting through an RLock is legal — silent
    assert analysis.run_on_sources(
        {"runtime/q.py": src.replace("threading.Lock()",
                                     "threading.RLock()")}) == []


def test_lock_order_cycle_self_deadlock_through_member_chain():
    # A.m1 holds _la -> b.m2 -> a.m4 re-acquires _la: deadlock with no
    # second thread, reported even though it crosses two member calls
    src = ("import threading\n"
           "class A:\n"
           "    def __init__(self):\n"
           "        self._la = threading.Lock()\n"
           "        self.b = B()\n"
           "    def m1(self):\n"
           "        with self._la:\n"
           "            self.b.m2()\n"
           "    def m4(self):\n"
           "        with self._la:\n"
           "            pass\n"
           "class B:\n"
           "    def __init__(self):\n"
           "        self.a = A()\n"
           "    def m2(self):\n"
           "        self.a.m4()\n")
    fs = analysis.run_on_sources({"runtime/chain.py": src})
    assert rules_of(fs) == ["lock-order-cycle"]
    assert "non-reentrant" in fs[0].message


def test_lock_order_cycle_pragma_and_scope():
    # pragma on the anchor line silences the cycle
    pragmad = dict(TWO_LOCK_CYCLE)
    pragmad["runtime/locks_a.py"] = pragmad["runtime/locks_a.py"].replace(
        "            self.b.m2()",
        "            self.b.m2()  # lint: disable=lock-order-cycle")
    assert analysis.run_on_sources(pragmad) == []
    # outside the concurrency core (agent/) the rule stays out
    moved = {k.replace("runtime/", "agent/"):
             v.replace("runtime.", "agent.")
             for k, v in TWO_LOCK_CYCLE.items()}
    assert analysis.run_on_sources(moved) == []


# ------------------------------------------------ unlocked-shared-write

SHARED_WRITE = (
    "import threading\n"
    "class W:\n"
    "    def __init__(self, sup):\n"
    "        self._lock = threading.Lock()\n"
    "        self._buf = []\n"
    "        sup.spawn('w', self._run)\n"
    "    def put(self, frame):\n"
    "        with self._lock:\n"
    "            self._buf.append(frame)\n"
    "    def _run(self):\n"
    "        self._buf = []\n")


def test_unlocked_shared_write_positive():
    fs = analysis.run_on_sources({"runtime/w.py": SHARED_WRITE})
    assert rules_of(fs) == ["unlocked-shared-write"]
    assert "_buf" in fs[0].message and "_run" in fs[0].message


def test_unlocked_shared_write_negatives():
    # both writes under the lock: silent
    locked = SHARED_WRITE.replace(
        "    def _run(self):\n        self._buf = []\n",
        "    def _run(self):\n        with self._lock:\n"
        "            self._buf = []\n")
    assert analysis.run_on_sources({"runtime/w.py": locked}) == []
    # a *_locked helper carries the caller-holds-the-lock promise
    suffixed = SHARED_WRITE.replace(
        "    def _run(self):\n        self._buf = []\n",
        "    def _run(self):\n        self._clear_locked()\n"
        "    def _clear_locked(self):\n        self._buf = []\n")
    assert analysis.run_on_sources({"runtime/w.py": suffixed}) == []
    # a deliberately lock-free counter (no locked write anywhere) is
    # not this rule's business
    lockfree = SHARED_WRITE.replace("        with self._lock:\n"
                                    "            self._buf.append(frame)\n",
                                    "        self._buf.append(frame)\n")
    assert analysis.run_on_sources({"runtime/w.py": lockfree}) == []
    # __init__ writes are construction, not a race: the one finding in
    # the positive fixture indicts _run, never the constructor
    fs = analysis.run_on_sources({"runtime/w.py": SHARED_WRITE})
    assert len(fs) == 1 and "W._run()" in fs[0].message


def test_unlocked_shared_write_single_entry_and_pragma():
    # only ONE thread root: nothing shared, silent
    single = SHARED_WRITE.replace("sup.spawn('w', self._run)\n", "pass\n")
    assert analysis.run_on_sources({"runtime/w.py": single}) == []
    pragmad = SHARED_WRITE.replace(
        "        self._buf = []\n",
        "        self._buf = []  # lint: disable=unlocked-shared-write\n")
    assert analysis.run_on_sources({"runtime/w.py": pragmad}) == []


def test_unlocked_shared_write_callback_entry():
    # a method handed out as a ctor callback is a thread root too
    src = ("import threading\n"
           "class W:\n"
           "    def __init__(self, feed_cls):\n"
           "        self._lock = threading.Lock()\n"
           "        self._n = 0\n"
           "        self._feed = feed_cls(on_error=self._on_error)\n"
           "    def put(self, frame):\n"
           "        with self._lock:\n"
           "            self._n += 1\n"
           "    def _on_error(self, exc):\n"
           "        self._n = 0\n")
    fs = analysis.run_on_sources({"runtime/cb.py": src})
    assert rules_of(fs) == ["unlocked-shared-write"]


# ------------------------------------------------------- silent-drop

def test_silent_drop_except_swallow():
    src = ("class D:\n"
           "    def feed(self, frames):\n"
           "        for frame in frames:\n"
           "            try:\n"
           "                frame.decode()\n"
           "            except Exception:\n"
           "                continue\n")
    fs = analysis.run_on_sources({"runtime/d.py": src})
    assert rules_of(fs) == ["silent-drop"]
    assert "frame" in fs[0].message
    # counting the loss in the handler satisfies the ledger
    counted = src.replace("                continue\n",
                          "                self.decode_errors += 1\n")
    assert analysis.run_on_sources({"runtime/d.py": counted}) == []
    # ... and so does following a same-file helper that counts
    helper = src.replace(
        "                continue\n",
        "                self._on_error()\n") + (
        "    def _on_error(self):\n"
        "        self.decode_errors += 1\n")
    assert analysis.run_on_sources({"runtime/d.py": helper}) == []


def test_silent_drop_continue_and_guarded_return():
    src = ("class D:\n"
           "    def scan(self, batches):\n"
           "        for batch in batches:\n"
           "            if batch.stale:\n"
           "                continue\n"
           "            self._emit(batch)\n"
           "    def put(self, batch):\n"
           "        if self._closed:\n"
           "            return\n"
           "        self._emit(batch)\n")
    fs = analysis.run_on_sources({"runtime/d.py": src})
    assert rules_of(fs) == ["silent-drop", "silent-drop"]
    # emptiness guards abandon nothing
    ok = ("class D:\n"
          "    def put(self, batch):\n"
          "        if not batch:\n"
          "            return\n"
          "        self._emit(batch)\n")
    assert analysis.run_on_sources({"runtime/d.py": ok}) == []
    # counting before the guard covers the early return
    pre = ("class D:\n"
           "    def absorb(self, rows):\n"
           "        self.lost_rows += rows\n"
           "        if self.degraded:\n"
           "            return\n"
           "        self._restore()\n")
    assert analysis.run_on_sources({"runtime/d.py": pre}) == []


def test_silent_drop_empty_skip_continue_stays_silent():
    # `if not frame: continue` skips NOTHING — same emptiness-guard
    # exemption the return shape has
    src = ("class D:\n"
           "    def feed(self, frames):\n"
           "        for frame in frames:\n"
           "            if not frame:\n"
           "                continue\n"
           "            self._emit(frame)\n")
    assert analysis.run_on_sources({"runtime/d.py": src}) == []
    src2 = src.replace("if not frame:", "if frame is None:")
    assert analysis.run_on_sources({"runtime/d.py": src2}) == []


def test_silent_drop_retry_idioms_stay_silent():
    # recv-retry: the noun was only ever an assignment target — no
    # data existed when the call raised
    recv = ("class R:\n"
            "    def _loop(self):\n"
            "        while True:\n"
            "            try:\n"
            "                chunk = self.sock.recv(65536)\n"
            "            except OSError:\n"
            "                return\n"
            "            self._dispatch(chunk)\n")
    assert analysis.run_on_sources({"runtime/r.py": recv}) == []
    # backpressure wait-and-continue consumes nothing
    bp = ("class S:\n"
          "    def _drain(self):\n"
          "        while True:\n"
          "            blobs = self.store.take()\n"
          "            if self.queue.full():\n"
          "                self._stop.wait(0.05)\n"
          "                continue\n"
          "            self.queue.reinject(blobs)\n")
    assert analysis.run_on_sources({"runtime/s.py": bp}) == []


def test_silent_drop_pragma_and_scope():
    src = ("class D:\n"
           "    def put(self, batch):\n"
           "        if self._closed:\n"
           "            return  # lint: disable=silent-drop\n"
           "        self._emit(batch)\n")
    assert analysis.run_on_sources({"runtime/d.py": src}) == []
    # telemetry modules are exempt: dropping a span is not row loss
    span = ("class T:\n"
            "    def observe(self, rows):\n"
            "        if self._off:\n"
            "            return\n"
            "        self._emit(rows)\n")
    assert analysis.run_on_sources({"runtime/tracing.py": span}) == []
    assert analysis.run_on_sources({"runtime/t.py": span}) != []


# -------------------------------------------------------- twin-drift

TWIN_SRCS = {
    "pkg/analysis/twins.py": (
        'TWIN_TABLE = [\n'
        '    ("host-sketch", "pkg/host.py:HostSketch", "pkg/dev.py:mix"),\n'
        ']\n'),
    "pkg/host.py": ("class HostSketch:\n"
                    "    def absorb(self, x):\n"
                    "        return x * 3\n"),
    "pkg/dev.py": ("def mix(x):\n"
                   "    return x * 3\n"),
    "pkg/marked.py": (
        "from deepflow_tpu.analysis.twins import host_twin_of\n"
        "@host_twin_of('pkg/dev.py:mix')\n"
        "def mix_np(x):\n"
        "    return x * 3\n"),
}


def _twin_store_for(srcs):
    from deepflow_tpu.analysis import core as ana_core
    from deepflow_tpu.analysis import twins as ana_twins
    _ctxs, index, _errs = ana_core.build_index(sorted(srcs.items()))
    store, missing = ana_twins.build_store(index)
    assert missing == []
    return store


def test_twin_drift_unacked_edit_trips_both_decl_kinds():
    store = _twin_store_for(TWIN_SRCS)
    # acked store + unchanged tree: clean
    assert analysis.run_on_sources(TWIN_SRCS, twin_store=store) == []
    # editing the shared DEVICE side without re-ack trips BOTH the
    # table pair and the decorator pair
    edited = dict(TWIN_SRCS)
    edited["pkg/dev.py"] = "def mix(x):\n    return x * 5\n"
    fs = analysis.run_on_sources(edited, twin_store=store)
    assert rules_of(fs) == ["twin-drift", "twin-drift"]
    assert all("device side" in f.message for f in fs)
    # editing the HOST class twin trips just its pair, at the class
    edited2 = dict(TWIN_SRCS)
    edited2["pkg/host.py"] = TWIN_SRCS["pkg/host.py"].replace("* 3", "* 4")
    fs = analysis.run_on_sources(edited2, twin_store=store)
    assert [f.path for f in fs] == ["pkg/host.py"]
    assert "host side" in fs[0].message


def test_twin_drift_comment_edits_do_not_trip():
    store = _twin_store_for(TWIN_SRCS)
    cosmetic = dict(TWIN_SRCS)
    cosmetic["pkg/dev.py"] = ("def mix(x):\n"
                              "    # a comment, not a drift\n"
                              "    return x * 3\n")
    assert analysis.run_on_sources(cosmetic, twin_store=store) == []


def test_twin_drift_unregistered_missing_and_stale():
    # declared pair with no committed fingerprints: unacked
    fs = analysis.run_on_sources(TWIN_SRCS, twin_store=None)
    assert rules_of(fs) == ["twin-drift"] * 2
    assert all("no committed fingerprints" in f.message for f in fs)
    # one side deleted: the registry itself has drifted
    store = _twin_store_for(TWIN_SRCS)
    gone = {k: v for k, v in TWIN_SRCS.items() if k != "pkg/dev.py"}
    fs = analysis.run_on_sources(gone, twin_store=store)
    assert fs and all("does not resolve" in f.message for f in fs)
    # a committed pair no longer declared anywhere: deliberate drop
    # required (--ack-twin)
    undeclared = dict(TWIN_SRCS)
    undeclared["pkg/analysis/twins.py"] = "TWIN_TABLE = []\n"
    fs = analysis.run_on_sources(undeclared, twin_store=store)
    assert any("no longer declared" in f.message for f in fs)
    # ...including when EVERY registration is deleted at once — an
    # emptied registry must not disarm its own gate
    disarmed = dict(undeclared)
    disarmed["pkg/marked.py"] = "def mix_np(x):\n    return x * 3\n"
    fs = analysis.run_on_sources(disarmed, twin_store=store)
    assert sorted(f.message.split("'")[1] for f in fs
                  if "no longer declared" in f.message) == \
        ["host-sketch", "pkg/marked.py:mix_np"]


def test_twin_drift_pragma_and_partial_scan():
    store = _twin_store_for(TWIN_SRCS)
    edited = dict(TWIN_SRCS)
    edited["pkg/dev.py"] = ("def mix(x):  # lint: disable=twin-drift\n"
                            "    return x * 5\n")
    assert analysis.run_on_sources(edited, twin_store=store) == []
    # a scan that sees NEITHER side of a pair stays silent (partial
    # scans must not cry drift)
    partial = {"pkg/analysis/twins.py": TWIN_SRCS["pkg/analysis/twins.py"]}
    assert analysis.run_on_sources(partial, twin_store=store) == []


def test_twin_ack_cli_round_trip(tmp_path, capsys):
    """The --ack-twin workflow end to end: ack -> clean gate, edit ->
    gate trips, re-ack -> clean again (the CI acceptance shape)."""
    for rel, src in TWIN_SRCS.items():
        if rel == "pkg/marked.py":
            continue            # keep the fixture import-free
        f = tmp_path / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(src)
    store = tmp_path / "twins.json"
    assert cli_main(["lint", str(tmp_path), "--twins", str(store),
                     "--ack-twin"]) == 0
    assert cli_main(["lint", str(tmp_path), "--twins", str(store),
                     "--rules", "twin-drift"]) == 0
    (tmp_path / "pkg/dev.py").write_text("def mix(x):\n    return x * 9\n")
    assert cli_main(["lint", str(tmp_path), "--twins", str(store),
                     "--rules", "twin-drift"]) == 1
    out = capsys.readouterr().out
    assert "twin-drift" in out and "--ack-twin" in out
    assert cli_main(["lint", str(tmp_path), "--twins", str(store),
                     "--ack-twin"]) == 0
    assert cli_main(["lint", str(tmp_path), "--twins", str(store),
                     "--rules", "twin-drift"]) == 0
    capsys.readouterr()


def test_twin_ack_path_scope_merges_not_overwrites(tmp_path, capsys):
    """A path-scoped --ack-twin must not drop acknowledged pairs it
    never scanned — partial acks merge; only a full scan replaces."""
    for rel, src in TWIN_SRCS.items():
        if rel == "pkg/marked.py":
            continue
        f = tmp_path / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(src)
    other = tmp_path / "other.py"
    other.write_text(
        "from deepflow_tpu.utils.twinmark import host_twin_of\n"
        "@host_twin_of('other.py:dev')\n"
        "def host(x):\n"
        "    return x\n"
        "def dev(x):\n"
        "    return x\n")
    store = tmp_path / "twins.json"
    assert cli_main(["lint", str(tmp_path), "--twins", str(store),
                     "--ack-twin"]) == 0
    n_full = len(json.loads(store.read_text())["pairs"])
    assert n_full == 2          # the table pair + the decorator pair
    # re-ack ONLY the decorator file: the table pair must survive
    assert cli_main(["lint", str(other), "--twins", str(store),
                     "--ack-twin"]) == 0
    assert len(json.loads(store.read_text())["pairs"]) == n_full
    capsys.readouterr()


@pytest.mark.parametrize("extra", [[], None])
def test_fingerprint_ignores_empty_fields_a_python_adds(extra):
    """A field that a newer interpreter adds and leaves empty (3.12's
    type_params=[] on every def) must not move the fingerprint, in
    either direction; a filled field still must."""
    import ast
    import copy

    from deepflow_tpu.analysis.twins import fingerprint

    node = ast.parse("def f(x):\n    return x + 1\n").body[0]
    fp = fingerprint(node)
    grown = copy.deepcopy(node)
    grown._fields = grown._fields + ("future_field",)
    grown.future_field = extra
    assert fingerprint(grown) == fp
    if hasattr(node, "type_params"):      # the pre-3.12 shape
        shrunk = copy.deepcopy(node)
        shrunk._fields = tuple(f for f in shrunk._fields
                               if f != "type_params")
        del shrunk.type_params
        assert fingerprint(shrunk) == fp
    grown.future_field = [ast.Name("T", ast.Load())]
    assert fingerprint(grown) != fp


def test_repo_twin_store_matches_tree(repo_scan):
    """The committed .lint-twins.json is in lockstep with the shipped
    tree: the self-scan (which loads it by default) reports no drift,
    and every committed pair still resolves."""
    assert [f for f in repo_scan if f.rule == "twin-drift"] == []
    store = json.loads((REPO_ROOT / ".lint-twins.json").read_text())
    assert store["version"] == 1
    assert len(store["pairs"]) >= 10


# --------------------------------------------------------------- sarif

def test_cli_sarif_output(tmp_path, capsys):
    f = tmp_path / "mod.py"
    f.write_text(THREAD_SRC)
    out = tmp_path / "lint.sarif"
    assert cli_main(["lint", str(f), "--sarif", str(out)]) == 1
    doc = json.loads(out.read_text())
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "deepflow-lint"
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    for need in ("lock-order-cycle", "unlocked-shared-write",
                 "silent-drop", "twin-drift", "unsupervised-thread"):
        assert need in rule_ids
    assert run["results"][0]["ruleId"] == "unsupervised-thread"
    loc = run["results"][0]["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] == 2
    capsys.readouterr()


# --------------------------------------------------------- framework

def test_parse_error_is_a_finding():
    fs = analysis.run_on_sources({"bad.py": "def f(:\n"})
    assert rules_of(fs) == ["parse-error"]


def test_pragma_inside_string_literal_does_not_suppress():
    src = ('import threading\n'
           't = threading.Thread(target=print); '
           's = "# lint: disable=all"\n')
    assert rules_of(analysis.run_on_sources({"m.py": src})) \
        == ["unsupervised-thread"]


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match="unknown rule"):
        analysis.run_on_sources({"a.py": "x = 1\n"}, rules=["no-such-rule"])


def test_baseline_round_trip_and_line_shift(tmp_path):
    fs = analysis.run_on_sources({"a.py": THREAD_SRC})
    bl = tmp_path / "bl.json"
    analysis.save_baseline(fs, str(bl))
    loaded = analysis.load_baseline(str(bl))
    assert analysis.new_findings(fs, loaded) == []
    # shifting the finding to another line must not resurface it
    shifted = analysis.run_on_sources({"a.py": "\n\n# pad\n" + THREAD_SRC})
    assert analysis.new_findings(shifted, loaded) == []
    # a SECOND identical violation exceeds the baselined count -> new
    doubled = analysis.run_on_sources(
        {"a.py": THREAD_SRC + "u = threading.Thread(target=print)\n"})
    assert len(analysis.new_findings(doubled, loaded)) == 1


def test_baseline_file_is_sorted_and_versioned(tmp_path):
    fs = analysis.run_on_sources(
        {"b.py": THREAD_SRC, "a.py": THREAD_SRC})
    bl = tmp_path / "bl.json"
    analysis.save_baseline(fs, str(bl))
    doc = json.loads(bl.read_text())
    assert doc["version"] == 1
    paths = [e["path"] for e in doc["findings"]]
    assert paths == sorted(paths)
    assert all("line" not in e for e in doc["findings"])


# --------------------------------------------------------------- CLI

_RULE_FIXTURES = {
    "unsupervised-thread": ("mod.py", THREAD_SRC),
    "emit-under-lock": ("mod.py", LOCKED_EMIT),
    "host-sync-in-device-path": ("runtime/tpu_sketch.py", DEVICE_SYNC),
    "trace-unsafe-jit": ("mod.py", ("import time, jax\n"
                                    "f = jax.jit(lambda x: time.time())\n")),
    "countable-missing-counters": ("mod.py", (
        "class P:\n"
        "    def __init__(self, stats):\n"
        "        stats.register('p', self.counters)\n")),
    "fault-site-drift": ("runtime/faults.py", 'FAULT_O = "ghost.site"\n'),
    "lock-order-cycle": ("runtime/q.py", (
        "import threading\n"
        "class Q:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def put(self, x):\n"
        "        with self._lock:\n"
        "            self._flush()\n"
        "    def _flush(self):\n"
        "        with self._lock:\n"
        "            pass\n")),
    "unlocked-shared-write": ("runtime/w.py", (
        "import threading\n"
        "class W:\n"
        "    def __init__(self, sup):\n"
        "        self._lock = threading.Lock()\n"
        "        self._buf = []\n"
        "        sup.spawn('w', self._run)\n"
        "    def put(self, frame):\n"
        "        with self._lock:\n"
        "            self._buf.append(frame)\n"
        "    def _run(self):\n"
        "        self._buf = []\n")),
    "silent-drop": ("runtime/d.py", (
        "class D:\n"
        "    def put(self, batch):\n"
        "        if self._closed:\n"
        "            return\n"
        "        self._emit(batch)\n")),
    # the table-declared pair is unacked against the committed store,
    # so the gate trips on the fixture without touching the real tree
    "twin-drift": ("analysis/twins.py", (
        'TWIN_TABLE = [("p", "analysis/twins.py:f",'
        ' "analysis/twins.py:g")]\n'
        "def f(x):\n"
        "    return x\n"
        "def g(x):\n"
        "    return x\n")),
}


@pytest.mark.parametrize("rule", sorted(_RULE_FIXTURES))
def test_cli_exits_nonzero_on_synthetic_violation(rule, tmp_path, capsys):
    relpath, src = _RULE_FIXTURES[rule]
    f = tmp_path / rule / relpath
    f.parent.mkdir(parents=True)
    f.write_text(src)
    assert cli_main(["lint", str(tmp_path / rule)]) == 1
    out = capsys.readouterr().out
    assert rule in out


def test_cli_baseline_gates_and_updates(tmp_path, capsys):
    f = tmp_path / "mod.py"
    f.write_text(THREAD_SRC)
    bl = tmp_path / "bl.json"
    assert cli_main(["lint", str(f), "--baseline", str(bl),
                     "--update-baseline"]) == 0
    # same tree + baseline: clean exit
    assert cli_main(["lint", str(f), "--baseline", str(bl)]) == 0
    # a new violation beyond the baseline: gate trips
    f.write_text(THREAD_SRC + "u = threading.Thread(target=print)\n")
    assert cli_main(["lint", str(f), "--baseline", str(bl)]) == 1
    capsys.readouterr()


def test_cli_explicit_path_gate_is_cwd_independent(tmp_path, capsys,
                                                   monkeypatch):
    """Explicit package paths key findings like the committed baseline
    (package-parent-relative) from ANY cwd — an operator gating from
    /tmp must not see 24 grandfathered findings resurface as new."""
    monkeypatch.chdir(tmp_path)
    assert cli_main(["lint", str(REPO_ROOT / "deepflow_tpu"),
                     "--baseline",
                     str(REPO_ROOT / ".lint-baseline.json")]) == 0
    capsys.readouterr()


def test_cli_json_output(tmp_path, capsys):
    f = tmp_path / "mod.py"
    f.write_text(THREAD_SRC)
    assert cli_main(["lint", str(f), "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc[0]["rule"] == "unsupervised-thread"


# ---------------------------------------------------- repo self-scan

@pytest.fixture(scope="module")
def repo_scan():
    """One ~250-file scan shared by the self-scan tests (ci.sh already
    pays for a full scan in its lint gate; no need for two more)."""
    return analysis.scan_package()


def test_repo_self_scan_zero_new_findings(repo_scan):
    """The shipped tree + committed baseline must gate clean — exactly
    what ci.sh enforces. If this fails you either introduced a new
    violation (fix it) or fixed a baselined one (shrink
    .lint-baseline.json with --update-baseline and commit the diff)."""
    baseline = analysis.load_baseline(str(REPO_ROOT / ".lint-baseline.json"))
    new = analysis.new_findings(repo_scan, baseline)
    assert new == [], "\n" + analysis.format_findings(new)


def test_repo_baseline_has_no_stale_entries(repo_scan):
    """Every baselined finding still exists AT ITS COUNT: entries whose
    violations were (even partially) fixed must be deleted, or the spare
    credits would grandfather a later reintroduction of the identical
    violation (the baseline only ever shrinks — ISSUE 3). Multiset
    compare: three identical Agent.start spawns are three entries."""
    baseline = analysis.load_baseline(str(REPO_ROOT / ".lint-baseline.json"))
    current = Counter(f.key for f in repo_scan)
    stale = sorted(k for k, n in baseline.items() if n > current[k])
    assert stale == [], f"over-credited baseline entries (shrink): {stale}"
