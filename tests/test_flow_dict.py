"""Dictionary-lane wire (models/flow_dict.py): the host->device
SmartEncoding path must produce bit-identical additive sketch state to
the packed-lane path on the same records, at roughly half the steady-
state wire bytes, with index reuse provably confusion-free."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepflow_tpu.models import flow_dict, flow_dict_native, flow_suite
from deepflow_tpu.models.flow_dict import FlowDictPacker
from deepflow_tpu.models.flow_suite import FlowSuiteConfig

CFG = FlowSuiteConfig(cms_log2_width=10, ring_size=256, top_k=20,
                      hll_groups=64, entropy_log2_buckets=8)


def _pool(n, seed=7):
    rng = np.random.default_rng(seed)
    return {
        "ip_src": rng.integers(0, 1 << 32, n, dtype=np.uint32),
        "ip_dst": rng.integers(0, 1 << 32, n, dtype=np.uint32),
        "port_src": rng.integers(1024, 65536, n, dtype=np.uint32),
        "port_dst": rng.integers(1, 1024, n, dtype=np.uint32),
        "proto": rng.choice(np.array([6, 17], np.uint32), n),
        "packet_tx": rng.integers(1, 1000, n, dtype=np.uint32),
        "packet_rx": rng.integers(0, 1000, n, dtype=np.uint32),
    }


def _zipf_stream(pool, n_batches, batch, seed=11):
    rng = np.random.default_rng(seed)
    n = len(pool["ip_src"])
    for _ in range(n_batches):
        picks = (rng.zipf(1.3, batch) - 1).clip(max=n - 1)
        yield {k: v[picks] for k, v in pool.items()}


def _run_packed(batches):
    state = flow_suite.init(CFG)
    for cols in batches:
        lanes = {k: jnp.asarray(v)
                 for k, v in flow_suite.pack_lanes(cols).items()}
        mask = jnp.ones(len(cols["ip_src"]), bool)
        state = flow_suite.update_packed(state, lanes, mask, CFG)
    return state


def _run_dict(batches, packer):
    state = flow_suite.init(CFG)
    dstate = flow_dict.init_dict(packer.capacity)
    wire = []
    for cols in batches:
        wire.extend(packer.pack(cols))
    wire.extend(packer.flush())
    state, dstate = flow_dict.apply_batches(state, dstate, wire, CFG)
    return state, dstate, wire


def _assert_additive_state_equal(a, b):
    """Everything except the ring: top-K admission stride-samples per
    batch, so a different batch partition of the same records admits
    different candidates (same class of difference as topk_sample_log2
    itself); the additive sketches must match EXACTLY."""
    np.testing.assert_array_equal(np.asarray(a.sketch.counts),
                                  np.asarray(b.sketch.counts))
    np.testing.assert_array_equal(np.asarray(a.services.registers),
                                  np.asarray(b.services.registers))
    np.testing.assert_array_equal(np.asarray(a.ent.hist),
                                  np.asarray(b.ent.hist))
    assert int(a.rows_seen) == int(b.rows_seen)


def test_dict_path_matches_packed_path_state():
    pool = _pool(512)
    batches = list(_zipf_stream(pool, 6, 2048))
    packed = _run_packed(batches)
    dicted, _, wire = _run_dict(
        batches, FlowDictPacker(capacity=4096, hits_batch=2048,
                                news_batch=256))
    _assert_additive_state_equal(packed, dicted)
    kinds = [k for k, _, _ in wire]
    assert "news" in kinds and "hits" in kinds


def test_steady_state_ships_half_the_bytes():
    """After warmup the stream is hits-only: 8B/record vs the packed
    lane's 16B. Bytes are counted on PADDED planes (what actually
    crosses the link), so the ratio must still land under 0.6 here."""
    pool = _pool(256)
    batches = list(_zipf_stream(pool, 20, 4096))
    packer = FlowDictPacker(capacity=8192, hits_batch=4096,
                            news_batch=256)
    _, _, wire = _run_dict(batches, packer)
    records = 20 * 4096
    lane_bytes = records * 16
    dict_bytes = packer.bytes_news + packer.bytes_hits
    assert dict_bytes < 0.6 * lane_bytes, (
        packer.bytes_news, packer.bytes_hits, lane_bytes)
    # the tail of the stream must be pure hits (dictionary warm)
    assert all(k == "hits" for k, _, _ in wire[-5:])


def test_news_only_once_per_flow():
    pool = _pool(64)
    batches = [dict(pool) for _ in range(3)]   # same 64 flows, 3 times
    packer = FlowDictPacker(capacity=1024, hits_batch=64, news_batch=64)
    news_rows = 0
    for cols in batches:
        for kind, _, n in packer.pack(cols):
            if kind == "news":
                news_rows += n
    assert news_rows == 64


def test_eviction_reuse_never_confuses_counts():
    """Roll through 3x the dictionary capacity in distinct flows so
    eviction and index reuse churn constantly; CMS counts must still
    equal the packed path's exactly (a mispaired gather would shift
    counts between flow keys)."""
    pool = _pool(1536, seed=23)
    # visit flows in overlapping windows so evicted flows return
    rng = np.random.default_rng(29)
    batches = []
    for start in (0, 256, 512, 768, 1024, 0, 512, 1200):
        picks = rng.integers(start, min(start + 400, 1536), 512)
        batches.append({k: v[picks] for k, v in pool.items()})
    packer = FlowDictPacker(capacity=500, hits_batch=256, news_batch=128)
    packed = _run_packed(batches)
    dicted, _, _ = _run_dict(batches, packer)
    assert packer.evictions > 0
    _assert_additive_state_equal(packed, dicted)


def test_recall_through_dict_path():
    """End-to-end heavy-hitter recall over the dictionary wire: the
    flows the exact GROUP BY ranks top-K must surface through
    news/hits -> table gather -> sketches -> ring."""
    pool = _pool(512, seed=31)
    batches = list(_zipf_stream(pool, 8, 4096, seed=37))
    packer = FlowDictPacker(capacity=8192, hits_batch=4096,
                            news_batch=512)
    state, _, _ = _run_dict(batches, packer)
    _, out = flow_suite.flush(state, CFG)
    got = set(np.asarray(out.topk_keys)[np.asarray(out.topk_counts) > 0]
              .tolist())
    # exact side
    keyfn = jax.jit(flow_suite.flow_key)
    pool_keys = np.asarray(keyfn(
        {k: jnp.asarray(v) for k, v in pool.items()}))
    counts = np.zeros(512, np.int64)
    rng = np.random.default_rng(37)
    for _ in range(8):
        picks = (rng.zipf(1.3, 4096) - 1).clip(max=511)
        counts += np.bincount(picks, minlength=512)
    top = np.argsort(-counts)[:CFG.top_k]
    exact = [pool_keys[i] for i in top]
    hit = sum(1 for k in exact if int(k) in got)
    assert hit / len(exact) >= 0.9, f"recall {hit}/{len(exact)}"


def test_news_trickle_ships_small_bucketed_planes():
    """A few new flows per pack() call must cost a few hundred bytes,
    not a full padded news plane (review r5): buckets are the smallest
    power of two >= rows (floor 256), so the trickle case stays
    proportional while jit specializations stay bounded."""
    pool = _pool(2048, seed=41)
    packer = FlowDictPacker(capacity=8192, hits_batch=2048,
                            news_batch=1024)
    # warm with 512 flows
    warm = {k: v[:512] for k, v in pool.items()}
    packer.pack(warm)
    before = packer.bytes_news
    # trickle: 3 new flows among 512 repeats
    trick = {k: np.concatenate([v[:509], v[512:515]])
             for k, v in pool.items()}
    out = packer.pack(trick)
    news = [(p, n) for kind, p, n in out if kind == "news"]
    assert len(news) == 1 and news[0][1] == 3
    assert news[0][0].shape == (6, 256)            # smallest bucket
    assert packer.bytes_news - before == 6 * 256 * 4
    # state equivalence must hold across mixed bucket shapes
    batches = [warm, trick]
    packed = _run_packed(batches)
    dicted, _, _ = _run_dict(batches,
                             FlowDictPacker(capacity=8192,
                                            hits_batch=2048,
                                            news_batch=1024))
    _assert_additive_state_equal(packed, dicted)


def test_pkts_above_u16_still_match_packed_lane():
    """The pairs wire carries u16 packet counts; entropy — the only
    sketch that reads pkts — saturates per-record weights at 65535 on
    BOTH its update paths (ops/entropy.py unified the exact path with
    the MXU clip), so the dict wire equals the packed lane even for
    records far above the field width. No pre-capping of the
    reference: this is the unconditional claim."""
    pool = _pool(32)
    pool["packet_tx"] = np.full(32, 200_000, np.uint32)   # > u16
    pool["packet_rx"] = np.zeros(32, np.uint32)
    batches = [dict(pool)]
    packed = _run_packed(batches)
    dicted, _, _ = _run_dict(batches,
                             FlowDictPacker(capacity=1024,
                                            hits_batch=64,
                                            news_batch=64))
    _assert_additive_state_equal(packed, dicted)


def test_capacity_guards():
    with pytest.raises(ValueError):
        FlowDictPacker(capacity=64, hits_batch=64)
    packer = FlowDictPacker(capacity=100, hits_batch=64, news_batch=32)
    pool = _pool(200)
    with pytest.raises(ValueError, match="unique flows"):
        packer.pack(pool)


def test_padding_rows_do_not_count():
    """A partial hits batch (padding beyond n) must contribute nothing:
    padded rows gather table row 0 — without the mask they would
    credit a real flow."""
    pool = _pool(8)
    packer = FlowDictPacker(capacity=256, hits_batch=128, news_batch=16)
    wire = packer.pack(pool) + packer.flush()
    state = flow_suite.init(CFG)
    dstate = flow_dict.init_dict(packer.capacity)
    state, _ = flow_dict.apply_batches(state, dstate, wire, CFG)
    assert int(state.rows_seen) == 8


# -- native key resolution == the pure-Python oracle -----------------------

def _fresh_mix(pool, n, fresh, rng):
    """n records Zipf over `pool`, a `fresh` share given new source
    ports and addresses (keys never seen before)."""
    m = len(pool["ip_src"])
    picks = (rng.zipf(1.25, n) - 1) % m
    cols = {k: v[picks].copy() for k, v in pool.items()}
    f = rng.random(n) < fresh
    cols["port_src"][f] = rng.integers(0, 1 << 16, f.sum(), dtype=np.uint32)
    cols["ip_src"][f] = rng.integers(0, 1 << 32, f.sum(), dtype=np.uint32)
    return cols


def _distinct(pool, lo, hi):
    return {k: v[lo:hi] for k, v in pool.items()}


def _oracle_pool(n, seed):
    """Half the flows run between 4 x 4 hosts, so many keys share their
    first 8 bytes and only the ports order them."""
    pool = _pool(n, seed=seed)
    rng = np.random.default_rng(seed + 1)
    hosts = rng.integers(0, 1 << 32, 8, dtype=np.uint32)
    few = rng.random(n) < 0.5
    pool["ip_src"][few] = hosts[rng.integers(0, 4, few.sum())]
    pool["ip_dst"][few] = hosts[rng.integers(4, 8, few.sum())]
    return pool


def _sequence(case, seed):
    """(packer kwargs, ops): ops are ("pack", cols), ("flush",) or
    ("reset",), the last a device-state restore's fresh packer."""
    rng = np.random.default_rng(seed)
    if case == "resident":
        pool = _oracle_pool(2048, seed)
        kw = dict(capacity=8192, hits_batch=2048, news_batch=256)
        ops = [("pack", _fresh_mix(pool, 3000, 0.0, rng))
               for _ in range(8)]
    elif case == "churn":
        # half fresh keys through a 1,024-slot table: fills, then
        # evicts on every call
        pool = _oracle_pool(512, seed)
        kw = dict(capacity=1024, hits_batch=512, news_batch=128)
        ops = [("pack", _fresh_mix(pool, 900, 0.5, rng))
               for _ in range(10)]
    elif case == "capacity":
        # the second batch's distinct keys reach capacity: refused,
        # state untouched, and the stream goes on
        pool = _oracle_pool(600, seed)
        kw = dict(capacity=256, hits_batch=128, news_batch=64)
        ops = [("pack", _distinct(pool, 0, 200)),
               ("pack", _distinct(pool, 200, 500)),
               ("pack", _fresh_mix(_distinct(pool, 100, 300), 250, 0.0,
                                   rng)),
               ("flush",)]
    elif case == "predrain":
        # hits stay buffered across calls (hits_batch > a call's
        # records) until a call that could fill the table drains them
        # ahead of its news
        pool = _oracle_pool(1400, seed)
        kw = dict(capacity=1024, hits_batch=1000, news_batch=128)
        warm = _distinct(pool, 0, 900)
        ops = [("pack", warm),
               ("pack", _fresh_mix(warm, 300, 0.0, rng)),
               ("pack", _distinct(pool, 900, 1100)),
               ("pack", _fresh_mix(_distinct(pool, 500, 1100), 600, 0.1,
                                   rng)),
               ("flush",)]
    else:                                        # reset
        pool = _oracle_pool(1024, seed)
        kw = dict(capacity=2048, hits_batch=512, news_batch=128)
        mix = [("pack", _fresh_mix(pool, 1500, 0.2, rng)) for _ in range(3)]
        ops = mix[:2] + [("reset",)] + mix[2:] + [("flush",)]
    return kw, ops


def _emit(kw, ops):
    """Run ops; returns [(op index, kind, plane bytes, shape, n)], the
    per-op errors and each packer generation's evictions."""
    packer = FlowDictPacker(**kw)
    wire, errors, evictions = [], [], []
    for i, op in enumerate(ops):
        if op[0] == "reset":
            evictions.append(packer.evictions)
            packer = FlowDictPacker(**kw)
            continue
        try:
            out = packer.pack(op[1]) if op[0] == "pack" else packer.flush()
        except ValueError as e:
            errors.append((i, str(e)))
            continue
        wire += [(i, k, p.tobytes(), p.shape, n) for k, p, n in out]
    evictions.append(packer.evictions)
    return wire, errors, evictions, packer


@pytest.mark.skipif(not flow_dict_native.available(),
                    reason=f"no native flow dict: "
                    f"{flow_dict_native.build_error()}")
@pytest.mark.parametrize("seed", [5, 2147483651])
@pytest.mark.parametrize("case", ["resident", "churn", "capacity",
                                  "predrain", "reset"])
def test_native_resolution_emits_the_python_wire(case, seed, monkeypatch):
    """The native key table and the pure-Python oracle emit the same
    wire, byte for byte: every plane, its n, their order, the refused
    calls and the evictions."""
    kw, ops = _sequence(case, seed % (1 << 32))
    native = _emit(kw, ops)
    assert native[3].resolve_native
    with monkeypatch.context() as m:
        m.setattr(flow_dict_native, "available", lambda: False)
        m.setattr(flow_dict, "_python_resolution_logged", True)
        python = _emit(kw, ops)
    assert not python[3].resolve_native
    assert native[:3] == python[:3]
    wire, errors, evictions, _ = python
    assert any(k == "news" for _, k, _, _, _ in wire)
    if case == "churn":
        assert evictions[0] > 0
    if case == "capacity":
        assert [i for i, _ in errors] == [1]
        assert "unique flows" in errors[0][1]
    else:
        assert errors == []
    if case == "predrain":
        # the call that could fill the table opens with the drained
        # partial hits plane, ahead of its own news
        first = [(k, n) for i, k, _, _, n in wire if i == 2][0]
        assert first[0] == "hits" and 0 < first[1] < kw["hits_batch"]
    if case == "reset":
        assert len(evictions) == 2
