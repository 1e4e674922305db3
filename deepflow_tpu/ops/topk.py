"""Device-side heavy-hitter top-K over a CMS-estimated candidate ring.

Exact top-K needs the full key universe (the reference gets it for free from
ClickHouse GROUP BY at query time). On device we instead keep a fixed-size
candidate ring: every batch, the batch's (deduped) keys are scored against
the Count-Min sketch, merged with the standing candidates, and compacted back
to ring size with `lax.top_k` — all static shapes, fully jittable.

Recall loss vs exact comes from (a) CMS overestimation (mitigated by
conservative update) and (b) ring evictions (mitigated by ring_size >> K).
tests/test_topk.py scores recall against an exact numpy GROUP BY, the
in-repo stand-in for the reference exactness harness (SURVEY.md §4).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from deepflow_tpu.ops import cms

# np scalar, NOT jnp: a jnp.uint32() here would be a device array
# committed to the default backend at import, and compiling any program
# that embeds it would fetch it back from the device.
SENTINEL = np.uint32(0xFFFFFFFF)


class TopKState(NamedTuple):
    keys: jnp.ndarray    # [ring] uint32, SENTINEL = empty
    counts: jnp.ndarray  # [ring] int32 CMS estimates


def init(ring_size: int) -> TopKState:
    return TopKState(
        keys=jnp.full((ring_size,), SENTINEL, dtype=jnp.uint32),
        counts=jnp.full((ring_size,), -1, dtype=jnp.int32),
    )


def _nonzero_u32(x: jnp.ndarray) -> jnp.ndarray:
    """[n] uint32 1 where x != 0, else 0, via (x | -x) >> 31 — pure
    arithmetic, no compare/select/minimum op at all."""
    return (x | (jnp.uint32(0) - x)) >> jnp.uint32(31)


def _not_sentinel(keys: jnp.ndarray) -> jnp.ndarray:
    """[n] int32 1 where key != SENTINEL, else 0 — WITHOUT a compare op.

    Every predicate on moved data here is pure arithmetic: SENTINEL is
    u32 max, so SENTINEL - k is 0 iff k is the sentinel, and
    _nonzero_u32 turns that into a 0/1 lane. The compare-free form
    served a remote runtime that is gone; nothing needs it now
    (ROADMAP D8)."""
    return _nonzero_u32(SENTINEL - keys).astype(jnp.int32)


def _dedup_sorted(k: jnp.ndarray, c: jnp.ndarray):
    """Dedup ALREADY-SORTED (key, count) pairs: within an equal-key run
    counts sort ascending, so the run's LAST lane already holds the max —
    no segment-max scatter, no cumsum. Run boundaries are detected
    arithmetically (sorted ascending => k[i+1] - k[i] is 0 iff equal),
    never with a compare: see _not_sentinel."""
    diff = _nonzero_u32(k[1:] - k[:-1])
    last_u = jnp.concatenate([diff, jnp.ones((1,), jnp.uint32)])
    last_i = last_u.astype(jnp.int32) * _not_sentinel(k)
    # k where last-of-run, SENTINEL elsewhere; c where kept, -1 elsewhere
    k = k * last_u + SENTINEL * (jnp.uint32(1) - last_u)
    c = last_i * (c + 1) - 1
    return k, c


def _dedup_keep_max(keys: jnp.ndarray, counts: jnp.ndarray):
    """Sort by key; on equal runs keep the max count on one lane, -1 on
    rest (one two-key sort + arithmetic boundary detect)."""
    k, c = jax.lax.sort((keys, counts), num_keys=2)
    return _dedup_sorted(k, c)


def candidate_keys(state_keys: jnp.ndarray, batch_keys: jnp.ndarray,
                   mask: jnp.ndarray | None = None, sample_log2: int = 0,
                   phase: jnp.ndarray | int = 0) -> jnp.ndarray:
    """Standing ring keys + (sampled) batch keys — the movement half of
    admission, shared by offer() and the staged pipeline.

    The mask is applied arithmetically (bool -> u32 - 1 = all-ones where
    dead, OR'd in = SENTINEL), not with jnp.where."""
    bk = batch_keys.astype(jnp.uint32)
    if mask is not None:
        bk = bk | (mask.astype(jnp.uint32) - jnp.uint32(1))
    if sample_log2 > 0:
        bk = jnp.roll(bk, -(jnp.asarray(phase) % (1 << sample_log2)))
        bk = bk[:: 1 << sample_log2]
    return jnp.concatenate([state_keys, bk])


def blend_counts(all_keys: jnp.ndarray, est: jnp.ndarray) -> jnp.ndarray:
    """est where the key is live, -1 at sentinels — compare-free."""
    live = _not_sentinel(all_keys)
    return live * (est.astype(jnp.int32) + 1) - 1


def sort_pairs(all_keys: jnp.ndarray, all_counts: jnp.ndarray):
    """Two-key lexicographic sort (movement only, no compares)."""
    return jax.lax.sort((all_keys, all_counts), num_keys=2)


def select_ring(k: jnp.ndarray, c: jnp.ndarray,
                ring_size: int) -> TopKState:
    """Dedup (last-of-run on the sorted pairs) + top_k compaction.
    Compares here touch only this function's inputs — the staged pipeline
    relies on that (see flow_suite.make_staged_update)."""
    k2, c2 = _dedup_sorted(k, c)
    top_c, top_i = jax.lax.top_k(c2, ring_size)
    return TopKState(keys=k2[top_i], counts=top_c)


def offer(state: TopKState, batch_keys: jnp.ndarray, sketch: cms.CMSState,
          mask: jnp.ndarray | None = None, sample_log2: int = 0,
          phase: jnp.ndarray | int = 0) -> TopKState:
    """Merge a batch of keys (scored via `sketch`) into the candidate ring.

    `sample_log2 > 0` admits only a 1/2^s stride-sample of lanes. Admission
    is sampled; *scores* always come from the full Count-Min sketch, and
    standing candidates are rescored every batch, so a hot key only has to be
    sampled once per window to be ranked with its true (full-stream) estimate.
    This cuts the per-batch gather + sort from O(n) to O(n/2^s), bounding
    per-batch work the way the reference's throttler bounds per-second writes
    (server/ingester/flow_log/throttler/throttling_queue.go:98).

    `phase` rotates which residue class (mod 2^s) gets sampled — pass a
    per-batch counter so lane positions correlated with the stride (e.g.
    round-robin packers upstream) still get admitted over a window.
    """
    # Standing candidates get re-scored too (their CMS estimates only
    # grow), in the SAME query as the batch keys: one concat + one gather
    # instead of a separate ring-sized pass.
    all_keys = candidate_keys(state.keys, batch_keys, mask, sample_log2,
                              phase)
    est = cms.query(sketch, all_keys)
    k, c = sort_pairs(all_keys, blend_counts(all_keys, est))
    return select_ring(k, c, state.keys.shape[0])


def result(state: TopKState, k: int):
    """(keys, counts) of the current top-k, count-descending."""
    top_c, top_i = jax.lax.top_k(state.counts, k)
    return state.keys[top_i], top_c


def reset(state: TopKState) -> TopKState:
    return init(state.keys.shape[0])
