import numpy as np

import jax
import jax.numpy as jnp

from deepflow_tpu.ops import cms, topk


def test_topk_recall_vs_exact_groupby(rng):
    """North-star harness in miniature: recall vs exact GROUP BY (<1% loss
    target from BASELINE.md, measured at 100% here on a small universe)."""
    n, k = 200_000, 50
    keys = rng.zipf(1.2, size=n).clip(max=200_000).astype(np.uint32)
    sketch = cms.init(depth=4, log2_width=16)
    ring = topk.init(ring_size=512)

    step = jax.jit(lambda s, r, b: (
        lambda s2: (s2, topk.offer(r, b, s2)))(cms.update_conservative(s, b)))
    for i in range(0, n, 20_000):
        batch = jnp.asarray(keys[i:i + 20_000])
        sketch, ring = step(sketch, ring, batch)

    got_keys, got_counts = topk.result(ring, k)
    got = set(np.asarray(got_keys).tolist())
    uniq, counts = np.unique(keys, return_counts=True)
    want = set(uniq[np.argsort(counts)[::-1][:k]].tolist())
    recall = len(got & want) / k
    assert recall >= 0.99, recall
    # counts of returned keys are CMS overestimates of truth
    truth = dict(zip(uniq.tolist(), counts.tolist()))
    for key, est in zip(np.asarray(got_keys).tolist(),
                        np.asarray(got_counts).tolist()):
        if key in truth:
            assert est >= truth[key]


def test_offer_dedups_standing_candidates(rng):
    sketch = cms.init(depth=4, log2_width=12)
    ring = topk.init(ring_size=8)
    batch = jnp.asarray(np.array([5, 5, 5, 6], np.uint32))
    sketch = cms.update(sketch, batch)
    ring = topk.offer(ring, batch, sketch)
    ring = topk.offer(ring, batch, sketch)   # same keys again
    keys = np.asarray(ring.keys)
    real = keys[keys != 0xFFFFFFFF]
    assert len(np.unique(real)) == len(real)  # no duplicate candidates


def test_mask_excludes_padding():
    sketch = cms.init(depth=4, log2_width=12)
    ring = topk.init(ring_size=8)
    batch = jnp.asarray(np.array([1, 2, 3, 999], np.uint32))
    mask = jnp.asarray(np.array([1, 1, 1, 0], bool))
    sketch = cms.update(sketch, batch, mask=mask)
    ring = topk.offer(ring, batch, sketch, mask=mask)
    keys, counts = topk.result(ring, 8)
    keys = np.asarray(keys)[np.asarray(counts) > 0]
    assert 999 not in keys.tolist()


def test_sampled_admission_recall_production_path(rng):
    """Recall harness for the production-style path: plain MXU-histogram CMS
    + 1/16 stride-sampled, phase-rotated ring admission (the flow_suite
    mechanism, at test-scale width). Admission is sampled but scores are
    full-sketch and standing candidates are rescored each batch, so hot keys
    rank correctly once admitted."""
    n, k, batch = 400_000, 100, 40_000
    keys = rng.zipf(1.2, size=n).clip(max=200_000).astype(np.uint32)
    sketch = cms.init(depth=4, log2_width=16)
    ring = topk.init(ring_size=1024)

    step = jax.jit(lambda s, r, b, ph: (
        lambda s2: (s2, topk.offer(r, b, s2, sample_log2=4, phase=ph))
    )(cms.update(s, b)))
    for j, i in enumerate(range(0, n, batch)):
        sketch, ring = step(sketch, ring, jnp.asarray(keys[i:i + batch]),
                            jnp.int32(j))

    got_keys, _ = topk.result(ring, k)
    got = set(np.asarray(got_keys).tolist())
    uniq, counts = np.unique(keys, return_counts=True)
    want = set(uniq[np.argsort(counts)[::-1][:k]].tolist())
    recall = len(got & want) / k
    assert recall >= 0.98, recall


def test_staged_update_equals_fused():
    """flow_suite.make_staged_update (the four-program pipeline the
    tpu_sketch exporter runs with staged=True) produces bit-identical
    state to the fused update."""
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.batch.schema import SKETCH_L4_SCHEMA
    from deepflow_tpu.models import flow_suite

    cfg = flow_suite.FlowSuiteConfig(cms_log2_width=12, ring_size=256,
                                     hll_groups=32, hll_precision=6,
                                     entropy_log2_buckets=6)
    rng = np.random.default_rng(11)
    staged = flow_suite.make_staged_update(cfg)
    fused = jax.jit(lambda s, c, m: flow_suite.update(s, c, m, cfg))
    s_f, s_s = flow_suite.init(cfg), flow_suite.init(cfg)
    n = 4096
    for i in range(4):
        cols = {nm: jnp.asarray(rng.integers(0, 1 << 16, n).astype(d))
                for nm, d in SKETCH_L4_SCHEMA.columns}
        mask = jnp.asarray(rng.random(n) < 0.9)
        s_f = fused(s_f, cols, mask)
        s_s = staged(s_s, cols, mask)
    for a, b in zip(jax.tree.leaves(s_f), jax.tree.leaves(s_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
