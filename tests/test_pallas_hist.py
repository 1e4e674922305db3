"""Pallas VMEM-resident histogram vs the XLA scan path: identical
outputs on every shape the sketches use (interpret mode on CPU; the
real-chip perf comparison lives in benches/kernel_bench.py)."""

import numpy as np
import pytest

import jax.numpy as jnp

from deepflow_tpu.ops.mxu_hist import hist
from deepflow_tpu.ops.pallas_hist import hist_pallas


@pytest.mark.parametrize("width,d,n", [
    (1 << 16, 4, 50_000),       # CMS: depth 4, 2^16 counters
    (1 << 12, 4, 20_000),       # entropy buckets
    (1024 * 512, 1, 30_000),    # DDSketch flat (groups x buckets)
])
def test_matches_xla_path(width, d, n):
    rng = np.random.default_rng(width % 97)
    idx = jnp.asarray(rng.integers(0, width, (d, n), dtype=np.int32))
    w = jnp.asarray(rng.integers(0, 3000, n, dtype=np.int32))
    for weights in (None, w):
        a = hist(idx, width, weights, method="xla")
        b = hist_pallas(idx, width, weights, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("width,chunk", [
    (1 << 17, 1024),            # the CMS width: 2048 asks 23 MB of VMEM
    (1 << 16, 2048),
    (1 << 12, 2048),
    (1024 * 512, 1024),         # floor: the 1-D weight block tiles by 1024
])
def test_fit_chunk_stays_inside_scoped_vmem(width, chunk):
    from deepflow_tpu.ops.mxu_hist import _split_hi_lo
    from deepflow_tpu.ops.pallas_hist import fit_chunk

    assert fit_chunk(4096, *_split_hi_lo(width)) == chunk


def test_saturation_and_padding():
    # weights above the plane range saturate identically; n not a
    # multiple of chunk exercises the zero-weight pad rows
    idx = jnp.asarray(np.zeros((2, 4097), np.int32))
    w = jnp.asarray(np.full(4097, 1 << 20, np.int32))
    a = hist(idx, 1 << 16, w, method="xla")
    b = hist_pallas(idx, 1 << 16, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the true sum (~2^28) exceeds f32's exact-integer range; both
    # paths round identically (checked above), value is approximate
    assert float(a[0, 0]) == pytest.approx(4097 * (256 ** 2 - 1),
                                           rel=1e-6)


def test_method_dispatch(monkeypatch):
    from deepflow_tpu.ops import pallas_hist

    idx = jnp.asarray(np.random.default_rng(0).integers(
        0, 1 << 16, (4, 9000), dtype=np.int32))
    out_x = hist(idx, 1 << 16, method="xla")
    # a forced kernel off a TPU is refused, never silently interpreted
    with pytest.raises(ValueError, match="interpret"):
        hist(idx, 1 << 16, method="pallas")
    monkeypatch.setattr(pallas_hist, "INTERPRET", True)
    out_p = hist(idx, 1 << 16, method="pallas")
    np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_p))
    # auto on CPU stays on the XLA path regardless of the env opt-in
    monkeypatch.setenv("DEEPFLOW_HIST_PALLAS", "1")
    out_a = hist(idx, 1 << 16, method="auto")
    np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_a))
