"""Compile rehearsals for a v5e chip, with no chip attached.

The TPU compiler in the installed libtpu compiles for a described
v5e:2x2 topology, so what Mosaic or XLA would refuse on the chip (a
scoped-VMEM overflow, a block shape off the tiling) fails here, at
production widths, before any chip time is spent. Nothing runs: these
tests say nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process may hold libtpu, and every xdist worker imports this
file (on-chip-measurement guide, section 2).
"""

import os

import pytest

import jax
import jax.numpy as jnp

from deepflow_tpu.models import flow_dict, flow_suite
from deepflow_tpu.ops import pallas_hist, pallas_sketch

CFG = flow_suite.FlowSuiteConfig()
BATCH = 1 << 15                   # TpuSketchExporter's default batch_rows


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _state_spec(one_chip, cfg):
    return jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                        jax.eval_shape(lambda: flow_suite.init(cfg)))


@pytest.mark.parametrize("kernel,rows", [
    (pallas_sketch.fused_lane_hists, 4),
    (pallas_sketch.fused_news_hists, 6),
])
def test_fused_hist_kernels_compile(one_chip, kernel, rows):
    st = _state_spec(one_chip, CFG)
    compiled = jax.jit(
        lambda p, n, a, b: kernel(
            p, n, a, b, cms_log2_width=CFG.cms_log2_width,
            ent_log2_buckets=CFG.entropy_log2_buckets)).lower(
        _spec(one_chip, (rows, BATCH), jnp.uint32),
        _spec(one_chip, (), jnp.uint32),
        st.sketch.seeds, st.ent.seeds).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width,d", [
    (1 << 17, 4),                 # the CMS
    (1 << 16, 4),
    (1 << 12, 4),                 # entropy buckets
    (1024 * 512, 1),              # DDSketch flat
])
def test_hist_pallas_compiles_at_fitted_chunk(one_chip, width, d):
    compiled = jax.jit(
        lambda i, w: pallas_hist.hist_pallas(i, width, w)).lower(
        _spec(one_chip, (d, BATCH), jnp.int32),
        _spec(one_chip, (BATCH,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fused", [False, True])
def test_lanes_wire_update_compiles(one_chip, fused):
    cfg = flow_suite.FlowSuiteConfig(fused_hists=fused)
    prog = flow_suite.make_coalesced_update(cfg, 1, BATCH)
    compiled = prog.lower(
        _state_spec(one_chip, cfg),
        _spec(one_chip, (flow_suite.coalesced_lanes_words(1, BATCH),),
              jnp.uint32)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) is fused


@pytest.mark.parametrize("fused", [False, True])
def test_dict_wire_update_compiles(one_chip, fused):
    cfg = flow_suite.FlowSuiteConfig(fused_hists=fused)
    sig = (("news", BATCH), ("hits", BATCH // 2))
    prog = flow_dict.make_wire_update(cfg, sig)
    table = _spec(one_chip, (4, max(2 * BATCH, 1 << 17)), jnp.uint32)
    compiled = prog.lower(
        _state_spec(one_chip, cfg), flow_dict.FlowDictState(table=table),
        _spec(one_chip, (flow_dict.wire_words(sig),), jnp.uint32)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) is fused
