"""Compile rehearsals of the main-path kernels for a described TPU v5e.

Mosaic (``interpret=False``) compiles each kernel at smollm-135m widths for
a ``v5e:2x2`` topology that is described, not attached, so a kernel the
chip's compiler would refuse fails here without a chip. Nothing runs. The
topology is described inside a fixture (only the worker that runs this
file loads the TPU library); every case compiles with the persistent
compilation cache off.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.acu import make_acu

D, F, HQ, HKV, HD = 576, 1536, 9, 3, 64        # smollm-135m widths
V = 49152                                       # its vocabulary


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _dense(lut, off):
    from repro.kernels.fused_lut_dense.ops import fused_lut_dense
    return (lambda x, wq, ws: fused_lut_dense(x, wq, lut, off, 0.02, 0.0, ws,
                                              interpret=False))


def _bwd(lut, off):
    from repro.kernels.fused_lut_dense.ops import fused_lut_bwd
    return (lambda a, b: fused_lut_bwd(a, b, lut, off, 0.01, 0.02,
                                       interpret=False))


def _bwd_4planes(lut, off):
    # entries past three signed base-256 digits: a table of four planes,
    # the widest working set a tile can hold
    return _bwd((np.asarray(lut, np.int64) * 40000).astype(np.int32), off)


def _attn(lut, off):
    from repro.kernels.flash_attention.approx import approx_flash_attention
    return (lambda q, k, v: approx_flash_attention(
        q, k, v, lut, off, 0.01, 0.01, 0.01, interpret=False))


def _paged(lut, off):
    from repro.kernels.flash_attention.approx import \
        approx_flash_attention_paged
    return (lambda q, k, v, info, pt: approx_flash_attention_paged(
        q, k, v, lut, off, 0.01, 0.01, 0.01, rowinfo=info, page_table=pt,
        rep=HQ // HKV, interpret=False))


def _grouped(lut, off):
    from repro.kernels.fused_lut_grouped.ops import fused_lut_grouped
    return (lambda x, wq, ws, counts: fused_lut_grouped(
        x, wq, lut, off, 0.02, 0.0, ws, counts, interpret=False))


f32, i32 = jnp.float32, jnp.int32
CASES = {
    "fused_lut_dense_prefill": (_dense, [((256, D), f32), ((D, F), i32),
                                         ((F,), f32)]),
    "fused_lut_dense_decode": (_dense, [((4, D), f32), ((D, D), i32),
                                        ((D,), f32)]),
    "fused_lut_bwd": (_bwd, [((256, F), f32), ((F, D), f32)]),
    # the benchmark cells' shapes at M = 2048 tokens: the widest tiles
    "fused_lut_dense_cell": (_dense, [((2048, D), f32), ((D, F), i32),
                                      ((F,), f32)]),
    "fused_lut_dense_head": (_dense, [((2048, D), f32), ((D, V), i32),
                                      ((V,), f32)]),
    "fused_lut_bwd_gw": (_bwd, [((D, 2048), f32), ((2048, F), f32)]),
    # four planes: (640, 256, 640) would pass the default scoped VMEM by
    # the tile model, so the 576-wide gw narrows to (640, 256, 128); the
    # gate's (640, 256, 512) is kept
    "fused_lut_bwd_gw_4planes": (_bwd_4planes, [((D, 2048), f32),
                                                ((2048, D), f32)]),
    "fused_lut_bwd_gw_gate_4planes": (_bwd_4planes, [((D, 2048), f32),
                                                     ((2048, F), f32)]),
    "fused_lut_grouped": (_grouped, [((8, 32, D), f32), ((4, D, F), i32),
                                     ((4, F), f32), ((8,), i32)]),
    "approx_flash_attention": (_attn, [((HQ, 128, HD), f32),
                                       ((HKV, 128, HD), f32),
                                       ((HKV, 128, HD), f32)]),
    "approx_flash_attention_paged_decode": (
        _paged, [((4 * HQ, 1, HD), f32), ((HKV, 64, 16, HD), f32),
                 ((HKV, 64, 16, HD), f32), ((4 * HQ, 3), i32),
                 ((4 * HQ, 16), i32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    build, shapes = CASES[name]
    acu = make_acu("mul8s_1L2H", "lut")
    fn = build(jnp.asarray(acu.lut), acu.offset)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
