"""Compile the Pallas kernels and the weight init for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
chip that is described rather than attached, and refuses what Mosaic or the
chip's memory would refuse (misaligned blocks, too much VMEM, a program that
does not fit HBM). Interpret mode, which the CPU tests use, catches none of
that. Shapes are the published widths of chatglm3-6b (granite-moe-3b-a800m
for `moe_gemm`, mamba2-370m for `ssd_scan`).

The topology is described only inside the module fixture: only the process
that runs these tests loads the TPU library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.dequant_matmul.dequant_matmul import (
    dequant_matmul_int4_pallas, dequant_matmul_int8_pallas)
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.moe_gemm.moe_gemm import moe_gemm_pallas
from repro.kernels.ssd_scan.ssd_scan import ssd_chunk_pallas
from repro.models import Model

HBM_BYTES = 16e9
BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one; keep these compiles out of any cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _glm():
    cfg = get_config("chatglm3-6b")
    return cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model, cfg.d_ff


def _flash(S):
    H, Hkv, D, _, _ = _glm()
    return (lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
            [((1, S, H, D), BF), ((1, S, Hkv, D), BF), ((1, S, Hkv, D), BF)])


def _paged():
    H, Hkv, D, _, _ = _glm()
    B, P, bs, nb = 8, 384, 16, 32
    return (lambda *a: paged_decode_attention_pallas(*a, interpret=False),
            [((B, 1, H, D), BF), ((P, bs, Hkv, D), BF), ((P, bs, Hkv, D), BF),
             ((P, bs), I32), ((B, nb), I32), ((B,), I32)])


def _dense():
    H, Hkv, D, _, _ = _glm()
    B, W = 8, 1024
    return (lambda *a: decode_attention_pallas(*a, interpret=False),
            [((B, 1, H, D), BF), ((B, W, Hkv, D), BF), ((B, W, Hkv, D), BF),
             ((B, W), I32), ((B,), I32)])


def _int8():
    _, _, _, d, f = _glm()
    return (lambda *a: dequant_matmul_int8_pallas(*a, interpret=False),
            [((16, d), BF), ((d, f), jnp.int8), ((f,), F32)])


def _int4():
    _, _, _, d, f = _glm()
    return (lambda *a: dequant_matmul_int4_pallas(*a, interpret=False),
            [((16, d), BF), ((d // 2, f), jnp.uint8), ((d // 32, f), F32)])


def _moe():
    cfg = get_config("granite-moe-3b-a800m")
    E, d, f = cfg.moe.n_experts, cfg.d_model, cfg.expert_ff()
    return (lambda x, w: moe_gemm_pallas(x, w, interpret=False),
            [((E, 128, d), BF), ((E, d, f), BF)])


def _ssd():
    cfg = get_config("mamba2-370m")
    s = cfg.ssm
    BH, nc = s.expand * cfg.d_model // s.headdim, 4
    col = ((BH, nc, s.chunk, 1), F32)
    return (lambda *a: ssd_chunk_pallas(*a, interpret=False),
            [((BH, nc, s.chunk, s.headdim), F32), col, col, col,
             ((BH, nc, s.chunk, s.d_state), F32),
             ((BH, nc, s.chunk, s.d_state), F32)])


@pytest.mark.parametrize("case", [
    pytest.param(lambda: _flash(1024), id="flash_attention"),
    pytest.param(lambda: _flash(1000), id="flash_attention_padded"),
    pytest.param(_paged, id="paged_decode_attention"),
    pytest.param(_dense, id="decode_attention"),
    pytest.param(_int8, id="dequant_matmul_int8"),
    pytest.param(_int4, id="dequant_matmul_int4"),
    pytest.param(_moe, id="moe_gemm"),
    pytest.param(_ssd, id="ssd_scan"),
])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = case()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_chatglm3_init_fits_one_chip(one_chip):
    """The jitted init builds the 28 stacked layers in place: its peak is
    about its output (12.49 GB of bf16 weights), not twice that."""
    model = Model(get_config("chatglm3-6b"), dtype=BF)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    mem = jax.jit(model.init).lower(key).compile().memory_analysis()
    weights = sum(leaf.size * leaf.dtype.itemsize
                  for leaf in jax.tree.leaves(jax.eval_shape(model.init,
                                                             key)))
    # (the output exceeds the leaves' bytes only by tile padding)
    assert mem.output_size_in_bytes == pytest.approx(weights, rel=1e-3)
    assert mem.temp_size_in_bytes < 0.01 * weights
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
