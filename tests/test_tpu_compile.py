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

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.decode_attention.paged_latent import (
    latent_pool_view, paged_latent_decode_pallas)
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


def _paged_latent():
    """DeepSeek-V2-Lite's latent decode at the docqa cell's size: 16
    sequences over 1,038 blocks of 16 in a 4,544-block pool, 16 heads,
    latent 512, rope 64."""
    cfg = get_config("deepseek-v2-lite-16b-ep4")
    H, r, rd = cfg.n_heads, cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
    B, P, bs, nb, L = 16, 4544, 16, 1038, 1

    def fn(ql, qr, c_new, kr_new, c_kv, k_rope, pos, table, q_pos):
        view = latent_pool_view(c_kv, k_rope, pos, table)
        return paged_latent_decode_pallas(ql, qr, c_new, kr_new, view, 0,
                                          table, q_pos, scale=0.1)
    return fn, [((B, H, r), BF), ((B, H, rd), BF), ((B, r), BF),
                ((B, rd), BF), ((L, P, bs, r), BF), ((L, P, bs, rd), BF),
                ((L, P, bs), I32), ((B, nb), I32), ((B,), I32)]


def _dense():
    H, Hkv, D, _, _ = _glm()
    B, W = 8, 1024
    return (lambda *a: decode_attention_pallas(*a, interpret=False),
            [((B, 1, H, D), BF), ((B, W, Hkv, D), BF), ((B, W, Hkv, D), BF),
             ((B, W), I32), ((B,), I32)])


def _int8(M=16, K=None, N=None):
    _, _, _, d, f = _glm()
    K, N = K or d, N or f
    return (lambda *a: dequant_matmul_int8_pallas(*a, interpret=False),
            [((M, K), BF), ((K, N), jnp.int8), ((N,), F32)])


# every (K, N) chatglm3-6b serves through the int8 kernel (q, k and v, o,
# gate and up, down), at a decode batch and a prefill tail: each compile
# proves its blocks fit the default scoped VMEM
_INT8_SERVED = [(M, K, N) for M in (32, 576)
                for K, N in ((4096, 4096), (4096, 256), (4096, 13696),
                             (13696, 4096))]


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
    pytest.param(_paged_latent, id="paged_latent_decode"),
    pytest.param(_dense, id="decode_attention"),
    pytest.param(_int8, id="dequant_matmul_int8"),
    *[pytest.param(functools.partial(_int8, *mkn),
                   id="dequant_matmul_int8-{}x{}x{}".format(*mkn))
      for mkn in _INT8_SERVED],
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


def _entry(hlo):
    """(name, opcode, operands, result type) of each ENTRY instruction."""
    entry = hlo[hlo.index("\nENTRY "):]
    return re.findall(r"^\s*(?:ROOT )?%(\S+) = (\S+) ([\w-]+)\((.*?)\)",
                      entry, re.M)


def test_int8_kernel_is_one_named_custom_call(one_chip):
    """Decode over the down projection's (13696, 4096) int8 weight, the
    kernel called on its own: the compiled program is the kernel's one
    custom call, named after its jit (the name `chipbench`'s roofline
    reader sums), and nothing else reads the weight: no pad, copy or
    convert of it outside the kernel (a bitcast moves no bytes). Under a
    layer scan see `test_int8_model_reads_weight_stacks_in_place`."""
    fn, shapes = _int8(32, 13696, 4096)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    instrs = [(n, op, ops) for n, _, op, ops in
              _entry(jax.jit(fn).lower(*args).compile().as_text())]
    weight = {next(name for name, op, operands in instrs
                   if op == "parameter" and operands == "1")}
    readers = []
    for name, op, operands in instrs:
        if any(re.search(rf"%{re.escape(w)}\b", operands) for w in weight):
            (weight.add if op == "bitcast" else
             lambda n: readers.append((n, op)))(name)
    calls = [name for name, op, _ in instrs
             if op == "custom-call" and "dequant_matmul" in name]
    assert len(calls) == 1
    assert readers == [(calls[0], "custom-call")]


def test_int8_model_reads_weight_stacks_in_place(one_chip, monkeypatch):
    """A decode step of int8 chatglm3-6b at its published size, with the
    kernel the chip dispatches to: the layer scan hands the kernel each
    whole (L, K, N) weight stack and the layer index, so no XLA op slices,
    copies or stages an int8 weight (a scan over the stacks themselves
    stages each layer's seven weights into VMEM, and the kernel's device
    time then leaves out their HBM read); every s8 array the program
    makes is a view of a parameter."""
    from repro.kernels.dequant_matmul import ops as dq_ops
    from repro.quant import quantize_model
    monkeypatch.setattr(dq_ops, "interpret_mode", lambda: False)
    dq_ops.dequant_matmul.clear_cache()
    model = Model(get_config("chatglm3-6b"), dtype=BF)
    B, T = 32, 256

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: quantize_model(model.init(jax.random.key(0)), "int8")))
    cache = on_chip(model.init_cache(B, T, spec_only=True))
    tokens = jax.ShapeDtypeStruct((B, 1), I32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B, 1), I32, sharding=one_chip)

    def step(p, c, t, q):
        return model.forward(p, {"tokens": t, "positions": q}, c)[:2]

    try:
        hlo = jax.jit(step).lower(params, cache, tokens, pos).compile() \
            .as_text()
    finally:
        dq_ops.dequant_matmul.clear_cache()
    s8 = {(op, name) for name, ty, op in
          re.findall(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", hlo, re.M)
          if "s8[" in ty}
    assert {op for op, _ in s8} <= {"parameter", "get-tuple-element",
                                    "bitcast", "tuple", "while"}, sorted(s8)
    calls = re.findall(r"%(\S+) = \S+ custom-call\(([^)]*)\)", hlo)
    assert sum("dequant_matmul" in name for name, _ in calls) == 7


def test_deepseek_share_decode_fits_with_its_pool(one_chip, monkeypatch):
    """The DeepSeek-V2-Lite share's pooled decode step at its cell's size
    (16 sequences over 16,608 positions of the 4,544-block latent pool),
    with the kernel the chip dispatches to: the weights, the pool and the
    step's output pool (nothing donates it) and its temporaries fit the
    chip; latent attention is the paged latent decode kernel, which reads
    the pools in place: no gathered (16, 16608, 512) context is made in
    any dtype, and the one copy of a latent pool is the prefix layer's
    write into its undonated pool."""
    import types

    from repro.kernels.decode_attention import ops as da_ops
    from repro.serving.backend import ExecutionBackend
    monkeypatch.setattr(da_ops, "interpret_mode", lambda: False)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    model = Model(get_config("deepseek-v2-lite-16b-ep4"), dtype=BF)
    params = on_chip(model.param_specs())
    cache = on_chip(model.init_paged_cache(4544, 16, spec_only=True))
    B, blocks, K = 16, 1038, 16608

    def i32(*s):
        return jax.ShapeDtypeStruct(s, I32, sharding=one_chip)

    key = jax.eval_shape(lambda: jax.random.key(0))
    step = jax.jit(functools.partial(ExecutionBackend._decode_step,
                                     types.SimpleNamespace(model=model)),
                   static_argnames=("kv_len", "greedy"))
    compiled = step.lower(
        params, i32(B, 1), i32(), cache,
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((), F32, sharding=one_chip), {},
        i32(B, blocks), kv_len=K).compile()
    mem = compiled.memory_analysis()
    nbytes = [sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(t))
              for t in (params, cache)]
    assert sum(nbytes) + nbytes[1] + mem.temp_size_in_bytes < HBM_BYTES
    hlo = compiled.as_text()
    assert f"[{B},{K},512]" not in hlo
    calls = re.findall(r"%(\S+) = \S+ custom-call\(", hlo)
    assert sum("paged_latent_decode" in name for name in calls) == 2
    copies = re.findall(r"^\s*(?:ROOT )?%\S+ = (.*?) (?:copy|copy-start)"
                        r"\((.*?)\)", hlo, re.M)
    assert [ops for ty, ops in copies if "4544,16,512]" in ty] == [
        "%cache__prefix___0___c_kv__.1"]
