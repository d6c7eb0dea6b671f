"""Quantized serving subsystem (PR 6 tentpole).

* pack/unpack int4 round-trips (deterministic + hypothesis property);
* fused dequant-matmul Pallas kernels (interpret mode) match the jnp oracle
  bit-tolerance-tight on non-aligned shapes, int8 and group-wise int4;
* `quantize_model` quantizes every dense except the keep-list, and serving
  through the quantized tree is *bit-identical* to serving the dequantized
  tree (the oracle's dequantize-then-matmul contract), including the exact
  identity case (integer weights at full scale -> zero quantization error);
* perplexity smoke bound: fixed-batch NLL drifts by less than the floor;
* int8 paged KV: per-slot scales quantize on fill and dequantize on read,
  `copy_cache_blocks` moves the scales with the blocks, and the byte
  accounting roughly doubles the block budget at equal bytes;
* `quant_factor` raises ValueError naming the supported formats (was a bare
  KeyError);
* the calibration fitter keys quantized kernel records per-format
  ("dequant_matmul:int8") while full-precision records keep the bare name.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.decomposition import Workload, decompose  # noqa: E402
from repro.core.formalisms import quant_factor  # noqa: E402
from repro.kernels.dequant_matmul import (  # noqa: E402
    dequant_matmul, dequant_matmul_int4_pallas, dequant_matmul_int4_ref,
    dequant_matmul_int8_pallas, dequant_matmul_int8_ref, dequantize_int4,
    dequantize_int8, unpack_int4)
from repro.models import ArchConfig, Model  # noqa: E402
from repro.models.cache import (kv_bytes_per_token, make_cache,  # noqa: E402
                                PagedLayout, copy_cache_blocks)
from repro.quant import (bytes_per_param_for, dequantize_model,  # noqa: E402
                         group_size_for, pack_int4, param_bytes,
                         params_quant_format, qdense, quant_workload,
                         quantize_int4, quantize_int8, quantize_model)
from repro.quant.quantize import (bind_int8_stacks,  # noqa: E402
                                  split_int8_stacks)

CFG = ArchConfig(name="tq", arch_type="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)


@pytest.fixture(scope="module")
def model_params():
    model = Model(CFG, dtype=jnp.float32)
    return model, model.init(jax.random.key(0))


# ===================================================== satellite: quant_factor

def test_quant_factor_unknown_format_raises_valueerror():
    with pytest.raises(ValueError, match="int4"):
        quant_factor("int3")
    with pytest.raises(ValueError, match="supported"):
        quant_factor("q5_k_m")
    assert quant_factor("int4") == 0.45
    assert quant_factor("INT8") == 0.65


def test_bytes_per_param_for_unknown_raises():
    with pytest.raises(ValueError, match="supported"):
        bytes_per_param_for("int2")
    assert bytes_per_param_for("int4") == 0.5


# ================================================================ pack/unpack

def test_pack_unpack_round_trip_deterministic():
    rng = np.random.default_rng(0)
    q = rng.integers(-8, 8, size=(10, 7)).astype(np.int8)
    packed = pack_int4(jnp.asarray(q))
    assert packed.shape == (5, 7) and packed.dtype == jnp.uint8
    assert np.array_equal(np.asarray(unpack_int4(packed)), q)


def test_pack_unpack_round_trip_stacked_leading_axis():
    rng = np.random.default_rng(1)
    q = rng.integers(-8, 8, size=(3, 6, 5)).astype(np.int8)
    assert np.array_equal(np.asarray(unpack_int4(pack_int4(jnp.asarray(q)))),
                          q)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 12), st.integers(1, 9), st.integers(0, 2 ** 31 - 1))
def test_pack_unpack_round_trip_property(half_k, n, seed):
    rng = np.random.default_rng(seed)
    q = rng.integers(-8, 8, size=(2 * half_k, n)).astype(np.int8)
    assert np.array_equal(np.asarray(unpack_int4(pack_int4(jnp.asarray(q)))),
                          q)


def test_group_size_adjusts_to_even_divisor():
    assert group_size_for(64, 32) == 32
    assert group_size_for(48, 32) == 24
    assert group_size_for(10, 32) == 10
    assert group_size_for(6, 4) == 2
    with pytest.raises(ValueError, match="even"):
        group_size_for(7, 4)


# ===================================================== kernel vs oracle parity

def _exact_int8_operands(M, K, N, dtype, rng):
    """x on a grid its dtype holds exactly (quarters for bf16; for f32,
    steps of 1/256, finer than bf16 holds, so rounding x to bf16 would
    show), int8 weights and power-of-two scales: every product and partial
    sum is exact in f32, so kernel and oracle agree before the one rounding
    to x's dtype, at any K."""
    steps = 16 if dtype == jnp.bfloat16 else 1024
    x = jnp.asarray(rng.integers(-steps, steps + 1, size=(M, K))
                    / (steps // 4), dtype)
    qw = jnp.asarray(rng.integers(-127, 128, size=(K, N)), jnp.int8)
    scale = jnp.asarray(2.0 ** -rng.integers(4, 9, size=N), jnp.float32)
    return x, qw, scale


@pytest.mark.parametrize("M,K,N,dtype", [
    pytest.param(5, 48, 19, None, id="5-48-19"),
    pytest.param(1, 32, 130, None, id="1-32-130"),
    pytest.param(9, 64, 64, None, id="9-64-64"),
    pytest.param(17, 96, 33, None, id="17-96-33"),
    # decode: one resident row block, a wide tile that leaves N a partial edge
    pytest.param(32, 512, 1408, jnp.bfloat16, id="decode-bf16"),
    # K no power of two, N in three tiles, the last partial
    pytest.param(32, 1664, 1408, jnp.bfloat16, id="decode-bf16-wide-k"),
    pytest.param(32, 1664, 1408, jnp.float32, id="decode-f32"),
    # prefill: two row blocks, the second partial
    pytest.param(600, 640, 1408, jnp.bfloat16, id="prefill-bf16"),
    pytest.param(600, 640, 1408, jnp.float32, id="prefill-f32"),
])
def test_int8_kernel_matches_oracle_nonaligned(M, K, N, dtype):
    rng = np.random.default_rng(2)
    if dtype is None:  # normal draws, quantized
        x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
        qw, scale = quantize_int8(jnp.asarray(rng.normal(size=(K, N)),
                                              jnp.float32))
    else:
        x, qw, scale = _exact_int8_operands(M, K, N, dtype, rng)
    want = dequant_matmul_int8_ref(x, qw, scale)
    got = dequant_matmul_int8_pallas(x, qw, scale, interpret=True)
    assert got.dtype == x.dtype and got.shape == (M, N)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("M,K,N,gs", [(5, 48, 19, 16), (1, 32, 130, 32),
                                      (9, 64, 64, 16), (17, 96, 33, 8)])
def test_int4_kernel_matches_oracle_nonaligned(M, K, N, gs):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    packed, scale = quantize_int4(jnp.asarray(rng.normal(size=(K, N)),
                                              jnp.float32), gs)
    assert scale.shape == (K // gs, N)
    want = dequant_matmul_int4_ref(x, packed, scale)
    got = dequant_matmul_int4_pallas(x, packed, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dispatch_discriminates_by_dtype():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 3, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    qw8, s8 = quantize_int8(w)
    qw4, s4 = quantize_int4(w, 16)
    y8 = dequant_matmul(x, qw8, s8)
    y4 = dequant_matmul(x, qw4, s4)
    assert y8.shape == y4.shape == (2, 3, 16)
    np.testing.assert_array_equal(np.asarray(y8),
                                  np.asarray(dequant_matmul_int8_ref(x, qw8,
                                                                     s8)))
    np.testing.assert_array_equal(np.asarray(y4),
                                  np.asarray(dequant_matmul_int4_ref(x, qw4,
                                                                     s4)))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_int8_kernel_reads_one_layer_of_a_stack(dtype):
    """Given an (L, K, N) stack and a traced layer index, as a layer scan
    hands them over, the kernel computes with that layer's weight."""
    rng = np.random.default_rng(5)
    L, M, K, N = 3, 32, 256, 640
    x, _, _ = _exact_int8_operands(M, K, N, dtype, rng)
    stack = jnp.asarray(rng.integers(-127, 128, size=(L, K, N)), jnp.int8)
    scale = jnp.asarray(2.0 ** -rng.integers(4, 9, size=(L, N)), jnp.float32)

    def body(_, inp):
        s, i = inp
        return None, dequant_matmul_int8_pallas(x, stack, s, i,
                                                interpret=True)

    _, got = jax.lax.scan(body, None, (scale, jnp.arange(L)))
    for i in range(L):
        want = dequant_matmul_int8_ref(x, stack[i], scale[i])
        np.testing.assert_allclose(np.asarray(got[i], np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-5, atol=1e-5)


def test_int8_stacks_split_from_scanned_blocks_and_bound_per_layer(
        model_params):
    _, params = model_params
    rest, stacks = split_int8_stacks(quantize_model(params, "int8")["blocks"])
    assert all(leaf.dtype != jnp.int8 for leaf in jax.tree.leaves(rest))
    qws = jax.tree.leaves(stacks)
    assert qws and all(q.dtype == jnp.int8 and q.ndim == 3 for q in qws)
    # int4 stacks (and full-precision weights) stay where the scan slices
    assert split_int8_stacks(quantize_model(params, "int4")["blocks"])[1] \
        == {}
    assert split_int8_stacks(params["blocks"])[1] == {}
    x = jnp.asarray(np.random.default_rng(6).normal(size=(3, CFG.d_model)),
                    jnp.float32)
    for i in range(qws[0].shape[0]):
        layer = jax.tree.map(lambda a: a[i], rest)
        bound = bind_int8_stacks(layer, stacks, jnp.int32(i))
        wq = bound["l0"]["attn"]["wq"]
        assert wq["qw"] is stacks["l0"]["attn"]["wq"]["qw"]
        want = dequant_matmul_int8_ref(x, wq["qw"][i], layer["l0"]["attn"]
                                       ["wq"]["scale"])
        np.testing.assert_array_equal(np.asarray(qdense(wq, x)),
                                      np.asarray(want))


# =========================================================== model quantizing

def test_quantize_model_structure(model_params):
    _, params = model_params
    for fmt, qdtype in (("int8", jnp.int8), ("int4", jnp.uint8)):
        qp = quantize_model(params, fmt, 16)
        # keep-list untouched
        for key in ("embed", "lm_head", "final_norm"):
            assert jax.tree.all(jax.tree.map(
                lambda a, b: bool(jnp.array_equal(a, b)),
                params[key], qp[key]))
        # stacked scanned blocks quantized in place, format by dtype
        flat = jax.tree.leaves(qp["blocks"])
        assert any(leaf.dtype == qdtype for leaf in flat)
        assert params_quant_format(qp) == fmt
        assert param_bytes(qp) < param_bytes(params)
    assert params_quant_format(params) == "bf16"
    assert quantize_model(params, "bf16") is params
    with pytest.raises(ValueError, match="supported"):
        quantize_model(params, "fp4")


def _gen(backend, prompts, n_samples=2, max_new=6):
    h = backend.start_batch(prompts, n_samples, max_new, 0.8,
                            jax.random.key(42))
    while backend.decode_step(h):
        pass
    return backend.finalize(h)


def _assert_same_results(want, got):
    for a, b in zip(want, got):
        for s1, s2 in zip(a.samples, b.samples):
            assert np.array_equal(s1, s2)
        assert a.logprobs == b.logprobs


@pytest.mark.parametrize("fmt", ["int8", "int4"])
def test_quantized_generate_bit_identical_to_dequantized(model_params, fmt):
    """Serving the quantized tree == serving its (lossy) dequantized
    reconstruction, bit for bit: the dispatch path computes exactly
    ``x @ (qw * scale)``, nothing else."""
    from repro.serving import ExecutionBackend
    model, params = model_params
    qp = quantize_model(params, fmt, 16)
    dq = dequantize_model(qp, jnp.float32)
    prompts = [((np.arange(1, 11, dtype=np.int32) * m) % CFG.vocab_size)
               for m in (1, 3)]
    want = _gen(ExecutionBackend(model, dq), prompts)
    got = _gen(ExecutionBackend(model, qp), prompts)
    _assert_same_results(want, got)


def _integerize(params, max_q):
    """Replace every quantizable dense weight with integer values whose
    per-column absmax is exactly ``max_q`` -> quantization scale is exactly
    1.0 and round-tripping is lossless (the identity-scale case)."""
    rng = np.random.default_rng(9)

    def walk(node):
        if isinstance(node, dict):
            if "w" in node and getattr(node["w"], "ndim", 0) >= 2:
                out = dict(node)
                w = rng.integers(-max_q, max_q + 1,
                                 size=node["w"].shape).astype(np.float32)
                w[..., 0, :] = max_q            # every column/group hits max_q
                if max_q == 7:                  # int4: every group of 16 rows
                    w[..., ::16, :] = max_q
                out["w"] = jnp.asarray(w, node["w"].dtype)
                return out
            return {k: walk(v) for k, v in node.items()}
        return node
    return walk(params)


@pytest.mark.parametrize("fmt,max_q", [("int8", 127), ("int4", 7)])
def test_identity_scale_generate_bit_identical_to_unquantized(model_params,
                                                              fmt, max_q):
    from repro.serving import ExecutionBackend
    model, params = model_params
    ip = _integerize(params, max_q)
    qp = quantize_model(ip, fmt, 16)
    # lossless: dequantization reproduces the integer weights exactly
    rt = dequantize_model(qp, jnp.float32)
    for a, b in zip(jax.tree.leaves(ip), jax.tree.leaves(rt)):
        assert jnp.array_equal(a, b)
    prompts = [((np.arange(1, 11, dtype=np.int32) * m) % CFG.vocab_size)
               for m in (1, 3)]
    _assert_same_results(_gen(ExecutionBackend(model, ip), prompts),
                         _gen(ExecutionBackend(model, qp), prompts))


def test_perplexity_delta_smoke_bound(model_params):
    model, params = model_params
    rng = np.random.default_rng(11)
    toks = rng.integers(0, CFG.vocab_size, size=(4, 24)).astype(np.int32)
    pos = jnp.broadcast_to(jnp.arange(23, dtype=jnp.int32)[None], (4, 23))
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:]), "positions": pos}
    base = float(model.loss(params, batch))
    for fmt, bound in (("int8", 0.05), ("int4", 0.35)):
        q = float(model.loss(quantize_model(params, fmt, 16), batch))
        assert abs(q - base) <= bound, (fmt, base, q)


# ============================================================== int8 paged KV

def test_make_cache_int8_paged_shapes_and_dense_rejection():
    cache = make_cache(CFG, 0, 0, jnp.float32,
                       paged=PagedLayout(6, 4), kv_dtype=jnp.int8)
    entry = cache["blocks"]["l0"]
    n_super = cache["blocks"]["l0"]["k"].shape[0]
    assert entry["k"].dtype == jnp.int8
    assert entry["k_scale"].shape == (n_super, 6, 4, CFG.n_kv_heads)
    assert entry["k_scale"].dtype == jnp.float32
    with pytest.raises(ValueError, match="paged"):
        make_cache(CFG, 2, 16, jnp.float32, kv_dtype=jnp.int8)


def test_int8_kv_fill_read_roundtrip_and_attention_close(model_params):
    """Quantize-on-fill + dequant-on-read through gqa_forward: the paged
    int8 path's attention output stays within int8 tolerance of the f32
    paged path on identical inputs."""
    from repro.models.attention import gqa_forward
    model, params = model_params
    p = jax.tree.map(lambda a: a[0], params["blocks"]["l0"]["attn"])
    B, S = 2, 8
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(B, S, CFG.d_model)) * 0.3, jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    table = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)

    def run(kv_dtype):
        cache = make_cache(CFG, 0, 0, jnp.float32, paged=PagedLayout(8, 4),
                           kv_dtype=kv_dtype)["blocks"]["l0"]
        # single-layer entry: strip the stacked super-block axis
        cache = jax.tree.map(lambda a: a[0], cache)
        y, new_cache = gqa_forward(p, CFG, x, positions, cache=cache,
                                   block_table=table, kv_len=12)
        xd = x[:, -1:, :]
        pd = positions[:, -1:] + 1
        yd, _ = gqa_forward(p, CFG, xd, pd, cache=new_cache,
                            block_table=table, kv_len=12)
        return y, yd, new_cache

    y32, yd32, c32 = run(None)
    y8, yd8, c8 = run(jnp.int8)
    assert c8["k"].dtype == jnp.int8 and "k_scale" in c8
    # written slots dequantize back to the f32 cache within int8 tolerance
    filled = np.asarray(c8["pos"]) >= 0
    k_deq = np.asarray(c8["k"], np.float32) * \
        np.asarray(c8["k_scale"])[..., None]
    np.testing.assert_allclose(k_deq[filled],
                               np.asarray(c32["k"])[filled],
                               atol=0.02, rtol=0.02)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y32), atol=0.05)
    np.testing.assert_allclose(np.asarray(yd8), np.asarray(yd32), atol=0.05)


def test_copy_cache_blocks_moves_scales():
    cache = make_cache(CFG, 0, 0, jnp.float32, paged=PagedLayout(6, 4),
                       kv_dtype=jnp.int8)
    k = cache["blocks"]["l0"]["k_scale"]
    cache["blocks"]["l0"]["k_scale"] = k.at[:, 0].set(3.5)
    out = copy_cache_blocks(cache, jnp.asarray([0]), jnp.asarray([5]))
    assert float(out["blocks"]["l0"]["k_scale"][0, 5, 0, 0]) == 3.5


def test_int8_kv_doubles_block_budget_at_equal_bytes(model_params):
    from repro.serving import ExecutionBackend
    model, params = model_params
    assert kv_bytes_per_token(CFG, 2) / kv_bytes_per_token(CFG, 1) >= 1.8
    b16 = ExecutionBackend(model, params, kv_blocks=8, kv_block_size=4)
    b8 = ExecutionBackend(model, params, kv_blocks=8, kv_block_size=4,
                          kv_format="int8")
    assert b16.kv_token_bytes / b8.kv_token_bytes >= 1.8
    with pytest.raises(ValueError, match="kv_blocks"):
        ExecutionBackend(model, params, kv_format="int8")
    with pytest.raises(ValueError, match="kv_format"):
        ExecutionBackend(model, params, kv_blocks=8, kv_format="fp8")


def test_int8_kv_generate_completes_and_stays_close(model_params):
    from repro.serving import ExecutionBackend
    model, params = model_params
    prompts = [((np.arange(1, 11, dtype=np.int32) * m) % CFG.vocab_size)
               for m in (1, 3)]
    want = _gen(ExecutionBackend(model, params, kv_blocks=64,
                                 kv_block_size=4), prompts)
    got = _gen(ExecutionBackend(model, params, kv_blocks=64, kv_block_size=4,
                                kv_format="int8"), prompts)
    assert all(len(r.samples) == 2 for r in got)
    # int8 KV is lossy: sampled tokens may diverge, but per-sequence mean
    # logprob stays in the same regime
    for a, b in zip(want, got):
        for la, lb in zip(a.logprobs, b.logprobs):
            assert abs(la - lb) < 1.5, (la, lb)


# =========================================== workload / telemetry / fit hooks

def test_workload_kv_bytes_and_quant_factor_tiers():
    w = Workload()
    assert w.kv_bytes_per_el == w.bytes_per_act and w.quant_factor == 1.0
    w8 = quant_workload(w, "int8", kv_format="int8")
    assert w8.bytes_per_param == 1.0 and w8.kv_bytes_per_el == 1.0
    assert w8.quant_factor == 0.65
    w4 = quant_workload(w, "int4")
    assert w4.bytes_per_param == 0.5 and w4.quant_factor == 0.45
    assert w4.kv_bytes_per_el == w.bytes_per_act
    # decode stages move fewer bytes with a lighter KV element
    dec = [s for s in decompose(CFG, w8) if s.phase == "decode"]
    dec_ref = [s for s in decompose(CFG, Workload(bytes_per_param=1.0))
               if s.phase == "decode"]
    assert sum(s.bytes_moved for s in dec) < \
        sum(s.bytes_moved for s in dec_ref)


def test_fitter_keys_quantized_kernel_records_per_format():
    from repro.qeil2.telemetry import CalibrationFitter, TraceStore
    store = TraceStore()
    for quant, eta in (("bf16", 0.8), ("int8", 0.6), ("int4", 0.5)):
        for rep in range(3):
            store.ingest({"kind": "kernel", "kernel": "dequant_matmul",
                          "rep": rep, "flops": 1e9, "bytes": 1e6,
                          "measured_us": 100.0 / eta, "roofline_us": 100.0,
                          "quant": quant, "device": "synthetic"})
    profile, _ = CalibrationFitter(store, n_bootstrap=8).fit()
    eta_keys = dict(profile.kernel_eta)
    assert set(eta_keys) == {"dequant_matmul", "dequant_matmul:int8",
                             "dequant_matmul:int4"}
    assert eta_keys["dequant_matmul"] == pytest.approx(0.8, abs=1e-6)
    assert profile.eta_for("dequant_matmul", "int8") == \
        pytest.approx(0.6, abs=1e-6)
    assert profile.eta_for("dequant_matmul", "int4") == \
        pytest.approx(0.5, abs=1e-6)
    # unmeasured quant falls back to the bare-kernel eta, then 1.0
    assert profile.eta_for("dequant_matmul", "fp8") == \
        pytest.approx(0.8, abs=1e-6)
    assert profile.eta_for("missing", "int8") == 1.0


def test_serve_trace_records_carry_quant_fields():
    from repro.qeil2.telemetry import TraceStore
    from repro.serving.scheduler import BatchRecord
    rec = BatchRecord(batch_id=0, t_s=0.0, bucket=8, n_requests=1,
                      n_sequences=2, tier_mix={"standard": 1},
                      queue_delay_s=0.0, point_index=0, energy_j=1.0,
                      latency_s=0.5, meets_caps=True, reroute=False,
                      kv_blocks_in_use=3, quant="int4", kv_format="int8",
                      weight_bytes=1234, kv_bytes_in_use=816)
    stored = TraceStore().ingest_serve(rec)
    assert stored["quant"] == "int4" and stored["kv_format"] == "int8"
    assert stored["weight_bytes"] == 1234
    assert stored["kv_bytes_in_use"] == 816


def test_synthetic_fixture_recovers_per_format_etas():
    from repro.qeil2.telemetry import CalibrationFitter
    from repro.qeil2.telemetry.synthetic import (TRUE_KERNEL_ETA,
                                                 synthetic_trace_store)
    profile, _ = CalibrationFitter(synthetic_trace_store(seed=0),
                                   n_bootstrap=0).fit()
    eta = dict(profile.kernel_eta)
    for name, truth in TRUE_KERNEL_ETA.items():
        assert name in eta
        assert abs(eta[name] - truth) < abs(1.0 - truth), (name, eta[name])
