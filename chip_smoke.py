"""Smoke test of the serving stack on one TPU chip.

    python chip_smoke.py

Three phases, in one process:

1. kernels: every Pallas kernel the model can select, compiled (never
   interpreted) at the widths of chatglm3-6b (granite-moe-3b-a800m for
   `moe_gemm`, mamba2-370m for `ssd_scan`), each held to its ``ref.py``
   oracle under ``TOL``;
2. serve bf16: chatglm3-6b at its full published width (28 layers, bf16,
   random weights from a seed) through `repro.launch.serve.main` — router,
   continuous-batching scheduler, resident paged KV pool;
3. serve int8: the same with int8 weights, where the fused dequant-matmul
   kernel selects itself.

Earlier lines report the device, the JAX version, and per phase the wall and
compile seconds, the compile cache's hits and writes, and the device's peak
bytes. The last line of stdout is one JSON object, printed only when every
phase passed. Without a TPU, or when a phase fails, the script exits non-zero
and prints no such line.
"""
from __future__ import annotations

import gc
import json
import math
import sys
import time
from pathlib import Path

SEED = 0
ARCH = "chatglm3-6b"
REQUESTS, SAMPLES, PROMPT_LEN, MAX_NEW = 8, 2, 256, 32
KV_BLOCK_SIZE, KV_BLOCKS_MAX = 16, 512
# device bytes kept free beside the weights and the KV pool for the step
# programs' activations (prefill of 8 x 256 tokens at d_ff 13696 needs a few
# hundred MB; logits over the 65024-token vocabulary about as much again)
ACTIVATION_HEADROOM = 1.5e9
# max |kernel - oracle| / max |oracle|. Inputs are bf16 (f32 for ssd_scan);
# kernel and oracle both accumulate in f32 and differ only in summation order
# and in how the MXU rounds f32 operands, plus one bf16 rounding of the
# output (2^-8 relative): 1e-2 is about 2.5 output ulps at the largest
# element, and far below the error of a wrong mask, block or scale.
TOL = 1e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileStats:
    """Counts, from the moment it is made, the backend compiles of this
    process and their seconds, and the persistent cache's hits and writes
    (a write is a miss that compiled long enough to be kept)."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def as_dict(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}


class Phases:
    """Wall seconds, compile seconds and cache events per phase."""

    def __init__(self, stats, device):
        self.stats, self.device = stats, device

    def run(self, name, fn):
        before = self.stats.as_dict()
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        after = self.stats.as_dict()
        peak = self.device.memory_stats().get("peak_bytes_in_use")
        print(f"[phase] {name}: wall_s={wall} "
              f"compile_s={after['compile_s'] - before['compile_s']} "
              f"compiles={after['compiles'] - before['compiles']} "
              f"cache_hits={after['cache_hits'] - before['cache_hits']} "
              f"cache_writes={after['cache_writes'] - before['cache_writes']} "
              f"peak_bytes_in_use={peak}", flush=True)
        return out


# ------------------------------------------------------------------ kernels

def _kernel_cases():
    """(name, selecting wrapper, args, oracle) at real widths, from SEED."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.decode_attention.ops import (
        decode_attention_cache, decode_attention_ref, paged_decode_attention,
        paged_decode_attention_ref)
    from repro.kernels.dequant_matmul.ops import (dequant_matmul,
                                                  dequant_matmul_int4_ref,
                                                  dequant_matmul_int8_ref)
    from repro.kernels.flash_attention.ops import (flash_attention,
                                                   flash_attention_ref)
    from repro.kernels.moe_gemm.ops import moe_gemm, moe_gemm_ref
    from repro.kernels.ssd_scan.ops import ssd_chunk, ssd_chunk_ref
    from repro.quant import quantize_int4, quantize_int8

    glm = get_config(ARCH)
    H, Hkv, D = glm.n_heads, glm.n_kv_heads, glm.hd
    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.key(SEED), 32))
    rng = np.random.default_rng(SEED)

    def normal(shape, dtype=bf):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    cases = []
    S = 1024
    cases.append(("flash_attention", flash_attention,
                  (normal((1, S, H, D)), normal((1, S, Hkv, D)),
                   normal((1, S, Hkv, D))), flash_attention_ref))

    # paged decode: 8 sequences of up to 32 logical blocks, scattered over a
    # 384-block pool; slots past each sequence's length are empty (-1)
    B, bs, P, nb = 8, KV_BLOCK_SIZE, 384, 32
    table = rng.permutation(P)[:B * nb].reshape(B, nb).astype(np.int32)
    lengths = rng.integers(nb * bs // 2, nb * bs, size=B)
    pos_pool = np.full((P, bs), -1, np.int32)
    for b in range(B):
        p = np.arange(nb * bs).reshape(nb, bs)
        pos_pool[table[b]] = np.where(p < lengths[b], p, -1)
    q_pos = (lengths - 1).astype(np.int32)
    cases.append(("paged_decode_attention", paged_decode_attention,
                  (normal((B, 1, H, D)), normal((P, bs, Hkv, D)),
                   normal((P, bs, Hkv, D)), jnp.asarray(pos_pool),
                   jnp.asarray(table), jnp.asarray(q_pos)),
                  paged_decode_attention_ref))

    W = 1024
    lengths = rng.integers(W // 2, W, size=B)
    pos = np.where(np.arange(W)[None] < lengths[:, None], np.arange(W)[None],
                   -1).astype(np.int32)
    cases.append(("decode_attention_cache", decode_attention_cache,
                  (normal((B, 1, H, D)), normal((B, W, Hkv, D)),
                   normal((B, W, Hkv, D)), jnp.asarray(pos),
                   jnp.asarray((lengths - 1).astype(np.int32))),
                  decode_attention_ref))

    w = normal((glm.d_model, glm.d_ff), jnp.float32)
    x = normal((16, glm.d_model))
    cases.append(("dequant_matmul[int8]", dequant_matmul,
                  (x, *quantize_int8(w)), dequant_matmul_int8_ref))
    cases.append(("dequant_matmul[int4]", dequant_matmul,
                  (x, *quantize_int4(w, 32)), dequant_matmul_int4_ref))
    del w

    gr = get_config("granite-moe-3b-a800m")
    E, C = gr.moe.n_experts, 128
    cases.append(("moe_gemm", moe_gemm,
                  (normal((E, C, gr.d_model)),
                   normal((E, gr.d_model, gr.expert_ff()))), moe_gemm_ref))

    mb = get_config("mamba2-370m")
    s = mb.ssm
    Hs, Q, nc = s.expand * mb.d_model // s.headdim, s.chunk, 4
    dt = jax.nn.softplus(normal((1, nc, Q, Hs), jnp.float32))
    A = -jnp.exp(normal((Hs,), jnp.float32))
    dA = dt * A
    cases.append(("ssd_scan", ssd_chunk,
                  (normal((1, nc, Q, Hs, s.headdim), jnp.float32), dt, dA,
                   jnp.cumsum(dA, axis=2),
                   normal((1, nc, Q, Hs, s.d_state), jnp.float32),
                   normal((1, nc, Q, Hs, s.d_state), jnp.float32)),
                  _ssd_ref_model_layout(ssd_chunk_ref)))
    return cases


def _ssd_ref_model_layout(ssd_chunk_ref):
    """The ssd oracle takes the kernel layout (B*H, nc, Q, ...); wrap it to
    the model layout `ssd_chunk` takes and returns."""
    import jax.numpy as jnp

    def ref(xc, dtc, dA, dA_cs, Bc, Cc):
        B, nc, Q, H, P = xc.shape

        def to_bh(a):
            a = a if a.ndim == 5 else a[..., None]
            return jnp.moveaxis(a, 3, 1).reshape(B * H, nc, Q, a.shape[-1])

        y, st = ssd_chunk_ref(to_bh(xc), to_bh(dtc), to_bh(dA), to_bh(dA_cs),
                              to_bh(Bc), to_bh(Cc))
        N = Bc.shape[-1]
        return (jnp.moveaxis(y.reshape(B, H, nc, Q, P), 1, 3),
                st.reshape(B, H, nc, P, N).transpose(0, 2, 1, 3, 4))
    return ref


def kernels_phase() -> None:
    import jax
    import numpy as np

    from repro.kernels.platform import interpret_mode

    check(interpret_mode() is False, "kernels would run in interpret mode")
    print(f"[kernels] tolerance: max|kernel - oracle| / max|oracle| <= {TOL}")
    for name, fn, args, oracle in _kernel_cases():
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no Mosaic kernel in the compiled program")
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        errs = []
        for o, r in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
            o = np.asarray(o, np.float32)
            r = np.asarray(r, np.float32)
            check(o.shape == r.shape, f"{name}: shape {o.shape} != {r.shape}")
            check(bool(np.isfinite(o).all()), f"{name}: non-finite output")
            errs.append(float(np.abs(o - r).max() / np.abs(r).max()))
        err = max(errs)
        shapes = " ".join("x".join(map(str, a.shape)) for a in args)
        print(f"[kernel] {name}: compiled in {compile_s}s, first call "
              f"{run_s}s, rel_err={err} (tol {TOL}) inputs {shapes}",
              flush=True)
        check(err <= TOL, f"{name}: rel_err {err} > {TOL}")


# -------------------------------------------------------------------- serve

def kv_blocks_for(device) -> int:
    """Blocks of the resident pool: what the device holds beside the
    weights (the jitted init's output bytes, from ``memory_analysis``), the
    activation headroom and a second copy of the pool (the step programs
    return a new pool), capped at ``KV_BLOCKS_MAX``."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import Model
    from repro.models.cache import paged_cache_bytes

    cfg = get_config(ARCH)
    mem = jax.jit(Model(cfg, dtype=jnp.bfloat16).init).lower(
        jax.random.key(SEED)).compile().memory_analysis()
    weights = mem.output_size_in_bytes
    limit = device.memory_stats()["bytes_limit"]
    block = paged_cache_bytes(cfg, 1, KV_BLOCK_SIZE)
    fit = int((limit - weights - ACTIVATION_HEADROOM) // (2 * block))
    blocks = min(KV_BLOCKS_MAX, fit)
    print(f"[budget] bytes_limit={limit} weights={weights} "
          f"init_temp={mem.temp_size_in_bytes} block_bytes={block} "
          f"headroom={ACTIVATION_HEADROOM:.0f} -> kv_blocks={blocks} "
          f"(pool {blocks * block} bytes)", flush=True)
    check(blocks >= REQUESTS * SAMPLES * math.ceil(
        (PROMPT_LEN + MAX_NEW) / KV_BLOCK_SIZE),
        f"only {blocks} KV blocks fit beside the weights")
    return blocks


def serve_phase(kv_blocks: int, quant: str) -> None:
    import numpy as np

    from repro.configs import get_config
    from repro.launch import serve

    argv = ["--arch", ARCH, "--router", "--kv-pool",
            "--kv-blocks", str(kv_blocks),
            "--kv-block-size", str(KV_BLOCK_SIZE),
            "--requests", str(REQUESTS), "--samples", str(SAMPLES),
            "--prompt-len", str(PROMPT_LEN), "--max-new", str(MAX_NEW),
            "--quant", quant]
    print(f"[serve] repro.launch.serve.main({' '.join(argv)})", flush=True)
    results = serve.main(argv)
    vocab = get_config(ARCH).vocab_size
    check(len(results) == REQUESTS,
          f"{quant}: {len(results)} of {REQUESTS} requests completed")
    tokens = sum(r.decode_tokens for r in results)
    check(tokens == REQUESTS * SAMPLES * MAX_NEW,
          f"{quant}: decode_tokens {tokens} != "
          f"{REQUESTS} x {SAMPLES} x {MAX_NEW}")
    for r in results:
        check(len(r.samples) == SAMPLES, f"{quant}: {len(r.samples)} samples")
        check(all(math.isfinite(lp) for lp in r.logprobs),
              f"{quant}: non-finite logprob {r.logprobs}")
        for s in r.samples:
            s = np.asarray(s)
            check(s.shape == (MAX_NEW,), f"{quant}: sample shape {s.shape}")
            check(bool(((s >= 0) & (s < vocab)).all()),
                  f"{quant}: token outside [0, {vocab})")
    lps = [lp for r in results for lp in r.logprobs]
    print(f"[serve] {quant}: {len(results)} requests, {tokens} tokens, "
          f"mean logprob {sum(lps) / len(lps)}", flush=True)
    del results
    gc.collect()      # the backend holds the weights through jit closures
    import jax
    live = sum(a.nbytes for a in jax.live_arrays())
    print(f"[serve] {quant}: {live} bytes of arrays still live", flush=True)


def main() -> int:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{device.platform!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    stats = CompileStats()
    print(f"[device] kind={device.device_kind} platform={device.platform} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={enable_compile_cache()}", flush=True)
    phases = Phases(stats, device)
    phases.run("kernels", kernels_phase)
    kv_blocks = phases.run("budget", lambda: kv_blocks_for(device))
    phases.run("serve_bf16", lambda: serve_phase(kv_blocks, "bf16"))
    phases.run("serve_int8", lambda: serve_phase(kv_blocks, "int8"))
    print(f"[compile] total {stats.as_dict()}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
