"""Per-kernel validation (deliverable c): shape/dtype sweeps asserting
allclose against the pure-jnp ref.py oracles, in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.decode_attention.decode_attention import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.ssd_scan.ops import ssd_chunk
from repro.kernels.ssd_scan.ref import ssd_chunk_ref
from repro.models.ssm import ssd_chunked


# =============================================================== flash attn
FLASH_SHAPES = [
    # (B, Sq, Sk, H, Hkv, D, window)
    (1, 64, 64, 4, 4, 32, None),
    (2, 64, 64, 4, 2, 32, None),       # GQA
    (2, 64, 64, 4, 1, 32, None),       # MQA
    (1, 100, 100, 4, 4, 64, None),     # non-multiple of block
    (2, 33, 33, 8, 2, 16, None),
    (1, 128, 128, 2, 2, 64, 32),       # sliding window
    (2, 50, 50, 4, 2, 32, 8),
]


@pytest.mark.parametrize("shape", FLASH_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(shape, dtype):
    B, Sq, Sk, H, Hkv, D, window = shape
    ks = jax.random.split(jax.random.key(42), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D), dtype)
    out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 block_q=32, block_k=32)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@given(bq=st.sampled_from([16, 32, 64]), bk=st.sampled_from([16, 32, 64]))
@settings(max_examples=9, deadline=None)
def test_flash_attention_block_size_invariance(bq, bk):
    """Property: output must not depend on the BlockSpec tiling."""
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (1, 48, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 48, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 48, 2, 32), jnp.float32)
    out = flash_attention_pallas(q, k, v, block_q=bq, block_k=bk)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# =============================================================== decode attn
DECODE_SHAPES = [
    # (B, W, H, Hkv, D, filled, window)
    (2, 64, 4, 4, 32, 64, None),
    (2, 64, 4, 2, 32, 40, None),       # partially-filled cache
    (1, 100, 8, 2, 64, 77, None),
    (2, 64, 4, 2, 32, 64, 16),         # windowed
    (1, 32, 2, 1, 16, 5, None),        # nearly-empty cache
]


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(shape, dtype):
    B, W, H, Hkv, D, filled, window = shape
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), dtype)
    kc = jax.random.normal(ks[1], (B, W, Hkv, D), dtype)
    vc = jax.random.normal(ks[2], (B, W, Hkv, D), dtype)
    pos = np.full((B, W), -1, np.int32)
    pos[:, :filled] = np.arange(filled)
    pos = jnp.asarray(pos)
    q_pos = jnp.full((B,), filled, jnp.int32)
    out = decode_attention_pallas(q, kc, vc, pos, q_pos, window=window,
                                  block_k=32)
    ref = decode_attention_ref(q, kc, vc, pos, q_pos, window=window)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attention_ring_semantics():
    """Slots hold out-of-order absolute positions (ring wraps): masking must
    follow positions, not slot order."""
    B, W, H, D = 1, 8, 2, 16
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    kc = jax.random.normal(ks[1], (B, W, H, D))
    vc = jax.random.normal(ks[2], (B, W, H, D))
    # ring after 11 writes with W=8: slots hold positions [8,9,10,3,4,5,6,7]
    pos = jnp.asarray([[8, 9, 10, 3, 4, 5, 6, 7]], jnp.int32)
    q_pos = jnp.asarray([10], jnp.int32)
    out = decode_attention_pallas(q, kc, vc, pos, q_pos, window=4, block_k=8)
    ref = decode_attention_ref(q, kc, vc, pos, q_pos, window=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ======================================================= paged latent decode
# Each case builds one layer's latent pool (block size 4) as serving leaves
# it before a decode step, the step's token, and the pool after its write.
LATENT_CASES = {
    # q_pos differs across the batch, last blocks partly filled; 3-block
    # chunks, so contexts span several chunks and the last chunk runs past
    # the table's end
    "ragged": dict(q_pos=[5, 18, 27, 9], chunk=3),
    # rows 0-2 share 3 prefix blocks of one prompt (14 tokens), each with
    # its copy-on-write copy of the partial 4th block; blocks handed out
    # in ascending order, so runs of consecutive blocks are copied whole
    "shared_prefix": dict(q_pos=[17, 20, 15, 6], chunk=3, run=2, shared=3,
                          consecutive=True),
    # empty slots (pos -1) inside the context, and row 0's slot at q_pos
    # holding a stale entry at q_pos itself (a rejected draft)
    "empty_slots": dict(q_pos=[13, 22, 7, 30], chunk=2, holes=True),
    # tables 4 blocks longer than any context: blocks of NaN latents that
    # must be neither read nor computed, one of them in a chunk the
    # context ends in, right after the context's consecutive blocks
    "long_table": dict(q_pos=[5, 18, 30, 9], chunk=3, run=3, spare=4,
                       consecutive=True),
    # every context in one chunk of the whole table, of consecutive blocks
    # copied in runs of 3, 3 and 2
    "one_chunk": dict(q_pos=[5, 18, 29, 9], chunk=8, run=3,
                      consecutive=True),
}


def _latent_case(case, dtype, B=4, H=4, r=32, rd=16, bs=4, nb=8):
    rng = np.random.default_rng(7)
    q_pos = np.asarray(case["q_pos"], np.int32)
    shared, spare = case.get("shared", 0), case.get("spare", 0)
    P = B * (nb + spare) + 2
    c = rng.standard_normal((P, bs, r)).astype(np.float32)
    kr = rng.standard_normal((P, bs, rd)).astype(np.float32)
    pos = np.full((P, bs), -1, np.int32)
    ids = iter(np.arange(P) if case.get("consecutive")
               else rng.permutation(P))
    first = [next(ids) for _ in range(shared)]
    table = np.asarray([(first if b < 3 else [])
                        + [next(ids) for _ in range(nb + spare
                                                    - (shared if b < 3
                                                       else 0))]
                        for b in range(B)], np.int32)
    for b in range(B):
        for t in range(q_pos[b]):
            pos[table[b, t // bs], t % bs] = t
        if case.get("holes"):
            pos[table[b, 1], 1:3] = -1
    if shared:   # the prompt's tokens, the same in each row's own CoW copy
        c[table[1:3, shared]] = c[table[0, shared]]
        kr[table[1:3, shared]] = kr[table[0, shared]]
    if case.get("holes"):
        pos[table[0, q_pos[0] // bs], q_pos[0] % bs] = q_pos[0]
    c[table[:, nb:]] = np.nan
    kr[table[:, nb:]] = np.nan
    ql, qr, c_new, kr_new = (rng.standard_normal(s).astype(np.float32)
                             for s in ((B, H, r), (B, H, rd), (B, r),
                                       (B, rd)))
    before = [jnp.asarray(a, d) for a, d in
              ((c, dtype), (kr, dtype), (pos, jnp.int32))]
    rows = (table[np.arange(B), q_pos // bs], q_pos % bs)
    c[rows], kr[rows], pos[rows] = c_new, kr_new, q_pos
    after = [jnp.asarray(a, d) for a, d in
             ((c, dtype), (kr, dtype), (pos, jnp.int32))]
    cast = lambda a: jnp.asarray(a, dtype)    # noqa: E731
    return (cast(ql), cast(qr), cast(c_new), cast(kr_new), before, after,
            jnp.asarray(table), jnp.asarray(q_pos))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", list(LATENT_CASES))
def test_paged_latent_decode_matches_refs(name, dtype):
    """The kernel, given the pool before the step's write and the step's
    token, against the float32 oracle over the pool after the write, and
    against the model's gathered `_latent_attend` at S == 1 (with W_uk and
    W_uv the identity, so its output is the latent context)."""
    from repro.kernels.decode_attention.paged_latent import (
        latent_pool_view, paged_latent_decode_pallas)
    from repro.kernels.decode_attention.ref import paged_latent_decode_ref
    from repro.models.attention import _latent_attend
    case = LATENT_CASES[name]
    ql, qr, c_new, kr_new, before, after, table, q_pos = _latent_case(
        case, dtype)
    B, H, r = ql.shape
    scale = 0.3
    view = latent_pool_view(*(a[None] for a in before), table,
                            chunk=case["chunk"])
    got = paged_latent_decode_pallas(
        ql, qr, c_new, kr_new, view, 0, table, q_pos, scale=scale,
        chunk=case["chunk"], run_blocks=case.get("run", 8), interpret=True)
    want = paged_latent_decode_ref(ql, qr, *after, table, q_pos, scale=scale)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    live = table[:, :8]            # the blocks the longest context spans
    eye = jnp.broadcast_to(jnp.eye(r, dtype=dtype)[:, None], (r, H, r))
    model = _latent_attend(
        ql[:, None], qr[:, None], q_pos[:, None],
        after[0][live].reshape(B, -1, r),
        after[1][live].reshape(B, -1, qr.shape[-1]),
        after[2][live].reshape(B, -1), eye, eye, scale, None)[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(model, np.float32),
                               rtol=tol, atol=tol)


# =============================================================== ssd scan
SSD_SHAPES = [
    # (B, L, H, P, N, chunk)
    (2, 32, 2, 16, 16, 8),
    (1, 64, 4, 32, 64, 16),
    (2, 24, 3, 8, 16, 8),
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_kernel_matches_ref(shape):
    B, L, H, P, N, chunk = shape
    ks = jax.random.split(jax.random.key(11), 5)
    nc, Q = L // chunk, chunk
    x = jax.random.normal(ks[0], (B, nc, Q, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, nc, Q, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    dA = dt * A[None, None, None]
    dAcs = jnp.cumsum(dA, axis=2)
    Bm = jax.random.normal(ks[3], (B, nc, Q, H, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, nc, Q, H, N), jnp.float32)

    y_k, st_k = ssd_chunk(x, dt, dA, dAcs, Bm, Cm)

    def to_bh(a, width):
        return jnp.moveaxis(a, 3, 1).reshape((B * H, nc, Q, width))
    y_r, st_r = ssd_chunk_ref(to_bh(x, P), to_bh(dt[..., None], 1),
                              to_bh(dA[..., None], 1),
                              to_bh(dAcs[..., None], 1),
                              to_bh(Bm, N), to_bh(Cm, N))
    y_r = jnp.moveaxis(y_r.reshape(B, H, nc, Q, P), 1, 3)
    st_r = st_r.reshape(B, H, nc, P, N).transpose(0, 2, 1, 3, 4)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_r),
                               rtol=1e-4, atol=1e-4)


def test_ssd_kernel_end_to_end_in_chunked_scan():
    """use_kernel=True path of ssd_chunked must equal the jnp path."""
    B, L, H, P, N = 2, 32, 2, 16, 16
    ks = jax.random.split(jax.random.key(13), 5)
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, L, H, N))
    Cm = jax.random.normal(ks[4], (B, L, H, N))
    y0, s0 = ssd_chunked(x, dt, A, Bm, Cm, chunk=8, use_kernel=False)
    y1, s1 = ssd_chunked(x, dt, A, Bm, Cm, chunk=8, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                               rtol=1e-4, atol=1e-4)
