"""Absorbed MLA decode over the paged latent pool, read in place.

One decode query per sequence attends in latent space (DeepSeek-V2's
absorbed form): the query's nope part folded through ``W_uk`` (``ql``, H x
r) scores against the pooled latent ``c_kv`` and its rope part (``qr``, H x
rd) against the pooled rope key; the softmax-weighted latent is the
context (H x r), which the caller takes through ``W_uv``.

The kernel reads the pools as they were before this step wrote its token,
and takes that token (``c_new``, ``kr_new`` at ``q_pos``) as one more key:
the keys are the pool's slots with ``0 <= pos < q_pos`` and the new token,
which is the set ``0 <= pos <= q_pos`` of the pool after the write. So a
scanned model hands it the whole stack of pools, which XLA leaves in HBM,
and the layer index: a layer's pool after the write is a scan temporary
that XLA stages in VMEM and would copy back out for the kernel.

Design:
  * The latent pool stays in HBM. A sequence's context is read in chunks
    of ``chunk`` logical blocks through the block table, into
    double-buffered VMEM; the next chunk's copies, or the next sequence's
    first chunk, are in flight while this one computes. A run of
    ``run_blocks`` logical blocks whose physical blocks are consecutive (an
    allocator hands a long prompt consecutive blocks) is one DMA a pool
    (latent, rope key), any other run one DMA a block and pool: each DMA
    costs scalar work to start, which 16-slot blocks would pay every 16
    positions. A chunk is waited for whole, as the DMA semaphore counts
    bytes.
  * Grid (B,): one step per sequence, its chunks in a loop whose length
    follows ``q_pos``. Position p lands in logical block ``p // bs``, so a
    block past the one holding ``q_pos - 1`` holds nothing the query may
    see and is neither copied nor computed. The last chunk's slots past
    that block re-read it and are masked by the positions of the blocks
    they stand for. Each sequence reads its own blocks, shared prefix
    blocks included: no read serves two sequences.
  * Mosaic copies a block only in whole 128-lane rows, and XLA stores the
    rope pool (64 lanes) with the block index minor: `latent_pool_view`
    lays the rope stack out as (L, P, bs, 128), zero-padded, once for a
    step's layers. The positions enter as each sequence's slots in logical
    order (4 bytes a slot), gathered by XLA in the same view, one VMEM row
    per sequence.
  * Per chunk: scores ``ql @ c^T + qr @ k_rope^T`` (H x T, tokens in
    lanes), masked by the slots' positions, online softmax in float32,
    context ``p @ c`` (H x r). Both matmuls hold the chunk's latent
    stationary in the MXU and stream the H query rows, so neither needs a
    transpose; they are fed in the pool's dtype with float32 accumulation.
  * The block table (flattened), ``q_pos`` and the layer ride in SMEM by
    scalar prefetch.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
CHUNK_BLOCKS = 64            # 1,024 positions of 16-slot blocks a chunk
RUN_BLOCKS = 8               # logical blocks one DMA copies when contiguous
LANES = 128


def _kernel(tab_ref, qpos_ref, layer_ref, ql_ref, qr_ref, cn_ref, kn_ref,
            pos_ref, ckv_hbm, kr_hbm, o_ref, ckv_buf, kr_buf, sem, slot_ref,
            m_scr, l_scr, acc_scr, *, scale: float, chunk: int,
            run_blocks: int, nb: int):
    b = pl.program_id(0)
    n_seq = pl.num_programs(0)
    bs = ckv_buf.shape[2]
    T = chunk * bs
    layer = layer_ref[0]
    pools = ((ckv_hbm.at[layer], ckv_buf), (kr_hbm.at[layer], kr_buf))

    def last_block(seq):
        """Logical block of the sequence's last position before q_pos."""
        return jnp.clip((qpos_ref[seq] - 1) // bs, 0, nb - 1)

    def start(seq, i, slot):
        last = last_block(seq)

        def page(j):
            return tab_ref[seq * nb + jnp.minimum(i * chunk + j, last)]

        def copy(src, dst, n):
            for hbm, buf in pools:
                pltpu.make_async_copy(hbm.at[pl.ds(src, n)],
                                      buf.at[slot, pl.ds(dst, n)],
                                      sem.at[slot]).start()

        def run(g, n):
            """Copies of logical blocks [g, g + n) of chunk i: one DMA a pool
            where their physical blocks are consecutive, else one a block."""
            first = page(g)
            consecutive = n > 1
            for j in range(1, n):
                consecutive &= page(g + j) == first + j
            jax.lax.cond(consecutive, lambda: copy(first, g, n),
                         lambda: jax.lax.fori_loop(
                             g, g + n, lambda j, c: copy(page(j), j, 1) or c,
                             None))

        n = min(run_blocks, chunk)
        full, rest = divmod(chunk, n)
        jax.lax.fori_loop(0, full, lambda k, c: run(k * n, n) or c, 0)
        if rest:
            run(full * n, rest)

    def wait(slot):
        for hbm, buf in pools:
            pltpu.make_async_copy(hbm.at[pl.ds(0, chunk)], buf.at[slot],
                                  sem.at[slot]).wait()

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 1

    q_pos = qpos_ref[b]
    last = last_block(b)
    n_chunks = last // chunk + 1
    dt = ckv_buf.dtype
    ql = ql_ref[0].astype(dt)                                # (H, r)
    qr = qr_ref[0].astype(dt)                                # (H, 128)
    # the new token: one key at q_pos, of weight exp(0) to start with
    c_new = cn_ref[0].astype(jnp.float32)                    # (1, r)
    s_new = (jnp.sum(ql.astype(jnp.float32) * c_new, axis=1, keepdims=True)
             + jnp.sum(qr.astype(jnp.float32)
                       * kn_ref[0].astype(jnp.float32), axis=1,
                       keepdims=True))
    m_scr[...] = s_new * scale
    l_scr[...] = jnp.ones_like(l_scr)
    acc_scr[...] = jnp.broadcast_to(c_new, acc_scr.shape)

    def body(i, carry):
        """Copies chunk i + 1, or the next sequence's first chunk, and
        computes chunk i; the first sequence begins at i = -1, which only
        copies its own first chunk."""
        slot = slot_ref[0]
        nxt = 1 - slot
        more = i + 1 < n_chunks

        @pl.when(more | (b + 1 < n_seq))
        def _prefetch():
            start(jnp.where(more, b, b + 1), jnp.where(more, i + 1, 0), nxt)

        @pl.when(i >= 0)
        def _compute():
            attend(i, slot)

        slot_ref[0] = nxt
        return carry

    def attend(i, slot):
        wait(slot)
        c = ckv_buf[slot].reshape(T, -1)                     # (T, r)
        kr = kr_buf[slot].reshape(T, -1)                     # (T, 128)
        pos = pos_ref[0, :, pl.ds(pl.multiple_of(i * T, T), T)]   # (1, T)
        nt = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(ql, c, nt, preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(qr, kr, nt,
                                 preferred_element_type=jnp.float32)
        valid = (pos >= 0) & (pos < q_pos)
        s = jnp.where(valid, s * scale, NEG_INF)             # (H, T)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot(
            p.astype(dt), c, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    jax.lax.fori_loop(jnp.where(b == 0, -1, 0), n_chunks, body, 0)
    o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def latent_pool_view(c_kv: jnp.ndarray, k_rope: jnp.ndarray,
                     pos: jnp.ndarray, block_table: jnp.ndarray, *,
                     chunk: int = CHUNK_BLOCKS) -> Tuple[jnp.ndarray, ...]:
    """Stacks of latent pools before a decode step's write, c_kv (L, P,
    bs, r), k_rope (L, P, bs, rd) and pos (L, P, bs) (absolute position a
    slot, -1 = empty), as the kernel reads them; made once for a step's
    layers. The latent pool as it is; the rope pool zero-padded to whole
    lanes, (L, P, bs, 128); each sequence's slot positions in logical
    order, (L, B, 1, nb * bs) padded with empty slots to whole chunks."""
    L, P, bs, rd = k_rope.shape
    B, nb = block_table.shape
    chunk = min(chunk, nb)
    lanes = -(-rd // LANES) * LANES
    k_rope = jnp.pad(k_rope, ((0, 0), (0, 0), (0, 0), (0, lanes - rd)))
    width = -(-nb // chunk) * chunk * bs
    pos_seq = pos.astype(jnp.int32)[:, block_table].reshape(L, B, 1, nb * bs)
    pos_seq = jnp.pad(pos_seq, ((0, 0), (0, 0), (0, 0), (0, width - nb * bs)),
                      constant_values=-1)
    return c_kv, k_rope, pos_seq


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "run_blocks",
                                             "interpret"))
def paged_latent_decode_pallas(ql: jnp.ndarray, qr: jnp.ndarray,
                               c_new: jnp.ndarray, kr_new: jnp.ndarray,
                               view: Tuple[jnp.ndarray, ...],
                               layer: jnp.ndarray, block_table: jnp.ndarray,
                               q_pos: jnp.ndarray, *, scale: float,
                               chunk: int = CHUNK_BLOCKS,
                               run_blocks: int = RUN_BLOCKS,
                               interpret: bool = False) -> jnp.ndarray:
    """ql (B, H, r) and qr (B, H, rd), the absorbed decode query; c_new
    (B, r) and kr_new (B, rd), the latent and rope key the step writes at
    q_pos (B,); ``view``, `latent_pool_view` of the pools before that
    write (made with this ``chunk``), of which layer ``layer`` is read;
    block_table (B, nb) physical block per logical block. Returns the
    latent context (B, H, r) in the pool's dtype."""
    c_kv, k_rope, pos_seq = view
    B, H, r = ql.shape
    bs, lanes = k_rope.shape[2:]
    nb = block_table.shape[1]
    chunk = min(chunk, nb)
    width = pos_seq.shape[-1]
    assert width % (chunk * bs) == 0 and width >= nb * bs, (width, chunk)
    dt = c_kv.dtype
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    qr, kr_new = (jnp.pad(a, [(0, 0)] * (a.ndim - 1)
                          + [(0, lanes - a.shape[-1])]) for a in (qr, kr_new))
    if not interpret:
        # else XLA may stage the pools into VMEM ahead of the kernel: a
        # copy outside the kernel's time
        c_kv, k_rope = (pltpu.with_memory_space_constraint(a, pltpu.HBM)
                        for a in (c_kv, k_rope))

    def seq(*shape):
        return pl.BlockSpec((1, *shape), lambda b, *_: (b,) + (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[seq(H, r), seq(H, lanes), seq(1, r), seq(1, lanes),
                  pl.BlockSpec((None, 1, 1, width),
                               lambda b, tab, qp, layer: (layer[0], b, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.HBM),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=seq(H, r),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, bs, r), dt),
            pltpu.VMEM((2, chunk, bs, lanes), k_rope.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, r), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, chunk=chunk,
                          run_blocks=run_blocks, nb=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_table.astype(jnp.int32).reshape(-1), q_pos.astype(jnp.int32),
      layer, ql, qr, c_new.reshape(B, 1, r), kr_new.reshape(B, 1, lanes),
      pos_seq, c_kv, k_rope)
