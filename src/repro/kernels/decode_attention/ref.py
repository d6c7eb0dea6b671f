"""Pure-jnp oracle for the decode attention kernel."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_attention_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, pos: jnp.ndarray,
                         q_pos: jnp.ndarray, *,
                         scale: Optional[float] = None,
                         window: Optional[int] = None) -> jnp.ndarray:
    """q (B,1,H,D); caches (B,W,Hkv,Dv); pos (B,W); q_pos (B,)."""
    B, _, H, D = q.shape
    _, W, Hkv, Dv = v_cache.shape
    if scale is None:
        scale = D ** -0.5
    g = H // Hkv
    qg = q.reshape(B, 1, Hkv, g, D).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                   k_cache.astype(jnp.float32)) * scale
    valid = (pos >= 0) & (pos <= q_pos[:, None])
    if window is not None:
        valid &= pos > (q_pos[:, None] - window)
    s = jnp.where(valid[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, Dv).astype(q.dtype)


def paged_decode_attention_ref(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray, pos_pool: jnp.ndarray,
                               block_table: jnp.ndarray,
                               q_pos: jnp.ndarray, *,
                               scale: Optional[float] = None,
                               kv_len: Optional[int] = None) -> jnp.ndarray:
    """Oracle for the paged kernel: gather each sequence's pool blocks in
    logical order into a dense (B, nb*bs) cache view, then run the dense
    decode oracle. pools (P, bs, Hkv, D[v]); block_table (B, nb)."""
    B = q.shape[0]
    kc = k_pool[block_table].reshape(B, -1, *k_pool.shape[2:])
    vc = v_pool[block_table].reshape(B, -1, *v_pool.shape[2:])
    pc = pos_pool[block_table].reshape(B, -1)
    if kv_len is not None:
        kc, vc, pc = kc[:, :kv_len], vc[:, :kv_len], pc[:, :kv_len]
    return decode_attention_ref(q, kc, vc, pc, q_pos, scale=scale)


def paged_latent_decode_ref(ql: jnp.ndarray, qr: jnp.ndarray,
                            c_kv: jnp.ndarray, k_rope: jnp.ndarray,
                            pos: jnp.ndarray, block_table: jnp.ndarray,
                            q_pos: jnp.ndarray, *,
                            scale: float) -> jnp.ndarray:
    """Oracle for the paged latent decode kernel, in float32: gather each
    sequence's latent, rope key and positions in logical order, score
    ``ql . c + qr . k_rope`` over the slots with ``0 <= pos <= q_pos``, and
    return the softmax-weighted latent (B, H, r) in the pool's dtype.
    Masked slots contribute nothing, whatever they hold."""
    B = ql.shape[0]
    hi = jax.lax.Precision.HIGHEST
    c = c_kv[block_table].reshape(B, -1, c_kv.shape[-1]).astype(jnp.float32)
    kr = k_rope[block_table].reshape(B, -1, k_rope.shape[-1]
                                     ).astype(jnp.float32)
    p = pos[block_table].reshape(B, -1)
    valid = (p >= 0) & (p <= q_pos[:, None])
    c = jnp.where(valid[..., None], c, 0.0)
    kr = jnp.where(valid[..., None], kr, 0.0)
    s = (jnp.einsum("bhr,bkr->bhk", ql.astype(jnp.float32), c, precision=hi)
         + jnp.einsum("bhr,bkr->bhk", qr.astype(jnp.float32), kr,
                      precision=hi)) * scale
    w = jax.nn.softmax(jnp.where(valid[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bhk,bkr->bhr", w, c, precision=hi).astype(c_kv.dtype)
