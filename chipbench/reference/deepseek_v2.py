"""DeepSeek-V2 (arXiv:2405.04434), as DeepSeek-V2-Lite publishes it: the
benchmark's weights, and a plain float32 reference forward over them.

Weights are the benchmark's, not the program's: every tensor is drawn from
``(seed, layer, tensor)`` alone, and every routed expert's from ``(seed,
layer, published expert id, tensor)``, so the four expert-parallel shares
of one seed are one model, and the reference regenerates one layer at a
time what the harness generated for the whole model in one call and loaded
into the program (`program_tree`). Linear weights are normal with standard
deviation ``1/sqrt(d_in)``, norm scales ``1 + 0.1 * normal``, the embedding
normal with deviation 0.02; all are rounded to bfloat16, the type they are
served in.

The forward follows the published block, un-absorbed: RMSNorm; ``q_proj``;
``kv_a_proj_with_mqa`` split into the latent and the shared rope key,
RMSNorm on the latent (``kv_a_layernorm``), ``kv_b_proj`` to per-head
``k_nope`` and ``v``; YaRN rotary embedding on ``q_pe`` and ``k_pe``
(frequencies blended over the correction range, cos/sin times
``mscale / mscale_all_dim``, softmax scale ``q_head_dim ** -0.5`` times
``mscale_all_dim``'s factor squared); causal softmax attention; ``o_proj``;
residual; RMSNorm; then layer 0's dense SwiGLU MLP, or, in every later
layer, the MoE: float32 softmax router over all published routed experts,
greedy top-k, gates renormalized only where ``norm_topk_prob``, times
``routed_scaling_factor``; the routed experts this share holds, each
weighted by its gate (zero where the token did not pick it), plus the
shared experts; residual. A final RMSNorm and an untied output head.

Departures, shared with the program: the rotary embedding pairs dimension
``i`` with ``i + 32`` of the rope part, where the published code pairs
``2i`` with ``2i + 1``; the two are the same function up to a fixed
permutation of the ``q_proj`` and ``kv_a_proj_with_mqa`` rope columns,
which random weights do not distinguish. Only the held routed experts are
computed (``n_routed_experts`` of the configuration, from
``expert_parallel.held_expert_offset``): the other chips' experts' part of
each MoE layer is left out, as a chip of the deployment leaves it to them.

Everything is computed in float32 with matmul precision ``HIGHEST``;
attention runs in blocks of queries so that 16k-token items fit. Weight
formats and control precisions are those of `chipbench.reference.chatglm`,
applied to every linear layer (router and experts included); the
embedding and the output head stay bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.chatglm import (FORMATS, HIGHEST, PRECISIONS,
                                         root_key)

__all__ = ["Shape", "root_key", "gen_model", "program_tree", "readings",
           "PRECISIONS"]

ATTN_TENSORS = ("ln1", "q_proj", "kv_a", "kv_norm", "kv_b", "o_proj", "ln2")
DENSE_TENSORS = ("gate", "up", "down")
MOE_TENSORS = ("router", "sh_gate", "sh_up", "sh_down")
EXPERT_TENSORS = ("gate", "up", "down")
ATTN_LINEARS = ("q_proj", "w_dkv", "w_krope", "w_uk", "w_uv", "o_proj")
NORMS = ("ln1", "ln2", "kv_norm", "final_norm")
QUERY_BLOCK = 256


class Shape:
    def __init__(self, cfg: dict):
        self.L = cfg["num_hidden_layers"]
        self.D = cfg["hidden_size"]
        self.H = cfg["num_attention_heads"]
        self.V = cfg["vocab_size"]
        self.F = cfg["intermediate_size"]
        self.Fe = cfg["moe_intermediate_size"]
        self.E = cfg["expert_parallel"]["published_n_routed_experts"]
        self.held = cfg["n_routed_experts"]
        self.offset = cfg["expert_parallel"]["held_expert_offset"]
        self.K = cfg["num_experts_per_tok"]
        self.n_shared = cfg["n_shared_experts"]
        self.dense = cfg["first_k_dense_replace"]
        self.r = cfg["kv_lora_rank"]
        self.nd = cfg["qk_nope_head_dim"]
        self.rd = cfg["qk_rope_head_dim"]
        self.vd = cfg["v_head_dim"]
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.norm_topk = bool(cfg["norm_topk_prob"])
        self.route_scale = float(cfg["routed_scaling_factor"])
        rs = cfg["rope_scaling"]
        self.yarn = (float(rs["factor"]),
                     int(rs["original_max_position_embeddings"]),
                     float(rs["beta_fast"]), float(rs["beta_slow"]),
                     float(rs["mscale"]), float(rs["mscale_all_dim"]))

    def __hash__(self):
        return hash(tuple(sorted(self.__dict__.items())))

    def __eq__(self, other):
        return self.__dict__ == other.__dict__

    def attn_shapes(self) -> Dict[str, Tuple[int, ...]]:
        D, H, r = self.D, self.H, self.r
        return {"ln1": (D,), "q_proj": (D, H * (self.nd + self.rd)),
                "kv_a": (D, r + self.rd), "kv_norm": (r,),
                "kv_b": (r, H * (self.nd + self.vd)),
                "o_proj": (H * self.vd, D), "ln2": (D,)}

    def dense_shapes(self) -> Dict[str, Tuple[int, ...]]:
        return {"gate": (self.D, self.F), "up": (self.D, self.F),
                "down": (self.F, self.D)}

    def moe_shapes(self) -> Dict[str, Tuple[int, ...]]:
        fs = self.Fe * self.n_shared
        return {"router": (self.D, self.E), "sh_gate": (self.D, fs),
                "sh_up": (self.D, fs), "sh_down": (fs, self.D)}

    def expert_shapes(self) -> Dict[str, Tuple[int, ...]]:
        return {"gate": (self.D, self.Fe), "up": (self.D, self.Fe),
                "down": (self.Fe, self.D)}

    def held_ids(self) -> np.ndarray:
        return np.arange(self.offset, self.offset + self.held)


# ------------------------------------------------------------------ weights

def _draw(key, name: str, shape: Tuple[int, ...]) -> jax.Array:
    z = jax.random.normal(key, shape, jnp.float32)
    if name in NORMS:
        w = 1.0 + 0.1 * z
    elif name == "embed":
        w = 0.02 * z
    else:
        w = z / np.sqrt(shape[0])
    return w.astype(jnp.bfloat16)


def _tensors(key, names, shapes) -> Dict[str, jax.Array]:
    return {n: _draw(jax.random.fold_in(key, i), n, shapes[n])
            for i, n in enumerate(names)}


def _layer_key(key, layer):
    return jax.random.fold_in(jax.random.fold_in(key, 1), layer)


def gen_attn(key, layer, shape: Shape) -> Dict[str, jax.Array]:
    """Layer ``layer``'s attention and norms (bf16); ``layer`` may be
    traced. ``kv_a_proj_with_mqa`` is drawn whole and split into the
    latent's columns (``w_dkv``) and the rope key's (``w_krope``);
    ``kv_b_proj`` drawn whole and split per head into ``w_uk`` and
    ``w_uv``."""
    t = _tensors(_layer_key(key, layer), ATTN_TENSORS, shape.attn_shapes())
    r, H, nd, vd = shape.r, shape.H, shape.nd, shape.vd
    kv_a = t.pop("kv_a")
    kv_b = t.pop("kv_b").reshape(r, H, nd + vd)
    t.update(w_dkv=kv_a[:, :r], w_krope=kv_a[:, r:],
             w_uk=kv_b[..., :nd].reshape(r, H * nd),
             w_uv=kv_b[..., nd:].reshape(r, H * vd))
    return t


def gen_dense(key, layer, shape: Shape) -> Dict[str, jax.Array]:
    return _tensors(jax.random.fold_in(_layer_key(key, layer), 50),
                    DENSE_TENSORS, shape.dense_shapes())


def gen_moe(key, layer, shape: Shape) -> Dict[str, jax.Array]:
    """Router and shared experts of MoE layer ``layer``."""
    return _tensors(jax.random.fold_in(_layer_key(key, layer), 60),
                    MOE_TENSORS, shape.moe_shapes())


def gen_expert(key, layer, expert, shape: Shape) -> Dict[str, jax.Array]:
    """Routed expert ``expert`` (its published id) of layer ``layer``."""
    ek = jax.random.fold_in(
        jax.random.fold_in(_layer_key(key, layer), 70), expert)
    return _tensors(ek, EXPERT_TENSORS, shape.expert_shapes())


def gen_held(key, layer, shape: Shape) -> Dict[str, jax.Array]:
    """The held experts of layer ``layer``, stacked on a leading axis."""
    return jax.vmap(lambda e: gen_expert(key, layer, e, shape))(
        jnp.asarray(shape.held_ids()))


def gen_embed(key, shape: Shape) -> jax.Array:
    return _draw(jax.random.fold_in(key, 0), "embed", (shape.V, shape.D))


def gen_final_norm(key, shape: Shape) -> jax.Array:
    return _draw(jax.random.fold_in(key, 2), "final_norm", (shape.D,))


def gen_head(key, shape: Shape) -> jax.Array:
    return _draw(jax.random.fold_in(key, 3), "head", (shape.D, shape.V))


def gen_model(key: jax.Array, shape: Shape) -> Dict:
    """Every tensor; the MoE layers stacked on a leading axis (one call, on
    the device under jit)."""
    dense_ids = jnp.arange(shape.dense)
    moe_ids = jnp.arange(shape.dense, shape.L)
    return {
        "embed": gen_embed(key, shape),
        "dense": {**jax.vmap(lambda l: gen_attn(key, l, shape))(dense_ids),
                  **jax.vmap(lambda l: gen_dense(key, l, shape))(dense_ids)},
        "moe": {**jax.vmap(lambda l: gen_attn(key, l, shape))(moe_ids),
                **jax.vmap(lambda l: gen_moe(key, l, shape))(moe_ids),
                "experts": jax.vmap(lambda l: gen_held(key, l, shape))(
                    moe_ids)},
        "final_norm": gen_final_norm(key, shape),
        "head": gen_head(key, shape),
    }


def _program_attn(t: Dict) -> Dict:
    return {"wq": {"w": t["q_proj"]}, "w_dkv": {"w": t["w_dkv"]},
            "w_krope": {"w": t["w_krope"]}, "w_uk": {"w": t["w_uk"]},
            "w_uv": {"w": t["w_uv"]}, "wo": {"w": t["o_proj"]},
            "kv_norm": {"scale": t["kv_norm"]}}


def program_tree(w: Dict) -> Dict:
    """The generated tensors in the parameter layout of the program's
    `Model` (a loader, as for a checkpoint): the dense layers unrolled as
    its prefix, then one scanned MoE layer stacked over depth."""
    d, m = w["dense"], w["moe"]
    prefix = [{
        "ln1": {"scale": d["ln1"][i]},
        "attn": _program_attn({k: v[i] for k, v in d.items()}),
        "ln2": {"scale": d["ln2"][i]},
        "mlp": {"gate": {"w": d["gate"][i]}, "up": {"w": d["up"][i]},
                "down": {"w": d["down"][i]}},
    } for i in range(d["ln1"].shape[0])]
    ex = m["experts"]
    return {
        "embed": {"table": w["embed"]},
        "prefix": prefix,
        "blocks": {"l0": {
            "ln1": {"scale": m["ln1"]},
            "attn": _program_attn(m),
            "ln2": {"scale": m["ln2"]},
            "moe": {"router": {"w": m["router"]},
                    "gate": ex["gate"], "up": ex["up"], "down": ex["down"],
                    "shared": {"gate": {"w": m["sh_gate"]},
                               "up": {"w": m["sh_up"]},
                               "down": {"w": m["sh_down"]}}},
        }},
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": {"w": w["head"]},
    }


# ---------------------------------------------------------------- rope

def yarn_inv_freq(shape: Shape) -> np.ndarray:
    """YaRN's blended inverse frequencies of the rope part (float64)."""
    factor, orig, beta_fast, beta_slow, _, _ = shape.yarn
    dim, base = shape.rd, shape.theta
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor

    def corr(turns):
        return dim * np.log(orig / (turns * 2 * np.pi)) / (2 * np.log(base))

    low = max(int(np.floor(corr(beta_fast))), 0)
    high = min(int(np.ceil(corr(beta_slow))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high - low) if high > low else 0.001), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def yarn_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * np.log(scale) + 1.0 if scale > 1 else 1.0


def softmax_scale(shape: Shape) -> float:
    factor, _, _, _, _, mscale_all = shape.yarn
    m = yarn_mscale(factor, mscale_all)
    return (shape.nd + shape.rd) ** -0.5 * m * m


def _rope(x, pos, shape: Shape):
    """x (B, T, heads, rd): pairs i with i + rd/2, YaRN frequencies."""
    factor, _, _, _, mscale, mscale_all = shape.yarn
    half = shape.rd // 2
    inv = jnp.asarray(yarn_inv_freq(shape), jnp.float32)
    ang = pos[:, :, None, None].astype(jnp.float32) * inv
    amp = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
    c, s = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


# ---------------------------------------------------------------- weights in
# a precision

def _fmt(prec: str):
    return FORMATS[PRECISIONS[prec][0]]


def _as_prec(w: Dict, prec: str, linears: Sequence[str]) -> Dict:
    fmt = _fmt(prec)
    return {n: (fmt(v) if n in linears else v.astype(jnp.float32))
            for n, v in w.items()}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dense_weights(key, layer, shape: Shape, prec: str) -> Dict:
    w = {**gen_attn(key, layer, shape), **gen_dense(key, layer, shape)}
    return _as_prec(w, prec, ATTN_LINEARS + DENSE_TENSORS)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _moe_weights(key, layer, shape: Shape, prec: str) -> Dict:
    w = _as_prec({**gen_attn(key, layer, shape),
                  **gen_moe(key, layer, shape)}, prec,
                 ATTN_LINEARS + MOE_TENSORS)
    fmt = _fmt(prec)
    w["experts"] = {n: jax.vmap(fmt)(v)
                    for n, v in gen_held(key, layer, shape).items()}
    return w


# ------------------------------------------------------------------ forward

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _attention(w: Dict, h: jax.Array, shape: Shape, mm) -> jax.Array:
    """RMSNorm, MLA (un-absorbed), residual, over h (B, T, D) causal from
    position 0; queries in blocks of `QUERY_BLOCK`."""
    B, T, _ = h.shape
    H, nd, rd, vd = shape.H, shape.nd, shape.rd, shape.vd
    x = _rms(h, w["ln1"], shape.eps)
    q = mm(x, w["q_proj"]).reshape(B, T, H, nd + rd)
    c = _rms(mm(x, w["w_dkv"]), w["kv_norm"], shape.eps)
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    q = jnp.concatenate([q[..., :nd], _rope(q[..., nd:], pos, shape)], -1)
    k_pe = _rope(mm(x, w["w_krope"])[:, :, None], pos, shape)
    k = jnp.concatenate([mm(c, w["w_uk"]).reshape(B, T, H, nd),
                         jnp.broadcast_to(k_pe, (B, T, H, rd))], -1)
    v = mm(c, w["w_uv"]).reshape(B, T, H, vd)
    scale = softmax_scale(shape)
    nb = -(-T // QUERY_BLOCK)
    qb = jnp.pad(q, ((0, 0), (0, nb * QUERY_BLOCK - T), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(B, nb, QUERY_BLOCK, H, nd + rd), 1, 0)

    def block(args):
        qi, i = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HIGHEST) * scale
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(qpos[:, None] >= jnp.arange(T)[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (qb, jnp.arange(nb)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, nb * QUERY_BLOCK, H * vd)[:, :T]
    return h + mm(o, w["o_proj"])


def _matmul(prec: str):
    act = PRECISIONS[prec][1] or (lambda x: x)

    def mm(x, w):
        return jnp.matmul(act(x), w, precision=HIGHEST)
    return mm


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dense_layer(w: Dict, h: jax.Array, shape: Shape, prec: str):
    mm = _matmul(prec)
    h = _attention(w, h, shape, mm)
    x = _rms(h, w["ln2"], shape.eps)
    return h + _swiglu(x, w["gate"], w["up"], w["down"], mm)


def route(x: jax.Array, router: jax.Array, shape: Shape, mm):
    """Softmax over every published routed expert, greedy top-k: gates
    (..., E) of each expert, zero where the token did not pick it."""
    probs = jax.nn.softmax(mm(x, router), axis=-1)
    top, idx = jax.lax.top_k(probs, shape.K)
    if shape.norm_topk:
        top = top / top.sum(-1, keepdims=True)
    top = top * shape.route_scale
    return jnp.sum(jax.nn.one_hot(idx, shape.E) * top[..., None], axis=-2)


def moe(w: Dict, x: jax.Array, shape: Shape, mm) -> jax.Array:
    """The held routed experts, each weighted by its gate, plus the shared
    experts, over x (..., D)."""
    gates = route(x, w["router"], shape, mm)
    ids = jnp.asarray(shape.held_ids())

    def one(y, e):
        ex = {n: w["experts"][n][e] for n in EXPERT_TENSORS}
        g = gates[..., ids[e]][..., None]
        return y + g * _swiglu(x, ex["gate"], ex["up"], ex["down"], mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(shape.held))
    return y + _swiglu(x, w["sh_gate"], w["sh_up"], w["sh_down"], mm)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _moe_layer(w: Dict, h: jax.Array, shape: Shape, prec: str):
    mm = _matmul(prec)
    h = _attention(w, h, shape, mm)
    return h + moe(w, _rms(h, w["ln2"], shape.eps), shape, mm)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


@jax.jit
def _head_lps(h, norm, head, eps, rows, served):
    """At each ``rows`` position of h (B, T, D): the output head's
    log-probability (``log_softmax`` at temperature 1) of ``served``."""
    x = jnp.take_along_axis(h, rows[:, :, None], axis=1)
    x = _rms(x, norm.astype(jnp.float32), eps)
    logits = jnp.matmul(x, head.astype(jnp.float32), precision=HIGHEST)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, served[:, :, None], -1)[..., 0]


def readings(cfg: dict, seed_words: Sequence[int],
             items: Sequence[Tuple[np.ndarray, int]], fmt: str,
             control_fmts: Sequence[str] = (), max_batch: int = 4
             ) -> List[Dict[str, np.ndarray]]:
    """For each ``(tokens, first)``: ``tokens`` is a prompt followed by its
    served tokens, the first at index ``first``. Returns per item ``lp``:
    for each served token, the reference's log-probability of it after the
    tokens before it; for each precision ``f`` of ``control_fmts``
    (`PRECISIONS`), also ``lp:f``: the same computed in precision ``f``.

    Items are run in groups of equal length, layer by layer, so the device
    holds one layer's weights and the groups' hidden states at a time."""
    shape = Shape(cfg)
    key = root_key(seed_words)
    table = jax.jit(gen_embed, static_argnums=1)(key, shape)
    groups: Dict[int, List[int]] = {}
    for i, (toks, _) in enumerate(items):
        groups.setdefault(len(toks), []).append(i)
    batches = [idx[j:j + max_batch] for idx in groups.values()
               for j in range(0, len(idx), max_batch)]
    streams = ["ref"] + list(control_fmts)
    hs = {(s, b): _embed(table, jnp.asarray(np.stack(
        [items[i][0] for i in batch]).astype(np.int32)))
        for s in streams for b, batch in enumerate(batches)}
    del table
    for layer in range(shape.L):
        dense = layer < shape.dense
        for s in streams:
            prec = fmt if s == "ref" else s
            w = (_dense_weights if dense else _moe_weights)(
                key, layer, shape, prec)
            for b in range(len(batches)):
                hs[(s, b)] = (_dense_layer if dense else _moe_layer)(
                    w, hs[(s, b)], shape, prec)
            del w
    norm = jax.jit(gen_final_norm, static_argnums=1)(key, shape)
    head = jax.jit(gen_head, static_argnums=1)(key, shape)
    out: List[Dict[str, np.ndarray]] = [dict() for _ in items]
    for b, batch in enumerate(batches):
        n = min(len(items[i][0]) - items[i][1] for i in batch)
        rows = np.stack([np.arange(items[i][1] - 1, items[i][1] - 1 + n)
                         for i in batch]).astype(np.int32)
        served = np.stack([items[i][0][items[i][1]:items[i][1] + n]
                           for i in batch]).astype(np.int32)
        rows, served = jnp.asarray(rows), jnp.asarray(served)
        for s in streams:
            lp = np.asarray(_head_lps(hs[(s, b)], norm, head, shape.eps,
                                      rows, served))
            for j, i in enumerate(batch):
                out[i]["lp" if s == "ref" else f"lp:{s}"] = lp[j]
    return out
