"""ChatGLM (arXiv:2406.12793): the benchmark's weights, and a plain float32
reference forward over them.

Weights are the benchmark's, not the program's: every tensor is drawn from
``(seed, layer, tensor)`` alone, so the reference regenerates one layer at a
time what the harness generated for the whole model in one call and loaded
into the program (`program_tree`). Linear weights are normal with standard
deviation ``1/sqrt(d_in)``, biases normal with deviation 0.1, norm scales
``1 + 0.1 * normal``, the embedding normal with deviation 0.02; all are
rounded to bfloat16, the type they are served in.

The forward follows the published block: RMSNorm, grouped-query attention
with bias on q/k/v and rotary embedding on the first half of each head,
residual, RMSNorm, SwiGLU MLP, residual; a final RMSNorm and an untied
output head. One departure, shared with the program: the rotary embedding
pairs dimension ``i`` with ``i + 32`` of the rotated half, where the
published code pairs ``2i`` with ``2i + 1``; the two are the same function
up to a fixed permutation of the q and k projection columns, which random
weights do not distinguish.

Everything is computed in float32 with matmul precision ``HIGHEST``.
Weight-only formats are reproduced independently of the program: int8 per
output channel (scale ``absmax / 127`` over the input dimension), float8
e4m3 per output channel (scale ``absmax / 448``), int4 in groups of 32
input rows (scale ``absmax / 7``); the embedding and the output head stay
bfloat16. The controls may also compute every linear layer on inputs
rounded as the weights are (``w8a8-int8``, ``w8a8-fp8``: per token, scale
``absmax / 127`` or ``absmax / 448``), as an 8-bit matmul would.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_TENSORS = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
                 "gate", "up", "down")
LINEARS = ("wq", "wk", "wv", "wo", "gate", "up", "down")
INT4_GROUP = 32


class Shape:
    def __init__(self, cfg: dict):
        self.L = cfg["num_layers"]
        self.D = cfg["hidden_size"]
        self.H = cfg["num_attention_heads"]
        self.G = cfg["multi_query_group_num"]
        self.hd = cfg["kv_channels"]
        self.F = cfg["ffn_hidden_size"]
        self.V = cfg["padded_vocab_size"]
        self.eps = float(cfg["layernorm_epsilon"])
        self.theta = float(cfg["rope"]["theta"])
        self.rot = int(self.hd * cfg["rope"]["rotary_fraction"])

    def __hash__(self):
        return hash(tuple(sorted(self.__dict__.items())))

    def __eq__(self, other):
        return self.__dict__ == other.__dict__

    def layer_shapes(self) -> Dict[str, Tuple[int, ...]]:
        D, q, kv, F = self.D, self.H * self.hd, self.G * self.hd, self.F
        return {"ln1": (D,), "wq": (D, q), "bq": (q,), "wk": (D, kv),
                "bk": (kv,), "wv": (D, kv), "bv": (kv,), "wo": (q, D),
                "ln2": (D,), "gate": (D, F), "up": (D, F), "down": (F, D)}


# ------------------------------------------------------------------ weights

def root_key(seed_words: Sequence[int]) -> jax.Array:
    return jax.random.fold_in(jax.random.key(seed_words[0]), seed_words[1])


def _draw(key, name: str, shape: Tuple[int, ...]) -> jax.Array:
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("ln1", "ln2", "final_norm"):
        w = 1.0 + 0.1 * z
    elif name.startswith("b"):
        w = 0.1 * z
    elif name == "embed":
        w = 0.02 * z
    else:
        w = z / np.sqrt(shape[0])
    return w.astype(jnp.bfloat16)


def gen_layer(key: jax.Array, layer, shape: Shape) -> Dict[str, jax.Array]:
    """Layer ``layer``'s tensors (bf16); ``layer`` may be traced."""
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    shapes = shape.layer_shapes()
    return {n: _draw(jax.random.fold_in(lk, i), n, shapes[n])
            for i, n in enumerate(LAYER_TENSORS)}


def gen_embed(key, shape: Shape) -> jax.Array:
    return _draw(jax.random.fold_in(key, 0), "embed", (shape.V, shape.D))


def gen_final_norm(key, shape: Shape) -> jax.Array:
    return _draw(jax.random.fold_in(key, 2), "final_norm", (shape.D,))


def gen_head(key, shape: Shape) -> jax.Array:
    return _draw(jax.random.fold_in(key, 3), "head", (shape.D, shape.V))


def gen_model(key: jax.Array, shape: Shape) -> Dict:
    """Every tensor, layers stacked on a leading axis (one call, on the
    device under jit)."""
    layers = jax.vmap(lambda l: gen_layer(key, l, shape))(
        jnp.arange(shape.L))
    return {"embed": gen_embed(key, shape), "layers": layers,
            "final_norm": gen_final_norm(key, shape),
            "head": gen_head(key, shape)}


def program_tree(w: Dict) -> Dict:
    """The generated tensors in the parameter layout of the program's
    `Model` (a loader, as for a checkpoint): one scanned super-block of one
    attention layer, stacked over depth."""
    t = w["layers"]
    return {
        "embed": {"table": w["embed"]},
        "prefix": [],
        "blocks": {"l0": {
            "ln1": {"scale": t["ln1"]},
            "attn": {"wq": {"w": t["wq"], "b": t["bq"]},
                     "wk": {"w": t["wk"], "b": t["bk"]},
                     "wv": {"w": t["wv"], "b": t["bv"]},
                     "wo": {"w": t["wo"]}},
            "ln2": {"scale": t["ln2"]},
            "mlp": {"gate": {"w": t["gate"]}, "up": {"w": t["up"]},
                    "down": {"w": t["down"]}},
        }},
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": {"w": w["head"]},
    }


# ------------------------------------------------------- weight formats

def int8_weight(w: jax.Array) -> jax.Array:
    """Per output channel: scale = absmax over the input rows / 127."""
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=0), 1e-8) / 127.0
    return jnp.clip(jnp.round(wf / scale), -127, 127) * scale


def int4_weight(w: jax.Array, group: int = INT4_GROUP) -> jax.Array:
    """Groups of ``group`` input rows: scale = group absmax / 7."""
    wf = w.astype(jnp.float32)
    k, n = wf.shape
    g = wf.reshape(k // group, group, n)
    scale = jnp.maximum(jnp.max(jnp.abs(g), axis=1, keepdims=True),
                        1e-8) / 7.0
    return (jnp.clip(jnp.round(g / scale), -7, 7) * scale).reshape(k, n)


def fp8_weight(w: jax.Array) -> jax.Array:
    """float8 e4m3 per output channel: scale = absmax over the input rows /
    448, the format's largest value."""
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=0), 1e-8) / 448.0
    return (wf / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


FORMATS = {"bf16": lambda w: w.astype(jnp.float32),
           "int8": int8_weight, "fp8": fp8_weight, "int4": int4_weight}


def int8_act(x: jax.Array) -> jax.Array:
    """Per row (token): scale = absmax / 127."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-8) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def fp8_act(x: jax.Array) -> jax.Array:
    """float8 e4m3 per row (token): scale = absmax / 448."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-8) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


# a precision the reference computes in: (weight format, the format of the
# inputs of every linear layer, or None where they stay float32)
PRECISIONS = {"bf16": ("bf16", None), "int8": ("int8", None),
              "fp8": ("fp8", None), "int4": ("int4", None),
              "w8a8-int8": ("int8", int8_act), "w8a8-fp8": ("fp8", fp8_act)}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_weights(key, layer, shape: Shape, prec: str) -> Dict:
    w = gen_layer(key, layer, shape)
    fmt = FORMATS[PRECISIONS[prec][0]]
    return {n: (fmt(v) if n in LINEARS else v.astype(jnp.float32))
            for n, v in w.items()}


# ------------------------------------------------------------------ forward

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, shape: Shape):
    """x (B, T, heads, hd); rotate the first ``rot`` dims, pairing i with
    i + rot/2."""
    half = shape.rot // 2
    inv = 1.0 / (shape.theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, :, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:shape.rot]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c,
                            x[..., shape.rot:]], -1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(w: Dict, h: jax.Array, shape: Shape, prec: str) -> jax.Array:
    """One decoder layer over h (B, T, D) float32, causal from position 0,
    computed in precision ``prec`` (`PRECISIONS`)."""
    B, T, _ = h.shape
    H, G, hd = shape.H, shape.G, shape.hd
    act = PRECISIONS[prec][1] or (lambda x: x)

    def mm(x, w):
        return jnp.matmul(act(x), w, precision=HIGHEST)

    x = _rms(h, w["ln1"], shape.eps)
    q = (mm(x, w["wq"]) + w["bq"]).reshape(B, T, H, hd)
    k = (mm(x, w["wk"]) + w["bk"]).reshape(B, T, G, hd)
    v = (mm(x, w["wv"]) + w["bv"]).reshape(B, T, G, hd)
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    q, k = _rope(q, pos, shape), _rope(k, pos, shape)
    q = q.reshape(B, T, G, H // G, hd)
    s = jnp.einsum("btgjd,bsgd->bgjts", q, k, precision=HIGHEST)
    s = s / np.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgjts,bsgd->btgjd", p, v, precision=HIGHEST)
    h = h + mm(o.reshape(B, T, H * hd), w["wo"])
    x = _rms(h, w["ln2"], shape.eps)
    return h + mm(jax.nn.silu(mm(x, w["gate"])) * mm(x, w["up"]), w["down"])


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
        for s in streams:
            w = _layer_weights(key, layer, shape, fmt if s == "ref" else s)
            for b in range(len(batches)):
                hs[(s, b)] = _layer(w, hs[(s, b)], shape,
                                    fmt if s == "ref" else s)
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
