"""Builds the served stack of a cell the way `repro.launch.serve` does, in
the harness's own code, and wraps the backend and the router so that every
call the scheduler makes into them is timed on the harness's clock.

Path: ``ContinuousBatchingScheduler.step`` -> ``ParetoRouter.route_batch``
-> ``ExecutionBackend.start_batch`` / ``decode_step`` over the resident
prefix pool.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench import registry
from chipbench.traffic import seed_words

# configuration key -> the program's ArchConfig attribute that must equal it
PROGRAM_SIZES = {
    "num_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "multi_query_group_num": "n_kv_heads",
    "ffn_hidden_size": "d_ff", "padded_vocab_size": "vocab_size",
    "kv_channels": "hd", "layernorm_epsilon": "norm_eps",
    "add_qkv_bias": "qkv_bias", "tie_word_embeddings": "tie_embeddings",
}


def clock() -> float:
    return time.perf_counter()


@dataclass
class Call:
    """One call into the backend or the router, on the harness clock."""
    kind: str                 # start_batch | decode_step | route_batch
    begin: float
    end: float
    n_seqs: int = 0
    batch: int = -1           # handle serial (start_batch / decode_step)
    plen: int = 0
    positions: List[int] = field(default_factory=list)  # decode: written
    hit_counts: List[int] = field(default_factory=list)  # start: per prompt
    prompt_ids: List[int] = field(default_factory=list)  # id() of prompts
    block_size: int = 16


class Recorder:
    """Calls and compile events, kept in memory until the run ends."""

    def __init__(self):
        self.calls: List[Call] = []
        self.compile_times: List[float] = []
        self.enabled = True

    def add(self, call: Call) -> None:
        if self.enabled:
            self.calls.append(call)

    def reset(self) -> None:
        self.calls = []
        self.compile_times = []


def listen_compiles(rec: Recorder) -> None:
    """Count XLA compilations and persistent-cache loads (process-wide)."""
    from jax import monitoring

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            rec.compile_times.append(clock())

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            rec.compile_times.append(clock())

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


class TimedBackend:
    """The backend, with ``start_batch`` and ``decode_step`` timed, and the
    per-token log-probabilities of each result kept (`served`); every other
    attribute is the backend's own."""

    def __init__(self, backend, rec: Recorder):
        self._b = backend
        self._rec = rec
        self._serial: Dict[int, int] = {}
        # id(GenerationResult) -> (per-sample token log-probs, batch seqs)
        self.served: Dict[int, Tuple[np.ndarray, int]] = {}

    def __getattr__(self, name):
        return getattr(self._b, name)

    def start_batch(self, prompts, n_samples, max_new, temperature, rng,
                    extras=None):
        import jax
        t0 = clock()
        with jax.profiler.TraceAnnotation("chipbench/start_batch"):
            h = self._b.start_batch(prompts, n_samples, max_new,
                                    temperature, rng, extras)
        t1 = clock()
        serial = len(self._serial)
        self._serial[id(h)] = serial
        layout = h.paged
        self._rec.add(Call(
            "start_batch", t0, t1, n_seqs=h.n_sequences, batch=serial,
            plen=h.plen, hit_counts=list(layout.hit_counts),
            prompt_ids=[id(p) for p in prompts],
            block_size=layout.block_size))
        return h

    def decode_step(self, h):
        import jax
        n = h.n_sequences - len(h.freed_seqs)
        pos = h.plen + h.step - 1
        t0 = clock()
        with jax.profiler.TraceAnnotation("chipbench/decode_step"):
            more = self._b.decode_step(h)
        t1 = clock()
        self._rec.add(Call("decode_step", t0, t1, n_seqs=n,
                           batch=self._serial.get(id(h), -1), plen=h.plen,
                           positions=[pos] * n))
        return more

    def finalize(self, h):
        lps = np.stack(h.out_lps, axis=1) if h.out_lps else \
            np.zeros((h.n_sequences, 0), np.float32)
        results = self._b.finalize(h)
        off = 0
        for r in results:
            k = len(r.samples)
            self.served[id(r)] = (lps[off:off + k], h.n_sequences)
            off += k
        return results


class TimedRouter:
    """The router, with ``route_batch`` timed."""

    def __init__(self, router, rec: Recorder):
        self._r = router
        self._rec = rec

    def __getattr__(self, name):
        return getattr(self._r, name)

    def route_batch(self, tiers, **kw):
        import jax
        t0 = clock()
        with jax.profiler.TraceAnnotation("chipbench/route_batch"):
            d = self._r.route_batch(tiers, **kw)
        self._rec.add(Call("route_batch", t0, clock(), n_seqs=len(tiers)))
        return d


@dataclass
class Stack:
    cfg: Any                  # the program's ArchConfig
    model: Any
    params: Any
    backend: Any              # ExecutionBackend
    router: Any               # ParetoRouter
    sched: Any                # ContinuousBatchingScheduler over the wrappers
    timed_backend: TimedBackend
    rec: Recorder


def arch_of(conf: dict):
    """The program's own `ArchConfig` that a configuration file names."""
    from repro.configs import get_config
    return get_config(conf["program_arch"])


def program_config(conf: dict):
    """The program's `ArchConfig` for a configuration file; raises where a
    size differs from the file."""
    arch = arch_of(conf)
    for key, attr in PROGRAM_SIZES.items():
        if getattr(arch, attr) != conf[key]:
            raise ValueError(f"program config {arch.name}: {attr}="
                             f"{getattr(arch, attr)} but {key}={conf[key]}")
    rope = conf["rope"]
    if (arch.rope_theta, arch.rope_fraction) != (rope["theta"],
                                                 rope["rotary_fraction"]):
        raise ValueError(f"program config {arch.name}: rope differs")
    return arch


def make_params(conf: dict, seed: int, model):
    """The benchmark's weights from the seed, in the program's layout and
    served format, made on the device in one jitted call."""
    import jax

    ref = registry.reference(conf["reference"])
    shape = ref.Shape(conf)
    fmt = conf["weights"]

    def make(key):
        tree = ref.program_tree(ref.gen_model(key, shape))
        if fmt != "bf16":
            from repro.quant import quantize_model
            tree = quantize_model(tree, fmt)
        return tree

    key = ref.root_key(seed_words(seed))
    want = jax.eval_shape(model.init, key)
    if fmt != "bf16":
        from repro.quant import quantize_model
        want = jax.eval_shape(lambda k: quantize_model(model.init(k), fmt),
                              key)
    got = jax.eval_shape(make, key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("generated weights do not match the program's "
                         "parameter layout")
    return jax.block_until_ready(jax.jit(make)(key))


def router_workload(conf: dict, mix) -> Any:
    from repro.core.decomposition import Workload
    from repro.quant import quant_workload
    p = mix.params
    lengths = mix.prompt_lengths
    weights = [w for _, w in p["suffixes"]]
    mean_len = int(round(sum(l * w for l, w in zip(lengths, weights))
                         / sum(weights)))
    w = Workload(batch=conf["scheduler"]["max_batch_requests"],
                 prompt_tokens=mean_len, decode_tokens=p["max_new_tokens"],
                 samples=p["n_samples"])
    return quant_workload(w, conf["weights"])


def build(conf: dict, mix, seed: int, rec: Recorder) -> Stack:
    import jax.numpy as jnp

    from repro.core import EDGE_PLATFORM, Constraints
    from repro.models import Model
    from repro.qeil2 import (PGSAMConfig, PGSAMOrchestrator, ParetoRouter,
                             default_tiers)
    from repro.serving import (ContinuousBatchingScheduler, ExecutionBackend,
                               SchedulerConfig)

    arch = program_config(conf)
    model = Model(arch, dtype=jnp.bfloat16)
    params = make_params(conf, seed, model)
    work = router_workload(conf, mix)
    orch = PGSAMOrchestrator(EDGE_PLATFORM,
                             Constraints(latency_budget_factor=None),
                             config=PGSAMConfig(seed=0, incremental=True))
    placed = [a for a in orch.pareto_frontier(arch, work) if a.mapping]
    if not placed:
        raise RuntimeError("the PGSAM frontier has no placeable point")
    base = min(a.latency_s for a in placed) / 0.9
    router = ParetoRouter(orch, arch, work, tiers=default_tiers(base))
    kv = conf["kv"]
    backend = ExecutionBackend(model, params, kv_blocks=kv["blocks"],
                               kv_block_size=kv["block_size"], kv_pool=True)
    s = conf["scheduler"]
    p = mix.params
    timed = TimedBackend(backend, rec)
    sched = ContinuousBatchingScheduler(
        timed, TimedRouter(router, rec),
        SchedulerConfig(max_batch_requests=s["max_batch_requests"],
                        max_inflight_batches=s["max_inflight_batches"],
                        max_new_tokens=p["max_new_tokens"],
                        temperature=p["temperature"],
                        seed=seed_words(seed)[0] & 0x7FFFFFFF))
    return Stack(arch, model, params, backend, router, sched, timed, rec)
