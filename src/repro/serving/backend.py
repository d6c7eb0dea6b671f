"""Execution backend: the *how* of serving, as a stepwise batch API.

Extracted from the original monolithic ``ServingEngine.generate`` so a
scheduler can interleave work across batches instead of blocking on one
call. The backend owns the jitted prefill/decode functions, the KV budget
(sequence slots, or paged blocks — see below), and static-shape bucketing;
policy (admission, batch formation, routing) lives in
`repro.serving.scheduler`.

The step API is deliberately small:

* ``start_batch`` — prefill a group of equal-length prompts (each tiled by
  its per-request sample count) and sample the first token; returns an
  `InFlightBatch` holding the KV cache and the rng stream.
* ``decode_step`` — advance an in-flight batch by one autoregressive token.
* ``finalize`` — stack the sampled tokens into per-request
  `GenerationResult`s and release the batch's KV budget.

Running ``start_batch`` + ``decode_step`` until done + ``finalize`` is
bit-identical to the pre-refactor monolith (same rng split sequence, same
jitted functions) — `ServingEngine.generate` is now exactly that loop, and
the parity test in ``tests/test_serving_scheduler.py`` pins it.

Batches are formed within a *bucket*: prompts of one length (the static
shape the jit specializes on) with one max-new-tokens horizon and one
temperature. ``bucket_key`` is the canonical key; the scheduler never mixes
buckets inside a batch.

Paged KV cache (``kv_blocks=`` in the constructor)
--------------------------------------------------
The dense cache allocates ``B x (plen + max_new)`` KV rows per batch and
holds them until the whole batch retires. For the paper's EAC/ARDE cascade
— k repeated samples per prompt, CSVET stopping early — that re-prefills
every repeat and re-buys the prefix k times, exactly the prefill memory
traffic the roofline model says dominates edge decode. Paged mode replaces
it:

* `BlockAllocator` — fixed-size KV blocks with refcounts, a free list and
  copy-on-write; ``kv_blocks`` is the *real* memory budget
  (``kv_blocks * block_size * kv_bytes_per_token``), and ``blocks_free`` is
  the admission currency the scheduler checks.
* One resident physical cache of ``kv_blocks`` blocks serves every paged
  batch: block tables carry global allocator ids, and a block's bytes are
  reusable the moment the allocator frees it. ``kv_pool=True`` adds the
  `PrefixPool` trie that keeps filled prefix blocks across batches.
* Prefill runs once per *unique prompt*; the k repeats share the full
  prefix blocks by reference (`fork`). A partially-filled last prefix block
  is copy-on-write forked at ``start_batch`` — each repeat gets a private
  copy of the block its first divergent token lands in (`cow`), so the
  whole block schedule is known up front and decode steps never touch the
  allocator (jit-friendly static block tables).
* Decode attention reads through the per-sequence block table — the Pallas
  paged kernel gathers physical blocks via scalar-prefetched index maps;
  the jnp reference path gathers + slices so it is *bit-identical* to the
  dense path (pinned by ``tests/test_kv_paging.py``).
* `release_sequences` returns a finished sample's private blocks to the
  free list immediately (CSVET early-stop), instead of at batch retirement.

Paged mode is supported for the architectures
`repro.models.cache.paged_supported` accepts; everything else keeps the
dense layout.

Speculative decode (``spec_policy=`` in the constructor)
--------------------------------------------------------
With a `repro.spec` draft policy attached, ``decode_step`` on a drafting
batch runs draft -> verify -> commit instead of one-token sampling: the
policy proposes n tokens from each sequence's own history, ONE verify
forward scores them against the cache (``decode=True`` forces the
cache-attending branches at S = n + 1), and `repro.spec.verify_tokens`
keeps the longest accepted prefix plus a correction/bonus token —
distribution-preserving under sampling, bit-identical tokens under greedy.

Rollback is free by construction: every verify scatters its S query tokens
into positions ``[base, base + n]`` *before* attending, positions above a
query's own are masked, and the next verify re-writes the whole span — so
rejected-draft KV entries are dead weight that the following step
overwrites, with zero allocator traffic per step (`release_sequences`
machinery is only exercised by early stop, exactly as without drafting).
To make those tail writes safe for rows that finish while the batch is
still ragged, spec batches allocate a slack horizon of ``spec_n + 1`` extra
token slots per sequence (`request_blocks` prices it into admission).
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import (Any, Deque, Dict, List, Optional, Sequence, Set, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import cache as cache_mod
from repro.models.model import Model
from repro.obs import NULL_OBS
from repro.obs.metrics import RATIO_BUCKETS
from repro.serving.prefix_pool import PrefixPool
from repro.spec.policy import spec_supported
from repro.spec.verify import verify_tokens


@dataclass
class GenerationResult:
    prompt: np.ndarray
    samples: List[np.ndarray]          # n_samples completions (token arrays)
    logprobs: List[float]              # mean per-token logprob per sample
    prefill_tokens: int = 0
    decode_tokens: int = 0


# ============================================================ block allocator

class BlockAllocator:
    """Fixed-size KV block accounting: free list + refcounts + copy-on-write.

    This is the global *admission budget* for paged serving: every in-flight
    batch's physical pool layout is mirrored here block-for-block, double
    frees raise instead of silently corrupting the budget, and a shared
    prefix block only returns to the free list when its *last* holder
    releases it. Block ids index the backend's one resident cache of
    ``n_blocks`` blocks, so the budget bounds the bytes.
    """

    def __init__(self, n_blocks: int, block_size: int):
        if n_blocks <= 0 or block_size <= 0:
            raise ValueError("n_blocks and block_size must be positive")
        self.n_blocks = n_blocks
        self.block_size = block_size
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self._ref: Dict[int, int] = {}
        # blocks indexed by a resident PrefixPool (bid -> owning-prefix
        # description): their cached KV content must never silently return
        # to the free list — the pool's evict path unprotects first
        self._protected: Dict[int, str] = {}

    @property
    def blocks_free(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        return self.n_blocks - len(self._free)

    def refcount(self, bid: int) -> int:
        return self._ref.get(bid, 0)

    def alloc(self) -> int:
        """Take one block off the free list (refcount 1)."""
        if not self._free:
            raise RuntimeError(
                f"KV block pool exhausted ({self.n_blocks} blocks; "
                "admission must check blocks_free)")
        bid = self._free.pop()
        self._ref[bid] = 1
        return bid

    def fork(self, bid: int) -> int:
        """Add a reference to a live block (prefix sharing across the
        repeated samples of one prompt)."""
        ref = self._ref.get(bid)
        if ref is None:
            raise KeyError(f"fork of unallocated block {bid}")
        self._ref[bid] = ref + 1
        return bid

    def cow(self, bid: int) -> Tuple[int, bool]:
        """Copy-on-write: the writable version of ``bid`` for one holder.
        Sole holder writes in place (``(bid, False)``); a shared block costs
        a fresh private block and drops one reference (``(new, True)`` — the
        caller must physically copy the contents)."""
        ref = self._ref.get(bid)
        if ref is None:
            raise KeyError(f"cow of unallocated block {bid}")
        if ref == 1:
            return bid, False
        new = self.alloc()              # may raise before any state changes
        self._ref[bid] = ref - 1
        return new, True

    def protect(self, bid: int, owner: str) -> None:
        """Mark a live block as trie-resident (`repro.serving.prefix_pool`):
        its last reference belongs to the pool's index, and `free` refuses
        to return it to the free list — eviction must go through the pool."""
        if bid not in self._ref:
            raise KeyError(f"protect of unallocated block {bid}")
        self._protected[bid] = owner

    def unprotect(self, bid: int) -> None:
        self._protected.pop(bid, None)

    def protected_owner(self, bid: int) -> Optional[str]:
        return self._protected.get(bid)

    def free(self, bid: int) -> bool:
        """Drop one reference; returns True when the block physically went
        back to the free list. Freeing an unallocated block raises — the
        double-free guard the invariant tests pin. Freeing the *last*
        reference of a trie-resident block also raises (with the block id
        and owning prefix named): cached KV returning to the free list
        behind the pool's back would corrupt the prefix index."""
        ref = self._ref.get(bid)
        if ref is None:
            raise RuntimeError(f"double free / free of unallocated block {bid}")
        owner = self._protected.get(bid)
        if owner is not None and ref == 1:
            raise RuntimeError(
                f"free of trie-resident block {bid} (owning prefix: "
                f"{owner}) would return an indexed block to the free list; "
                "evict it through PrefixPool.evict instead")
        if ref > 1:
            self._ref[bid] = ref - 1
            return False
        del self._ref[bid]
        self._free.append(bid)
        return True


@dataclass
class PagedBatchLayout:
    """Block tables of one in-flight batch into the resident cache. Built
    once at ``start_batch`` (the whole block schedule — prefix sharing, CoW
    fan-out, decode blocks — is deterministic given the bucket geometry),
    static for the batch lifetime so decode steps stay pure jitted
    functions. Every table holds global allocator ids."""
    block_size: int
    kv_len: int                        # logical slots per sequence
    prefill_table: np.ndarray          # (R, ceil(plen/bs)) block ids
    decode_table: np.ndarray           # (B, ceil(kv_len/bs)) block ids
    copy_src: np.ndarray               # CoW pairs: partial prefix block ->
    copy_dst: np.ndarray               #   each repeat's private copy
    seq_gids: List[List[int]]          # allocator ids referenced per sequence
    # prompt i's leading ``hit_counts[i]`` prefill blocks came from the
    # prefix pool already filled: only the tail from
    # ``hit_counts[i] * block_size`` needs prefilling
    hit_counts: List[int] = field(default_factory=list)


def build_paged_layout(allocator: BlockAllocator, plen: int, max_new: int,
                       repeats: Sequence[int],
                       hit_chains: Optional[Sequence[List[int]]] = None
                       ) -> PagedBatchLayout:
    """Allocate one batch's blocks and build its tables.

    Per request: the ``plen // bs`` full prefix blocks are allocated once and
    forked to every repeat; a partially-filled last prefix block is CoW-forked
    per repeat (first divergent token lands there); decode blocks are private.
    The caller must have checked ``request_blocks`` against ``blocks_free`` —
    allocation never fails mid-build after that.

    Blocks cover written positions only: the final sampled token is returned,
    never cached, so the last position is ``plen + max_new - 2`` (prefill end
    for max_new == 1) and sequences never pay for a block that would hold
    only the unwritten ``plen + max_new - 1`` slot.

    ``hit_chains[i]`` (empty when not given) is prompt *i*'s longest cached
    full-prefix block chain from the resident `PrefixPool` — already
    filled, already holding one reference per repeat
    (``PrefixPool.acquire``). Those blocks head the prompt's tables instead
    of fresh allocations, and only the tail is newly allocated. Partial
    blocks are never pool-shared, so the CoW fork and the whole block
    schedule stay static and decode jit-friendly. Tables carry global
    allocator ids: they index the backend's one resident cache.
    """
    bs = allocator.block_size
    n_logical = max(-(-(plen + max_new - 1) // bs), 1)
    full_prefix = plen // bs
    has_partial = plen % bs != 0
    if hit_chains is None:
        hit_chains = [[] for _ in repeats]

    prefill_rows: List[List[int]] = []
    decode_rows: List[List[int]] = []
    seq_gids: List[List[int]] = []
    copy_src: List[int] = []
    copy_dst: List[int] = []

    for k, hits in zip(repeats, hit_chains):
        if len(hits) > full_prefix:
            raise ValueError(f"hit chain of {len(hits)} blocks exceeds the "
                             f"{full_prefix} full prefix blocks of plen={plen}")
        shared = list(hits) + [allocator.alloc()
                               for _ in range(full_prefix - len(hits))]
        part = allocator.alloc() if has_partial else None
        for _ in range(k - 1):
            for g in shared[len(hits):]:   # hit refs already taken by acquire
                allocator.fork(g)
            if part is not None:
                allocator.fork(part)
        prefill_rows.append(shared + ([part] if part is not None else []))
        for _ in range(k):
            gids = list(shared)
            if part is not None:
                wg, copied = allocator.cow(part)
                if copied:
                    copy_src.append(part)
                    copy_dst.append(wg)
                gids.append(wg)
            while len(gids) < n_logical:
                gids.append(allocator.alloc())
            decode_rows.append(gids)
            seq_gids.append(gids)

    return PagedBatchLayout(
        block_size=bs,
        kv_len=plen + max_new,
        prefill_table=np.asarray(prefill_rows, np.int32),
        decode_table=np.asarray(decode_rows, np.int32),
        copy_src=np.asarray(copy_src, np.int32),
        copy_dst=np.asarray(copy_dst, np.int32),
        seq_gids=seq_gids,
        hit_counts=[len(h) for h in hit_chains])


@dataclass
class SpecState:
    """Speculative decode state of one in-flight batch.

    Sequences progress *raggedly* — each verify step commits between 1 and
    n+1 tokens per row — so per-sequence token/logprob lists replace the
    per-step stacked arrays, ``committed`` tracks each row's emitted count,
    and ``InFlightBatch.step`` is ``committed.min()`` (the batch retires
    when the slowest row reaches the horizon; finished rows keep riding the
    static-shape verify forward, writing only into their slack slots).
    ``proposed``/``accepted`` feed the "spec" telemetry record the
    `CalibrationFitter` learns accept rates from.
    """
    policy: Any                        # DraftPolicy proposing the drafts
    n: int                             # draft depth for this batch
    committed: np.ndarray              # (B,) tokens emitted per sequence
    histories: List[np.ndarray]        # prompt + committed tokens, per seq
    toks: List[List[int]]              # emitted tokens per sequence
    lps: List[List[float]]             # emitted logprobs per sequence
    proposed: int = 0                  # draft tokens offered to verify
    accepted: int = 0                  # draft tokens verify accepted
    steps: int = 0                     # verify forwards run

    @property
    def accept_rate(self) -> float:
        return self.accepted / self.proposed if self.proposed else 0.0


@dataclass
class PendingPrefill:
    """Chunked-prefill work of a batch that has its blocks but not yet its
    KV: ``jobs`` are (prompt idxs, start, end) token slices, drained one per
    ``decode_step`` call; the finish pass (CoW fan-out, pool indexing,
    first-token sampling) runs when the last job lands. The handle's
    ``step`` stays 0 throughout, so the scheduler never retires it early,
    and a preemption mid-prefill releases instead of parking (no block is
    guaranteed filled yet)."""
    jobs: Deque[Tuple[List[int], int, int]]
    base: np.ndarray                   # (R, L[,K]) stacked unique prompts
    extras: Dict[str, jax.Array]       # per-prompt rows (untiled)
    last_rows: List[Any]               # final-position logits per prompt
    rep: Union[int, np.ndarray]
    mc: bool
    n_spec: int = 0                    # draft depth armed for this batch


@dataclass
class InFlightBatch:
    """One prefilled batch mid-decode: the unit the scheduler interleaves."""
    prompts: List[np.ndarray]
    repeats: List[int]                 # samples per prompt (KV budget held)
    plen: int
    max_new: int
    temperature: float
    rng: jax.Array                     # stream state: split once per token
    extras: Dict[str, jax.Array]       # already tiled to sequence count
    cache: Any                         # dense batches only; a paged batch
    #                                    reads the backend's resident cache
    tok: Optional[jax.Array]           # last sampled token (B,) or (B, K);
    #                                    None while chunk-prefilling
    step: int                          # tokens sampled so far (>= 1)
    out_toks: List[np.ndarray] = field(default_factory=list)
    out_lps: List[np.ndarray] = field(default_factory=list)
    # paged state (None in dense mode)
    paged: Optional[PagedBatchLayout] = None
    block_table: Optional[jax.Array] = None    # decode table on device
    prefill_bytes_saved: float = 0.0   # KV bytes prefix sharing did not move
    # prefix-pool accounting (zero without the trie)
    pool_hit_blocks: int = 0           # trie-cached blocks this batch reused
    pool_evictions: int = 0            # idle blocks evicted to fit the tail
    freed_seqs: Set[int] = field(default_factory=set)   # early-released rows
    spec: Optional[SpecState] = None   # set when this batch drafts (n > 0)
    pending_prefill: Optional[PendingPrefill] = None

    @property
    def n_sequences(self) -> int:
        return sum(self.repeats)

    @property
    def done(self) -> bool:
        return self.step >= self.max_new


def bucket_key(prompt: np.ndarray, max_new: int,
               temperature: float) -> Tuple[int, int, float]:
    """Static-shape bucket: batches may only group requests that share the
    prompt length (the jit's shape key), decode horizon and temperature."""
    return (len(prompt), max_new, float(temperature))


class ExecutionBackend:
    """Owns model execution state: jitted step functions, KV budget,
    placement history.

    Dense mode: ``max_slots`` bounds concurrently resident sequences
    (prompt x samples rows); ``None`` means unbounded (the original engine's
    behaviour). Paged mode (``kv_blocks`` set): a `BlockAllocator` of
    ``kv_blocks`` blocks of ``kv_block_size`` token slots is the budget —
    admission prices a request at shared-prefix cost (`request_blocks`), so
    the k repeats of one prompt pay for their prefix once."""

    def __init__(self, model: Model, params, eos_token: Optional[int] = None,
                 max_slots: Optional[int] = None,
                 kv_blocks: Optional[int] = None, kv_block_size: int = 16,
                 kv_format: str = "bf16", obs=None,
                 spec_policy=None, spec_n: int = 0,
                 kv_pool: bool = False, pool_evict: str = "lru",
                 prefill_chunk: Optional[int] = None):
        self.model = model
        self.params = params
        # what a pool entry holds: MLA's latent and rope key, else k/v
        self.cache_kind = "latent" if model.cfg.mla is not None else "kv"
        self.eos_token = eos_token
        self.max_slots = max_slots
        self.slots_in_use = 0
        # speculative decode: spec_n is the MAXIMUM draft depth — it sizes
        # the per-sequence slack allocation, so per-batch depths noted via
        # note_spec may only go down from it
        self.spec_policy = spec_policy
        self.spec_n = int(spec_n)
        self._next_spec_n: Optional[int] = None
        if spec_policy is not None:
            if self.spec_n < 1:
                raise ValueError("spec_policy requires spec_n >= 1 "
                                 "(the maximum draft depth)")
            if not spec_supported(model.cfg):
                raise ValueError(
                    f"speculative decode unsupported for arch "
                    f"{model.cfg.name!r} (see repro.spec.spec_supported)")
        if kv_format not in ("bf16", "int8"):
            raise ValueError(f"unknown kv_format {kv_format!r} "
                             "(supported: bf16, int8)")
        if kv_format == "int8" and kv_blocks is None:
            raise ValueError("kv_format='int8' requires the paged cache "
                             "(set kv_blocks)")
        self.kv_format = kv_format
        # serving format of the loaded weights (repro.quant) + their actual
        # resident bytes — stamped on telemetry records
        from repro.quant.quantize import param_bytes, params_quant_format
        self.quant_format = params_quant_format(params)
        self.weight_bytes = param_bytes(params)
        self.allocator: Optional[BlockAllocator] = None
        self.prefix_pool = None
        self._pool_cache = None            # resident physical cache (paged)
        if kv_blocks is not None:
            if not cache_mod.paged_supported(model.cfg):
                raise ValueError(
                    f"paged KV cache unsupported for arch "
                    f"{model.cfg.name!r} (see repro.models.cache."
                    "paged_supported); use the dense max_slots budget")
            self.allocator = BlockAllocator(kv_blocks, kv_block_size)
            if kv_pool:
                # the radix trie indexes filled full-prefix blocks of the
                # resident cache for cross-batch reuse
                self.prefix_pool = PrefixPool(self.allocator,
                                              evict=pool_evict)
        elif kv_pool:
            raise ValueError("kv_pool requires the paged cache (set "
                             "kv_blocks)")
        # chunked prefill: split every prefill into <= prefill_chunk-token
        # slices, one slice per decode_step call, so a long prompt
        # interleaves with in-flight decode instead of stalling it. Rides
        # the tail-prefill kernel (explicit positions + block tables), so
        # paged mode only — and bit-identical to the one-shot prefill: the
        # kernel masks unwritten positions, so per-position attention sums
        # are over the same terms regardless of chunk boundaries.
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1 "
                                 f"(got {prefill_chunk})")
            if self.allocator is None:
                raise ValueError("prefill_chunk requires the paged cache "
                                 "(set kv_blocks)")
        self.prefill_chunk = prefill_chunk
        # live handles: release() must be called exactly once per started
        # batch — a second release raises instead of silently driving the
        # budget negative (the double-release regression).
        self._live: Dict[int, InFlightBatch] = {}
        # placement hook state (the orchestrator's simulated stage->device
        # plan for whatever is being executed): the scheduler notes the
        # routed operating point per batch; the legacy engine notes its
        # placement_provider's answer per generate call. Bounded history —
        # a long-lived server must not grow linearly with request count.
        self.last_placement = None
        self.placements: Deque = deque(maxlen=256)
        self.set_obs(obs)
        self._prefills = 0             # start_batch calls (profiler steps)
        self._prefill_jit = jax.jit(self._prefill)
        self._decode_jit = jax.jit(self._decode_step,
                                   static_argnames=("kv_len", "greedy"))
        self._spec_verify_jit = jax.jit(self._spec_verify,
                                        static_argnames=("kv_len", "greedy"))
        self._tail_prefill_jit = jax.jit(self._tail_prefill,
                                         static_argnames=("kv_len",))
        self._copy_blocks_jit = jax.jit(cache_mod.copy_cache_blocks)
        self._reset_blocks_jit = jax.jit(
            cache_mod.reset_cache_block_positions)

    def set_obs(self, obs) -> None:
        """Attach (or detach, ``None``) a `repro.obs.Observability` bundle.

        Separate from the constructor so the overhead bench can flip
        instrumentation on a backend whose jit caches are already warm —
        metric handles are resolved here, once, and hot paths guard on
        ``self._m``/``tracer.enabled``; execution state is untouched, so
        attaching obs cannot perturb outputs (the bit-parity test pins it).
        """
        self.obs = obs if obs is not None else NULL_OBS
        self._m = None
        if self.obs.metrics.enabled:
            reg = self.obs.metrics
            self._m = {
                "tokens_in": reg.counter(
                    "serving_tokens_in_total",
                    "Prompt tokens prefilled (unique rows in paged mode)"),
                "tokens_out": reg.counter(
                    "serving_tokens_out_total",
                    "Tokens sampled across all sequences"),
                "kv_blocks": reg.gauge(
                    "serving_kv_blocks_in_use",
                    "Paged KV blocks currently allocated"),
                "kv_high": reg.gauge(
                    "serving_kv_blocks_high_water",
                    "Peak paged KV block occupancy"),
                "slots": reg.gauge(
                    "serving_slots_in_use",
                    "Dense KV sequence slots currently resident"),
                "spec_proposed": reg.counter(
                    "serving_spec_proposed_total",
                    "Draft tokens proposed to speculative verify"),
                "spec_accepted": reg.counter(
                    "serving_spec_accepted_total",
                    "Draft tokens accepted by speculative verify"),
                "spec_accept": reg.histogram(
                    "serving_spec_accept_rate",
                    "Per-verify-step draft token accept rate",
                    buckets=RATIO_BUCKETS),
                "spec_tps": reg.gauge(
                    "serving_spec_tokens_per_step",
                    "Tokens committed per decode step "
                    "(last speculative verify)"),
                "pool_hits": reg.counter(
                    "serving_prefix_pool_hits_total",
                    "Full prefix blocks resolved from the resident "
                    "prefix-pool trie (prefill skipped)"),
                "pool_misses": reg.counter(
                    "serving_prefix_pool_misses_total",
                    "Full prefix blocks prefilled fresh and inserted "
                    "into the trie"),
                "pool_evictions": reg.counter(
                    "serving_prefix_pool_evictions_total",
                    "Idle (zero-ref) trie blocks evicted LRU to fit "
                    "new tails"),
                "pool_resident": reg.gauge(
                    "serving_prefix_pool_blocks_resident",
                    "KV blocks currently indexed by the prefix-pool trie"),
                "pool_ratio": reg.histogram(
                    "serving_prefix_pool_hit_ratio",
                    "Per-batch fraction of full prefix blocks served "
                    "from the trie", buckets=RATIO_BUCKETS),
            }

    def _note_occupancy(self) -> None:
        if self._m is None:
            return
        if self.allocator is not None:
            used = self.allocator.blocks_in_use
            self._m["kv_blocks"].set(used)
            self._m["kv_high"].set_max(used)
            if self.prefix_pool is not None:
                self._m["pool_resident"].set(
                    self.prefix_pool.blocks_resident)
        else:
            self._m["slots"].set(self.slots_in_use)

    # ------------------------------------------------------------------ jitted
    def _prefill(self, params, tokens, cache, extras):
        """Whole-prompt prefill into a dense batch's own cache."""
        batch = {"tokens": tokens, **extras}
        logits, cache, _ = self.model.forward(params, batch, cache,
                                              last_only=True)
        return logits[:, -1], cache

    def _tail_prefill(self, params, tokens, start_pos, cache, extras,
                      block_table, *, kv_len):
        """Prefill only a prompt's tail against cached prefix blocks.

        ``decode=True`` forces the cache-attending branches at S > 1 (the
        speculative-verify mechanism): the tail queries scatter their KV
        into the resident pool through ``block_table`` *before* attending,
        then each attends to every cached position ``<= its own`` — the hit
        chain's prefix KV plus the tail itself, masked causally by position.
        The gathered reference path reduces over the same ``kv_len = plen``
        positions in the same order as a full prefill. A zero-hit prompt
        runs its whole prompt through this path (start_pos 0), bit-identical
        to the dense prefill (pinned by ``tests/test_kv_paging.py``); a
        cache-hot tail's logits can differ from it in the last bits on the
        CPU (two pins in ``tests/test_prefix_pool.py`` fail on that)."""
        B, S = tokens.shape[0], tokens.shape[1]
        pos = start_pos + jnp.arange(S, dtype=jnp.int32)[None, :]
        pos = jnp.broadcast_to(pos, (B, S))
        if self.model.cfg.mrope_sections:
            pos = jnp.broadcast_to(pos[..., None], (B, S, 3))
        b = {"tokens": tokens, "positions": pos, "block_table": block_table,
             **extras}
        logits, cache, _ = self.model.forward(params, b, cache,
                                              kv_len=kv_len, decode=True,
                                              last_only=True)
        return logits[:, -1], cache

    def _decode_step(self, params, tok, step_pos, cache, rng, temperature,
                     extras, block_table=None, *, kv_len=None, greedy=False):
        B = tok.shape[0]
        # positions are built inside the jit from the scalar step counter:
        # nothing per-step is re-tiled or re-staged on the host
        pos = jnp.full((B, 1), step_pos, jnp.int32)
        if self.model.cfg.mrope_sections:
            pos = jnp.broadcast_to(pos[..., None], (B, 1, 3))
        b = {"tokens": tok, "positions": pos, **extras}
        if block_table is not None:
            b["block_table"] = block_table
        logits, cache, _ = self.model.forward(params, b, cache, kv_len=kv_len)
        logits = logits[:, 0].astype(jnp.float32)          # (B, V) or (B, K, V)
        logp = jax.nn.log_softmax(logits, axis=-1)
        if greedy:          # temperature == 0 convention (static branch)
            sample = jnp.argmax(logits, axis=-1)
        else:
            sample = jax.random.categorical(rng, logits / temperature,
                                            axis=-1)
        chosen_logp = jnp.take_along_axis(logp, sample[..., None],
                                          axis=-1)[..., 0]
        return sample, chosen_logp, cache

    def _spec_verify(self, params, toks, base_pos, cache, rng, temperature,
                     extras, block_table=None, *, kv_len=None, greedy=False):
        """One speculative verify forward: score the last committed token +
        n drafts (S = n + 1 queries per row) against the cache, then
        accept/reject. ``base_pos`` is each row's position of ``toks[:, 0]``
        — rows progress raggedly, so it is per-sequence and traced.
        ``decode=True`` forces the cache-attending branches at S > 1; the
        scatter of these S positions happens before attention, so every
        query sees exactly its own prefix (stale rejected-draft entries from
        the previous step are overwritten or masked by position)."""
        B, n_q = toks.shape
        pos = base_pos[:, None] + jnp.arange(n_q, dtype=jnp.int32)[None, :]
        if self.model.cfg.mrope_sections:
            pos = jnp.broadcast_to(pos[..., None], (B, n_q, 3))
        b = {"tokens": toks, "positions": pos, **extras}
        if block_table is not None:
            b["block_table"] = block_table
        logits, cache, _ = self.model.forward(params, b, cache,
                                              kv_len=kv_len, decode=True)
        accept_len, out_tokens, out_lps = verify_tokens(
            logits.astype(jnp.float32), toks[:, 1:], rng, temperature,
            greedy)
        return accept_len, out_tokens, out_lps, cache

    # ---------------------------------------------------------------- plumbing
    @property
    def paged(self) -> bool:
        return self.allocator is not None

    @property
    def slots_free(self) -> Optional[int]:
        """Remaining KV slot budget (None = unbounded; dense mode only)."""
        if self.max_slots is None:
            return None
        return self.max_slots - self.slots_in_use

    @property
    def blocks_free(self) -> Optional[int]:
        return self.allocator.blocks_free if self.allocator else None

    @property
    def blocks_in_use(self) -> Optional[int]:
        return self.allocator.blocks_in_use if self.allocator else None

    @property
    def capacity_free(self) -> Optional[int]:
        """Admission budget remaining, in this backend's currency: KV blocks
        (paged) or sequence slots (dense); None = unbounded. Idle prefix-pool
        blocks count as free — `_start_batch_paged` evicts them on demand —
        so a cache full of reclaimable prefixes never starves admission."""
        if self.allocator is not None:
            free = self.allocator.blocks_free
            if self.prefix_pool is not None:
                free += self.prefix_pool.evictable_blocks
            return free
        return self.slots_free

    @property
    def capacity_total(self) -> Optional[int]:
        if self.allocator is not None:
            return self.allocator.n_blocks
        return self.max_slots

    def _spec_slack(self) -> int:
        """Extra token slots per sequence a speculative backend allocates.

        A verify step writes KV for all its queries before masking decides
        acceptance — a row that already finished (``committed == max_new``)
        still rides the static-shape forward with base position
        ``plen + max_new - 1``, writing up to ``plen + max_new - 1 + n``.
        Slack of ``spec_n + 1`` slots past the non-speculative last written
        position (``plen + max_new - 2``) covers the worst case; priced into
        `request_blocks` so admission stays exact."""
        return self.spec_n + 1 if self.spec_policy is not None else 0

    def request_blocks(self, plen: int, max_new: int, n_samples: int,
                       prompt: Optional[np.ndarray] = None,
                       charged: Optional[set] = None) -> int:
        """Block cost of a request at shared-prefix price: the full prefix
        blocks once, plus per-sample privates (the CoW copy of a partial
        prefix block and the decode blocks). Mirrors `build_paged_layout`
        exactly — written positions end at ``plen + max_new - 2``, plus the
        speculative slack horizon when a draft policy is attached.

        With the resident prefix pool and the prompt tokens given, cost is
        *marginal* against `capacity_free` (= free + evictable blocks): a
        hit block already pinned by a live batch (refcount >= 2) is free —
        the batch only allocates the post-dedup tail — while an *idle* hit
        still charges one unit, because admitting the request pins it and
        removes it from the evictable headroom `capacity_free` counted.
        (Pricing idle hits free double-counts them against that headroom:
        admission could pass while the execution-time eviction loop finds
        the hits it needs to reclaim pinned under itself.) An idle hit is
        charged once a batch: ``charged`` holds the idle blocks already
        priced for the batch being formed (the first request that hits
        them pins them for the rest), and gains this request's. Under
        ``pool_evict="off"`` there is no evictable headroom to consume, so
        every hit is free and the price is the pure tail. The lookup is
        LRU-neutral (``touch=False``)."""
        bs = self.allocator.block_size
        horizon = max_new + self._spec_slack()
        n_logical = max(-(-(plen + horizon - 1) // bs), 1)
        full_prefix = plen // bs
        shared = full_prefix
        if prompt is not None and self.prefix_pool is not None:
            chain = self.prefix_pool.lookup(prompt, self._max_hit(plen),
                                            touch=False)
            if self.prefix_pool.evict_policy == "off":
                free_hits = len(chain)
            else:
                idle = {int(g) for g in chain
                        if self.allocator.refcount(g) < 2}
                if charged is not None:
                    idle -= charged
                    charged |= idle
                free_hits = len(chain) - len(idle)
            shared = full_prefix - free_hits
        return shared + n_samples * (n_logical - full_prefix)

    def request_cost(self, plen: int, max_new: int, n_samples: int,
                     prompt: Optional[np.ndarray] = None,
                     charged: Optional[set] = None) -> int:
        """Admission cost in ``capacity_free`` units (blocks or slots)."""
        if self.allocator is not None:
            return self.request_blocks(plen, max_new, n_samples,
                                       prompt=prompt, charged=charged)
        return n_samples

    def marginal_request_cost(self, prompt: np.ndarray, max_new: int,
                              n_samples: int,
                              charged: Optional[set] = None) -> int:
        """Post-dedup admission price of one request (the scheduler's
        per-batch budget check): identical to `request_cost` without a pool,
        cheaper by the already-pinned trie prefix blocks with one (see
        `request_blocks` for why idle hits still charge under LRU, once a
        batch given the batch's ``charged`` set)."""
        return self.request_cost(len(prompt), max_new, n_samples,
                                 prompt=np.asarray(prompt), charged=charged)

    def _max_hit(self, plen: int) -> int:
        """Cap on trie-reusable full prefix blocks: at least one prompt
        token must remain in the tail — the tail forward produces the
        position ``plen - 1`` logits the first sample comes from, and a
        fully cached prompt would otherwise re-scatter into shared blocks."""
        return (plen - 1) // self.allocator.block_size

    @property
    def kv_token_bytes(self) -> int:
        """KV bytes one token position costs across the stack (for mapping
        slot/block budgets to real memory, and the prefill-savings
        telemetry). Follows the actual cache element dtype: int8 KV stores
        one byte per element, so at a fixed byte budget the block budget
        roughly doubles."""
        if self.kv_format == "int8":
            el = 1
        else:
            el = 2 if self.model.dtype == jnp.bfloat16 else 4
        return cache_mod.kv_bytes_per_token(self.model.cfg, el)

    def note_placement(self, placement) -> None:
        self.last_placement = placement
        self.placements.append(placement)

    def note_spec(self, n: int) -> None:
        """Set the draft depth for the NEXT ``start_batch`` (the router's
        per-batch choice; 0 runs the batch without drafting). Depths above
        ``spec_n`` raise — the slack allocation is sized for ``spec_n``."""
        if self.spec_policy is None:
            raise RuntimeError("note_spec on a backend with no draft policy")
        n = int(n)
        if not 0 <= n <= self.spec_n:
            raise ValueError(f"spec depth {n} outside [0, {self.spec_n}] "
                             "(slack allocation is sized for spec_n)")
        self._next_spec_n = n

    def _consume_spec_n(self) -> int:
        """Draft depth the next batch runs at: the noted per-batch depth if
        the router set one, else the configured maximum."""
        if self.spec_policy is None:
            return 0
        n = self._next_spec_n if self._next_spec_n is not None else self.spec_n
        self._next_spec_n = None
        return n

    @property
    def _multi_codebook(self) -> bool:
        return self.model.cfg.n_codebooks > 1

    # ---------------------------------------------------------------- step API
    def start_batch(self, prompts: Sequence[np.ndarray],
                    n_samples: Union[int, Sequence[int]], max_new: int,
                    temperature: float, rng: jax.Array,
                    extras: Optional[Dict] = None) -> InFlightBatch:
        """Prefill equal-length prompts and sample the first token.

        ``n_samples`` may be a single count or one per prompt (mixed-tier
        batches can carry different coverage floors). ``extras`` values are
        per-prompt rows, tiled to the sequence count here — once; decode
        steps reuse the tiled arrays.

        Paged mode prefills one row per *prompt* and fans the result out to
        the repeats through shared prefix blocks (+ a tiled first-token
        sample, bit-identical to prefilling every repeat).
        """
        extras = extras or {}
        mc = self._multi_codebook
        repeats = ([int(n_samples)] * len(prompts)
                   if isinstance(n_samples, int) else
                   [int(n) for n in n_samples])
        if not prompts or any(k < 1 for k in repeats):
            # a 0-sample request would allocate prefix blocks that no
            # sequence references (and so could never release)
            raise ValueError("start_batch needs >= 1 prompt and >= 1 "
                             f"sample per prompt (got repeats={repeats})")
        plen = len(prompts[0])
        if any(len(p) != plen for p in prompts):
            raise ValueError("start_batch requires equal-length prompts "
                             "(one static-shape bucket)")
        uniform = len(set(repeats)) == 1
        rep: Union[int, np.ndarray] = \
            repeats[0] if uniform else np.asarray(repeats)
        base = np.stack(list(prompts))                      # (R, L[,K])
        B = int(sum(repeats))

        tracer = self.obs.tracer
        n_spec = self._consume_spec_n()
        self._prefills += 1
        # the batch id comes from the scheduler via tracer.batch_context
        with tracer.span("prefill", step_num=self._prefills, plen=plen,
                         n_sequences=B, cache=self.cache_kind) as span:
            if self.allocator is not None:
                h = self._start_batch_paged(prompts, repeats, rep, base, B,
                                            plen, max_new, temperature, rng,
                                            extras, mc)
                # one row per unique prompt, post-dedup tails only
                prefilled = (len(prompts) * plen
                             - h.pool_hit_blocks * self.allocator.block_size)
            else:
                h = self._start_batch_dense(prompts, repeats, rep, base, B,
                                            plen, max_new, temperature, rng,
                                            extras, mc)
                prefilled = B * plen
            if h.pending_prefill is not None:
                # chunked: nothing forwarded yet — each chunk step meters its
                # own tokens and spans; the draft depth arms at finish
                h.pending_prefill.n_spec = n_spec
                n_spec = 0
                prefilled = 0
            else:
                self._fetch_first(h)
            if n_spec > 0:
                self._arm_spec(h, n_spec)
            self._live[id(h)] = h
            span.set(prefill_tokens=prefilled)
            if self._m is not None:
                self._m["tokens_in"].inc(prefilled)
                if h.pending_prefill is None:       # else metered at finish
                    self._m["tokens_out"].inc(B)    # first token per seq
                if self.prefix_pool is not None and h.paged is not None:
                    lookupable = len(prompts) * (plen
                                                 // self.allocator.block_size)
                    misses = lookupable - h.pool_hit_blocks
                    self._m["pool_hits"].inc(h.pool_hit_blocks)
                    self._m["pool_misses"].inc(misses)
                    if h.pool_evictions:
                        self._m["pool_evictions"].inc(h.pool_evictions)
                    if lookupable:
                        self._m["pool_ratio"].observe(
                            h.pool_hit_blocks / lookupable)
                self._note_occupancy()
        return h

    def _sample_first(self, last_logits, rng, temperature: float, rep, mc):
        """First token of every sequence from the prefill logits (fanned
        out ``rep`` ways unless None): ``(rng, tok, lp)``, on the device.
        One sampler for every start path keeps their first tokens
        bit-identical."""
        with self.obs.tracer.span("prefill.dispatch", cache=self.cache_kind):
            rng, sub = jax.random.split(rng)
            lf = last_logits.astype(jnp.float32)
            if rep is not None:
                lf = jnp.repeat(lf, rep, axis=0)
            logp0 = jax.nn.log_softmax(lf, axis=-1)
            if temperature > 0:
                tok = jax.random.categorical(sub, lf / temperature, axis=-1)
            else:
                tok = jnp.argmax(lf, axis=-1)
            lp = jnp.take_along_axis(logp0, tok[..., None], axis=-1)[..., 0]
            return rng, tok, (lp if not mc else lp.mean(-1))

    def _fetch_first(self, h: InFlightBatch) -> None:
        """Bring the first sampled token and log-prob to the host."""
        with self.obs.tracer.span("prefill.fetch", cache=self.cache_kind):
            h.out_toks = [np.asarray(h.tok)]
            h.out_lps = [np.asarray(h.out_lps[0])]

    def _arm_spec(self, h: InFlightBatch, n_spec: int) -> None:
        """Draft state of a speculative batch, seeded with its first
        token."""
        first = h.out_toks[0].ravel()
        lp0 = h.out_lps[0].ravel()
        hists: List[np.ndarray] = []
        for prompt, k in zip(h.prompts, h.repeats):
            p = np.asarray(prompt, np.int64).ravel()
            for _ in range(k):
                i = len(hists)
                hists.append(np.concatenate([p, first[i:i + 1]]))
        h.spec = SpecState(
            policy=self.spec_policy, n=n_spec,
            committed=np.ones(h.n_sequences, np.int64), histories=hists,
            toks=[[int(t)] for t in first],
            lps=[[float(x)] for x in lp0])

    def _start_batch_dense(self, prompts, repeats, rep, base, B, plen,
                           max_new, temperature, rng, extras,
                           mc) -> InFlightBatch:
        tokens = np.repeat(base, rep, axis=0)               # (B, L[,K])
        if self.max_slots is not None and \
                self.slots_in_use + B > self.max_slots:
            raise RuntimeError(
                f"KV slot budget exceeded: {self.slots_in_use}+{B} > "
                f"{self.max_slots} (scheduler must check slots_free)")
        tiled_extras = {k: jnp.repeat(jnp.asarray(v), rep, axis=0)
                        for k, v in extras.items()}

        with self.obs.tracer.span("prefill.dispatch", cache=self.cache_kind):
            cache = self.model.init_cache(B,
                                          plen + max_new + self._spec_slack())
            last_logits, cache = self._prefill_jit(
                self.params, jnp.asarray(tokens), cache, tiled_extras)
        # first sampled token comes from the prefill logits
        rng, tok, lp = self._sample_first(last_logits, rng, temperature,
                                          None, mc)
        self.slots_in_use += B
        return InFlightBatch(
            prompts=list(prompts), repeats=repeats, plen=plen,
            max_new=max_new, temperature=temperature, rng=rng,
            extras=tiled_extras, cache=cache, tok=tok, step=1,
            out_toks=[tok], out_lps=[lp])

    def _ensure_pool_cache(self):
        """The single resident physical cache (lazy: sized to the whole
        ``kv_blocks`` budget, so it is only materialized once serving
        actually starts). Block tables index it with global allocator ids;
        it outlives every batch."""
        if self._pool_cache is None:
            self._pool_cache = self.model.init_paged_cache(
                self.allocator.n_blocks, self.allocator.block_size,
                kv_dtype=jnp.int8 if self.kv_format == "int8" else None)
        return self._pool_cache

    def _start_batch_paged(self, prompts, repeats, rep, base, B, plen,
                           max_new, temperature, rng, extras,
                           mc) -> InFlightBatch:
        """Paged start over the resident cache. With the prefix pool,
        resolve each prompt's longest cached block chain (pinning it with
        per-sequence refs) and evict idle LRU blocks to fit the post-dedup
        tails; without it every chain is empty and the whole prompt is the
        tail. Then prefill *only the tails* — grouped by hit depth so every
        forward keeps a static shape — and index the freshly filled
        full-prefix chains for the batches that follow."""
        pool = self.prefix_pool
        alloc = self.allocator
        bs = alloc.block_size
        R = len(prompts)
        full_prefix = plen // bs
        tracer = self.obs.tracer
        with tracer.span("pool.match", cache=self.cache_kind):
            # 1. acquire hit chains first: refs pin them, so the eviction
            #    pass below can never reclaim a block this batch just resolved
            hit_chains = ([pool.acquire(p, self._max_hit(plen), holders=k)
                           for p, k in zip(prompts, repeats)]
                          if pool is not None else [[] for _ in prompts])
            horizon = max_new + self._spec_slack()
            n_logical = max(-(-(plen + horizon - 1) // bs), 1)
            need = sum(full_prefix - len(ch) + k * (n_logical - full_prefix)
                       for ch, k in zip(hit_chains, repeats))
            # 2. make room for the tails (LRU over idle trie leaves only)
            evicted = pool.ensure_free(need) if pool is not None else 0
            if need > alloc.blocks_free:
                for ch, k in zip(hit_chains, repeats):
                    for g in ch:
                        for _ in range(k):
                            alloc.free(g)
                raise RuntimeError(
                    f"KV block budget exceeded: need {need} tail blocks > "
                    f"{alloc.blocks_free} free after {evicted} eviction(s) "
                    "(scheduler must check capacity_free)")
            # 3. static block schedule over GLOBAL ids (allocation cannot
            #    fail past the check above)
            layout = build_paged_layout(alloc, plen, horizon, repeats,
                                        hit_chains=hit_chains)
            # invalidate the pos slots of every block allocated this batch:
            # the resident cache outlives batches, and a block back from the
            # free list still carries its previous occupant's positions — a
            # stale slot in a partially filled tail block would become
            # visible the moment decode advances past it
            hit_gids = {int(g) for ch in hit_chains for g in ch}
            fresh = sorted({int(g) for gids in layout.seq_gids
                            for g in gids} - hit_gids)
            groups: Dict[int, List[int]] = {}
            for i, c in enumerate(layout.hit_counts):
                groups.setdefault(c, []).append(i)
        try:
            with tracer.span("prefill.dispatch", cache=self.cache_kind):
                # each step's output pool replaces the resident one at
                # once, so a step holds two pools (its input and output),
                # never a third
                self._ensure_pool_cache()
                if fresh:
                    self._pool_cache = self._reset_blocks_jit(
                        self._pool_cache, jnp.asarray(fresh, jnp.int32))
                prefill_extras = {k: jnp.asarray(v)
                                  for k, v in extras.items()}
                decode_extras = {k: jnp.repeat(jnp.asarray(v), rep, axis=0)
                                 for k, v in extras.items()}
                block_table = jnp.asarray(layout.decode_table)
                pending = None
                if self.prefill_chunk is not None:
                    # chunked: enqueue the tail slices instead of forwarding
                    # — decode_step drains one per call; CoW fan-out, trie
                    # indexing and first-token sampling run at finish
                    jobs: Deque[Tuple[List[int], int, int]] = deque()
                    for c, idxs in sorted(groups.items()):
                        s = c * bs
                        while s < plen:
                            e = min(s + self.prefill_chunk, plen)
                            jobs.append((idxs, s, e))
                            s = e
                    pending = PendingPrefill(
                        jobs=jobs, base=base, extras=prefill_extras,
                        last_rows=[None] * R, rep=rep, mc=mc)
                else:
                    # 4. tail-only prefill, one static-shape forward per hit
                    #    depth
                    last_rows: List[Any] = [None] * R
                    for c, idxs in sorted(groups.items()):
                        gl, self._pool_cache = self._tail_prefill_jit(
                            self.params, jnp.asarray(base[idxs][:, c * bs:]),
                            jnp.asarray(c * bs, jnp.int32), self._pool_cache,
                            {k: v[jnp.asarray(idxs)]
                             for k, v in prefill_extras.items()},
                            jnp.asarray(layout.prefill_table[idxs]),
                            kv_len=plen)
                        for j, i in enumerate(idxs):
                            last_rows[i] = gl[j]
                    last_logits = jnp.stack(last_rows, axis=0)
                    self._copy_partial_blocks(layout)
        except BaseException:
            # every reference the batch took (hits included — their holder
            # refs unwind to the trie ref) must return, or a failed prefill
            # permanently shrinks the budget
            for gids in layout.seq_gids:
                for g in gids:
                    alloc.free(g)
            raise
        tail_tokens = sum(plen - c * bs for c in layout.hit_counts)
        h = InFlightBatch(
            prompts=list(prompts), repeats=repeats, plen=plen,
            max_new=max_new, temperature=temperature, rng=rng,
            extras=decode_extras, cache=None, tok=None, step=0,
            paged=layout, block_table=block_table,
            prefill_bytes_saved=float((B * plen - tail_tokens)
                                      * self.kv_token_bytes),
            pool_hit_blocks=sum(layout.hit_counts), pool_evictions=evicted,
            pending_prefill=pending)
        if pending is None:
            self._first_token(h, last_logits, rep, mc)
        return h

    def _copy_partial_blocks(self, layout: PagedBatchLayout) -> None:
        """CoW fan-out of each prompt's shared partial prefix block into
        its repeats' private copies (partials are never pool-shared)."""
        if layout.copy_src.size > 0:
            self._pool_cache = self._copy_blocks_jit(
                self._pool_cache, jnp.asarray(layout.copy_src),
                jnp.asarray(layout.copy_dst))

    def _first_token(self, h: InFlightBatch, last_logits, rep,
                     mc) -> None:
        """The prefill landed: index the freshly filled full-prefix chains
        (only now: the trie must never point at unfilled blocks; a
        same-prefix sibling within the batch keeps the first writer's
        blocks), then fan the unique-prompt logits out to the repeats and
        sample with the same key/shape as the dense path — bit-identical
        first token."""
        if self.prefix_pool is not None:
            full_prefix = h.plen // self.allocator.block_size
            for i, p in enumerate(h.prompts):
                self.prefix_pool.insert(
                    p, [int(g) for g in
                        h.paged.prefill_table[i][:full_prefix]])
        h.rng, h.tok, lp = self._sample_first(last_logits, h.rng,
                                              h.temperature, rep, mc)
        h.step = 1
        h.out_toks, h.out_lps = [h.tok], [lp]

    def _prefill_chunk_step(self, h: InFlightBatch) -> None:
        """Run ONE pending prefill slice (the chunked-prefill unit a
        ``decode_step`` call spends instead of a token)."""
        pp = h.pending_prefill
        idxs, s, e = pp.jobs.popleft()
        tracer = self.obs.tracer
        self._prefills += 1
        with tracer.span("prefill", step_num=self._prefills,
                         prefill_tokens=len(idxs) * (e - s),
                         n_sequences=h.n_sequences, plen=h.plen,
                         chunk=[s, e]):
            with tracer.span("prefill.dispatch", cache=self.cache_kind):
                gl, self._pool_cache = self._tail_prefill_jit(
                    self.params, jnp.asarray(pp.base[idxs][:, s:e]),
                    jnp.asarray(s, jnp.int32), self._pool_cache,
                    {k: v[jnp.asarray(idxs)] for k, v in pp.extras.items()},
                    jnp.asarray(h.paged.prefill_table[idxs]), kv_len=h.plen)
                if e == h.plen:
                    for j, i in enumerate(idxs):
                        pp.last_rows[i] = gl[j]
            if self._m is not None:
                self._m["tokens_in"].inc(len(idxs) * (e - s))
            if not pp.jobs:
                self._finish_chunked_prefill(h)

    def _finish_chunked_prefill(self, h: InFlightBatch) -> None:
        """Last chunk landed: CoW-fan-out the shared partial block, index
        the now-filled full-prefix chains, sample the first token with the
        exact split sequence of the one-shot path (bit parity), and arm the
        draft state if a depth was noted."""
        pp = h.pending_prefill
        with self.obs.tracer.span("prefill.dispatch", cache=self.cache_kind):
            self._copy_partial_blocks(h.paged)
            last_logits = jnp.stack(pp.last_rows, axis=0)
        self._first_token(h, last_logits, pp.rep, pp.mc)
        self._fetch_first(h)
        if pp.n_spec > 0:
            self._arm_spec(h, pp.n_spec)
        if self._m is not None:
            self._m["tokens_out"].inc(h.n_sequences)
        h.pending_prefill = None

    def park_batch(self, h: InFlightBatch,
                   histories: Sequence[np.ndarray]) -> int:
        """Preemption handoff: index the victim's *filled* full blocks in
        the resident prefix pool before releasing the batch, so resuming it
        is a trie hit that prefills only the post-preemption tail.

        ``histories[i]`` is sequence row *i*'s token history (prompt +
        committed tokens). After ``step`` sampled tokens the KV holds
        written positions through ``plen + step - 2`` (the newest token is
        sampled, not yet scattered), so exactly ``(len(history) - 1) //
        block_size`` leading blocks of the row's table are full and
        correct — on a speculative batch that bound also keeps any stale
        rejected-draft writes (which live past the committed frontier) out
        of the trie. Returns parked blocks; degrades to a plain release
        when there is no prefix pool to park into, or mid-chunked-prefill
        (no block is guaranteed filled)."""
        pool = self.prefix_pool
        if pool is None or h.paged is None or h.pending_prefill is not None:
            self.release(h)
            return 0
        if len(histories) != h.n_sequences:
            raise ValueError(
                f"park_batch needs one history per sequence row "
                f"({h.n_sequences}), got {len(histories)}")
        bs = self.allocator.block_size
        parked = 0
        for i, hist in enumerate(histories):
            if i in h.freed_seqs:
                continue
            hist = np.asarray(hist)
            filled = (len(hist) - 1) // bs
            if filled <= 0:
                continue
            row = [int(g) for g in h.paged.decode_table[i][:filled]]
            pool.insert(hist, row)
            parked += filled
        self.release(h)
        return parked

    def _cache_of(self, h: InFlightBatch):
        """The cache a batch's step programs read: the resident cache for
        a paged batch, the batch's own for a dense one."""
        return self._pool_cache if h.paged is not None else h.cache

    def _store_cache(self, h: InFlightBatch, cache) -> None:
        if h.paged is not None:
            self._pool_cache = cache
        else:
            h.cache = cache

    def decode_step(self, h: InFlightBatch) -> bool:
        """Advance one token (or one draft/verify round on a speculative
        batch); returns True while the batch still has decode steps left
        (so ``while backend.decode_step(h): pass`` drains it)."""
        if h.pending_prefill is not None:
            self._prefill_chunk_step(h)
            return True
        if h.spec is not None:
            return self._spec_decode_step(h)
        if h.done:
            return False
        tracer = self.obs.tracer
        mc = self._multi_codebook
        with tracer.span("decode", step_num=h.step,
                         n_sequences=h.n_sequences, cache=self.cache_kind):
            with tracer.span("decode.dispatch", cache=self.cache_kind):
                h.rng, sub = jax.random.split(h.rng)
                step_pos = jnp.asarray(h.plen + h.step - 1, jnp.int32)
                tok_in = h.tok[:, None] if not mc else h.tok[:, None, :]
                h.tok, lp, cache = self._decode_jit(
                    self.params, tok_in, step_pos, self._cache_of(h), sub,
                    h.temperature, h.extras, h.block_table,
                    kv_len=h.paged.kv_len if h.paged is not None else None,
                    greedy=h.temperature == 0.0)
                if mc:
                    lp = lp.mean(-1)
            self._store_cache(h, cache)
            with tracer.span("decode.fetch", cache=self.cache_kind):
                h.out_toks.append(np.asarray(h.tok))
                h.out_lps.append(np.asarray(lp))
            h.step += 1
            if self._m is not None:
                self._m["tokens_out"].inc(h.n_sequences - len(h.freed_seqs))
        return not h.done

    def _spec_decode_step(self, h: InFlightBatch) -> bool:
        """One draft -> verify -> commit round of a speculative batch.

        Rows progress raggedly: each commits ``accept_len + 1`` tokens,
        clamped to its remaining horizon room; a finished row stays in the
        static-shape verify (its writes land in the slack slots, see
        `_spec_slack`) but commits nothing. Early-released rows
        (``freed_seqs``) keep committing like the non-speculative path keeps
        sampling them — their tokens just stop counting toward metrics — so
        a release landing between verify steps touches the allocator exactly
        once per block, never the in-flight verify state."""
        if h.done:
            return False
        sp = h.spec
        tracer = self.obs.tracer
        B = h.n_sequences
        n = sp.n
        with tracer.span("decode", step_num=h.step, n_sequences=B):
            with tracer.span("draft", policy=sp.policy.name, n=n,
                             n_sequences=B):
                drafts = np.asarray(sp.policy.propose(sp.histories, n),
                                    np.int32)
            if drafts.shape != (B, n):
                raise ValueError(f"draft policy {sp.policy.name!r} returned "
                                 f"shape {drafts.shape}, expected {(B, n)}")
            with tracer.span("verify", n=n, n_sequences=B) as vspan:
                with tracer.span("decode.dispatch", cache=self.cache_kind):
                    h.rng, sub = jax.random.split(h.rng)
                    last = np.asarray([row[-1] for row in sp.toks],
                                      np.int32)
                    toks_in = np.concatenate([last[:, None], drafts], axis=1)
                    base_pos = np.asarray(h.plen + sp.committed - 1,
                                          np.int32)
                    accept_len, out_tokens, out_lps, cache = \
                        self._spec_verify_jit(
                            self.params, jnp.asarray(toks_in),
                            jnp.asarray(base_pos), self._cache_of(h), sub,
                            h.temperature, h.extras, h.block_table,
                            kv_len=(h.paged.kv_len if h.paged is not None
                                    else None),
                            greedy=h.temperature == 0.0)
                self._store_cache(h, cache)
                with tracer.span("decode.fetch", cache=self.cache_kind):
                    accept_len = np.asarray(accept_len)
                    out_tokens = np.asarray(out_tokens)
                    out_lps = np.asarray(out_lps)

                emitted = 0         # metric-visible tokens (non-released)
                committed_this = 0  # tokens committed by rows decoding
                accepted_this = 0
                active = 0
                for b in range(B):
                    room = h.max_new - int(sp.committed[b])
                    if room <= 0:
                        continue    # finished row: verify output discarded
                    active += 1
                    a = int(accept_len[b])
                    accepted_this += a
                    sp.proposed += n
                    sp.accepted += a
                    e = min(a + 1, room)
                    new = out_tokens[b, :e]
                    sp.toks[b].extend(int(t) for t in new)
                    sp.lps[b].extend(float(x) for x in out_lps[b, :e])
                    sp.histories[b] = np.concatenate(
                        [sp.histories[b], new.astype(np.int64)])
                    sp.committed[b] += e
                    committed_this += e
                    if b not in h.freed_seqs:
                        emitted += e
                vspan.set(accepted=accepted_this)
            sp.steps += 1
            h.step = int(sp.committed.min())
            if self._m is not None:
                self._m["tokens_out"].inc(emitted)
                if active and n > 0:
                    self._m["spec_proposed"].inc(active * n)
                    self._m["spec_accepted"].inc(accepted_this)
                    self._m["spec_accept"].observe(
                        accepted_this / (active * n))
                    self._m["spec_tps"].set(committed_this / active)
        return not h.done

    def release(self, h: InFlightBatch) -> None:
        """Return a batch's remaining KV budget (blocks or slots). Raises on
        an unknown or already-released handle — a double release must fail
        loudly instead of silently driving the budget negative."""
        if self._live.pop(id(h), None) is None:
            raise RuntimeError("release of unknown or already-released "
                               "batch handle")
        if h.paged is not None:
            for i, gids in enumerate(h.paged.seq_gids):
                if i in h.freed_seqs:
                    continue
                for g in gids:
                    self.allocator.free(g)
        else:
            self.slots_in_use -= h.n_sequences - len(h.freed_seqs)
        h.freed_seqs = set(range(h.n_sequences))
        self._note_occupancy()

    def release_sequences(self, h: InFlightBatch,
                          seq_indices: Sequence[int]) -> int:
        """Early-release finished sequences' KV budget (CSVET early stop:
        once one sample of a prompt verifies, the remaining repeats cannot
        change pass@k). The batch keeps decoding with its static shapes, but
        the released rows' blocks/slots are free for new admissions *now*
        instead of at batch retirement. Returns blocks (or slots) actually
        returned to the budget; shared prefix blocks only come back with
        their last holder. A freed block's bytes are the next batch's at
        once: every paged batch indexes the one resident cache."""
        if id(h) not in self._live:
            raise RuntimeError("release_sequences on unknown or "
                               "already-released batch handle")
        bad = [i for i in seq_indices if not 0 <= i < h.n_sequences]
        if bad:
            raise ValueError(f"sequence indices {bad} out of range for a "
                             f"{h.n_sequences}-sequence batch")
        freed = 0
        for i in seq_indices:
            if i in h.freed_seqs:
                continue
            h.freed_seqs.add(i)
            if h.paged is not None:
                freed += sum(self.allocator.free(g)
                             for g in h.paged.seq_gids[i])
            else:
                self.slots_in_use -= 1
                freed += 1
        self._note_occupancy()
        return freed

    def finalize(self, h: InFlightBatch) -> List[GenerationResult]:
        """Stack per-step samples into per-request results and release the
        batch's KV budget."""
        mc = self._multi_codebook
        if h.spec is not None:
            # ragged per-sequence lists -> (B, max_new); the commit clamp
            # means done implies every row holds exactly max_new tokens
            toks = np.asarray([row[:h.max_new] for row in h.spec.toks],
                              np.int32)
            lps = np.asarray([row[:h.max_new] for row in h.spec.lps],
                             np.float32)
        else:
            toks = np.stack(h.out_toks, axis=1)             # (B, T[,K])
            lps = np.stack(h.out_lps, axis=1)               # (B, T)
        results = []
        offset = 0
        for prompt, ns in zip(h.prompts, h.repeats):
            sl = slice(offset, offset + ns)
            offset += ns
            samples = [toks[i] for i in range(sl.start, sl.stop)]
            if self.eos_token is not None and not mc:
                samples = [self._truncate(s) for s in samples]
            results.append(GenerationResult(
                prompt=prompt,
                samples=samples,
                logprobs=[float(lps[i].mean())
                          for i in range(sl.start, sl.stop)],
                prefill_tokens=h.plen,
                decode_tokens=int(np.prod(toks.shape[1:2])) * ns,
            ))
        self.release(h)
        return results

    def _truncate(self, sample: np.ndarray) -> np.ndarray:
        hits = np.nonzero(sample == self.eos_token)[0]
        return sample[: hits[0]] if hits.size else sample
