"""The one traffic generator: every mix is a parameter file read here.

A mix file (``traffic/<name>.json``) gives:

- ``loop``: ``"open"`` with ``rate_per_s`` (arrivals on a schedule,
  whatever the server does) or ``"closed"`` with ``outstanding`` (each
  client sends its next request when the last one completes);
- ``prefixes``: ``count`` shared prefixes of ``tokens`` tokens each, picked
  with Zipf popularity of exponent ``zipf_s`` (0 is uniform);
- ``suffixes``: ``[[tokens, weight], ...]``, the distinct part of each
  prompt, appended to its prefix;
- ``max_new_tokens``, ``n_samples``, ``temperature``, ``tier``.

Everything is drawn from the seed. Every seed gets the same multiset of
sizes, prefix picks and inter-arrival gaps (exponential
quantiles), in an order of its own, so that seeds change the order of the
work and not its amount. Prefix and suffix token ids are uniform over the
vocabulary.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np

STREAMS = {"window": 1, "warmup": 2}


@dataclass
class Request:
    index: int
    prompt: np.ndarray          # int32 token ids
    prefix_id: int
    suffix_len: int
    n_samples: int
    max_new: int
    temperature: float
    tier: str
    due: float = 0.0            # seconds after the window opens (open loop)


def seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words (numpy and JAX take those)."""
    seed = int(seed) % (1 << 64)
    return [seed & 0xFFFFFFFF, seed >> 32]


def exact_counts(weights: Sequence[float], n: int) -> List[int]:
    """``n`` split in proportion to ``weights`` by largest remainder."""
    w = np.asarray(weights, np.float64)
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    order = np.argsort(-(share - counts), kind="stable")
    for i in order[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def zipf_weights(count: int, s: float) -> List[float]:
    return [1.0 / (k + 1) ** s for k in range(count)]


class Mix:
    """One traffic mix under one seed."""

    def __init__(self, params: dict, vocab: int, seed: int):
        self.params = params
        self.vocab = int(vocab)
        self.seed = int(seed)
        pre = params["prefixes"]
        rng = np.random.default_rng(seed_words(seed) + [0])
        self.prefixes = rng.integers(
            0, self.vocab, size=(pre["count"], pre["tokens"]),
            dtype=np.int64).astype(np.int32)

    # ----------------------------------------------------------- sizes
    @property
    def suffix_lengths(self) -> List[int]:
        return [int(s) for s, _ in self.params["suffixes"]]

    @property
    def prompt_lengths(self) -> List[int]:
        n = self.prefixes.shape[1]
        return [n + s for s in self.suffix_lengths]

    def prompt(self, prefix_id: int, suffix_len: int,
               rng: np.random.Generator) -> np.ndarray:
        suffix = rng.integers(0, self.vocab, size=suffix_len,
                              dtype=np.int64).astype(np.int32)
        return np.concatenate([self.prefixes[prefix_id], suffix])

    # -------------------------------------------------------- requests
    def requests(self, stream: str, n: int, block: int = 0
                 ) -> List[Request]:
        """``n`` requests of ``stream`` in their order (block ``block`` of
        the stream: a closed loop draws blocks until its window ends); the
        open loop's ``due`` times are filled by `schedule`."""
        p = self.params
        rng = np.random.default_rng(seed_words(self.seed)
                                    + [STREAMS[stream], block])
        pre = p["prefixes"]
        prefix_ids = np.repeat(
            np.arange(pre["count"]),
            exact_counts(zipf_weights(pre["count"], pre["zipf_s"]), n))
        suffixes = np.repeat(self.suffix_lengths,
                             exact_counts([w for _, w in p["suffixes"]], n))
        rng.shuffle(prefix_ids)
        rng.shuffle(suffixes)
        out = []
        for i in range(n):
            out.append(Request(
                index=block * n + i,
                prompt=self.prompt(int(prefix_ids[i]), int(suffixes[i]), rng),
                prefix_id=int(prefix_ids[i]), suffix_len=int(suffixes[i]),
                n_samples=int(p["n_samples"]),
                max_new=int(p["max_new_tokens"]),
                temperature=float(p["temperature"]),
                tier=p["tier"]))
        return out

    def schedule(self, seconds: float, block: int = 0) -> List[Request]:
        """Open loop: ``round(rate * seconds)`` requests due over the
        window. The gaps are the exponential distribution's quantiles at
        ``(i + 1/2) / n``, shuffled, and scaled to end inside the window.
        ``block`` numbers windows that follow one another (a knee sweep)."""
        rate = float(self.params["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        reqs = self.requests("window", n, block)
        rng = np.random.default_rng(seed_words(self.seed) + [3, block])
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        rng.shuffle(gaps)
        due = np.cumsum(gaps)
        due = due / due[-1] * seconds * n / (n + 1)
        for r, t in zip(reqs, due):
            r.due = float(t)
        return reqs

    def blocks(self, stream: str, n: int = 256):
        """Requests of ``stream`` without end, ``n`` to a block."""
        block = 0
        while True:
            yield from self.requests(stream, n, block)
            block += 1
