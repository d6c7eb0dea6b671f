"""The traffic generator: deterministic per seed, with the stated lengths,
mix, rate and prefix sharing."""
import collections

import numpy as np
import pytest

from chipbench import registry
import itertools

from chipbench.traffic import Mix, exact_counts

BIG_SEED = 2 ** 31 + 12345
VOCAB = 65024


@pytest.fixture(scope="module")
def chat():
    return registry.traffic("chat-shared")


@pytest.fixture(scope="module")
def passk():
    return registry.traffic("passk-offline")


def _same(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due == y.due and x.temperature == y.temperature
        assert np.array_equal(x.prompt, y.prompt)


def test_deterministic_per_seed(chat):
    _same(Mix(chat, VOCAB, BIG_SEED).schedule(50),
          Mix(chat, VOCAB, BIG_SEED).schedule(50))
    a = Mix(chat, VOCAB, BIG_SEED).schedule(50)
    b = Mix(chat, VOCAB, BIG_SEED + 1).schedule(50)
    assert not np.array_equal(a[0].prompt, b[0].prompt)


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED, 2 ** 40 + 3])
def test_chat_lengths_mix_rate(chat, seed):
    reqs = Mix(chat, VOCAB, seed).schedule(50)
    n = round(chat["rate_per_s"] * 50)
    assert len(reqs) == n
    due = [r.due for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 50
    lens = collections.Counter(len(r.prompt) for r in reqs)
    assert set(lens) == {1024 + 64, 1024 + 448}
    assert lens[1088] == exact_counts([3, 1], n)[0]
    assert all(r.max_new == 64 and r.n_samples == 1 and r.tier == "standard"
               for r in reqs)
    # one temperature: every request can share a batch with any other of
    # its length
    assert all(r.temperature == 0.8 for r in reqs)
    assert all(((r.prompt >= 0) & (r.prompt < VOCAB)).all() for r in reqs)


def test_chat_prefix_sharing_is_zipf(chat):
    m = Mix(chat, VOCAB, BIG_SEED)
    reqs = m.schedule(50)
    counts = collections.Counter(r.prefix_id for r in reqs)
    assert counts[0] == max(counts.values())
    assert counts[0] >= 3 * counts[15]
    for r in reqs:                 # the prefix is the shared one, verbatim
        assert np.array_equal(r.prompt[:1024], m.prefixes[r.prefix_id])
    assert len({r.prompt[1024:].tobytes() for r in reqs}) == len(reqs)


def test_every_seed_gets_the_same_sizes_and_gaps(chat):
    a = Mix(chat, VOCAB, 1).schedule(50)
    b = Mix(chat, VOCAB, 2).schedule(50)
    for attr in (lambda r: len(r.prompt), lambda r: r.temperature,
                 lambda r: r.prefix_id):
        assert sorted(map(attr, a)) == sorted(map(attr, b))
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.due for r in rs]),
                                      9))
    assert gaps(a) == gaps(b)
    assert [r.due for r in a] != [r.due for r in b]


def test_passk_closed_loop(passk):
    assert passk["loop"] == "closed" and passk["outstanding"] == 16
    m = Mix(passk, VOCAB, BIG_SEED)
    reqs = list(itertools.islice(m.blocks("window"), 1024))
    assert len({r.prompt.tobytes() for r in reqs}) == 1024
    assert {len(r.prompt) for r in reqs} == {704 + 72}
    assert all(r.n_samples == 4 and r.max_new == 256 and r.tier == "economy"
               for r in reqs)
    assert all(r.temperature == 0.8 for r in reqs)
    again = list(itertools.islice(Mix(passk, VOCAB, BIG_SEED).blocks(
        "window"), 1024))
    assert all(np.array_equal(a.prompt, b.prompt) and
               a.temperature == b.temperature for a, b in zip(reqs, again))
    counts = collections.Counter(r.prefix_id for r in reqs)
    assert set(counts) == {0, 1} and abs(counts[0] - counts[1]) <= 1
    # a prompt length off the 16-token block: the copy-on-write fan-out of
    # the partial block runs for every request
    assert (704 + 72) % 16 != 0


def test_warmup_stream_differs_from_window(passk):
    m = Mix(passk, VOCAB, 5)
    w = m.requests("window", 4)
    u = m.requests("warmup", 4)
    assert not any(np.array_equal(a.prompt, b.prompt) for a in w for b in u)
    assert np.array_equal(w[0].prompt[:704], m.prefixes[w[0].prefix_id])
