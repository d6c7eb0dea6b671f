"""Percentiles, and how a request with no first token by the window's end
enters the tail."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

from chipbench import readers
from chipbench.stats import percentile, weighted_percentile


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_is_numpys_linear(q):
    xs = [5.0, 1.0, 3.0, 9.0, 7.0, 2.0, 8.0]
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_empty():
    assert percentile([], 90) is None
    assert weighted_percentile([], 95) is None


def test_weighted_percentile_counts_each_sequence():
    # one gap of 10 ms in a batch of 9 sequences, one of 100 ms in 1
    pairs = [(10.0, 9), (100.0, 1)]
    assert weighted_percentile(pairs, 90) == 10.0
    assert weighted_percentile(pairs, 95) == 100.0


def _run(reqs, seconds=10.0, calls=()):
    return NS(seconds=seconds, reqs=reqs, calls=list(calls),
              compile_times=[], trace=None, trace_span=None)


def _req(due, first, started=None):
    return NS(due=due, first_token=first,
              started=first if started is None else started)


def test_ttft_censors_a_request_without_first_token():
    reqs = [_req(float(i), float(i) + 1.0) for i in range(9)]
    reqs.append(_req(9.0, None))          # due at 9 s, never served
    run = _run(reqs)
    # the stalled one enters with its age at the window's end, 1 s
    vals = [1.0] * 9 + [1.0]
    assert readers.ttft_p(run, 90) == pytest.approx(
        1e3 * np.percentile(vals, 90))
    reqs[-1] = _req(2.0, None)            # due at 2 s: age 8 s at the end
    run = _run(reqs)
    assert readers.ttft_p(run, 100) == pytest.approx(8000.0)


def test_ttft_first_token_after_the_window_is_censored():
    reqs = [_req(1.0, 2.0), _req(8.0, 30.0)]
    assert readers.ttft_p(_run(reqs), 100) == pytest.approx(2000.0)


def test_requests_due_after_the_window_are_not_counted():
    reqs = [_req(1.0, 1.5), _req(12.0, 12.1)]
    assert readers.ttft_p(_run(reqs), 100) == pytest.approx(500.0)


def _call(kind, begin, end, n, batch, **kw):
    base = dict(kind=kind, begin=begin, end=end, n_seqs=n, batch=batch,
                plen=64, positions=[], hit_counts=[], block_size=16)
    base.update(kw)
    return NS(**base)


def test_tbt_and_tokens_per_second():
    calls = [_call("start_batch", 0.0, 0.1, 2, 0, hit_counts=[2, 4]),
             _call("decode_step", 0.1, 0.15, 2, 0),
             _call("decode_step", 0.15, 0.25, 2, 0),
             _call("start_batch", 0.3, 0.4, 1, 1, hit_counts=[0]),
             _call("decode_step", 0.4, 0.42, 1, 1),
             _call("decode_step", 10.5, 10.6, 1, 1)]   # after the window
    run = _run([], seconds=1.0, calls=calls)
    # gaps: batch 0: 50, 100 ms (x2 seqs); batch 1: 20 ms (x1)
    assert readers.tbt_p(run, 100) == pytest.approx(100.0)
    assert readers.tbt_p(run, 20) == pytest.approx(20.0)
    assert readers.output_tok_s(run) == pytest.approx(8.0)
    assert readers.prefix_hit_share(run) == pytest.approx(
        100.0 * (6 * 16) / (3 * 64))
    assert readers.batch_seqs_mean(run) == pytest.approx(5 / 3)
