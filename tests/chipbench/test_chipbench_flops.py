"""FLOP and byte functions against hand counts at small shapes, and the
peaks table."""
import pytest

from chipbench import flops
from chipbench.peaks import peaks_for


def _s(weights="bf16"):
    # d 8, 2 heads of 4, 1 kv head, ff 16, vocab 32, 2 layers
    return flops.Sizes(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                       d_ff=16, vocab=32, weights=weights)


def test_linear_params_by_hand():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, gate/up 8x16, down 16x8
    assert _s().linear_params == 64 + 32 + 32 + 64 + 128 + 128 + 128


def test_token_flops_by_hand():
    s = _s()
    lin = 2 * 2 * 576
    attn = 4 * 2 * 2 * 4 * 3          # position 2 attends 3 keys
    assert flops.token_flops(s, 2, False) == lin + attn
    assert flops.token_flops(s, 2, True) == lin + attn + 2 * 8 * 32


def test_prefill_flops_sums_positions():
    s = _s()
    want = sum(flops.token_flops(s, p, False) for p in range(3, 7)) \
        + 2 * 8 * 32
    assert flops.prefill_flops(s, 3, 7) == pytest.approx(want)


def test_kv_and_weight_bytes_by_hand():
    s = _s()
    assert s.kv_bytes_per_token == 2 * (2 * 1 * 4 * 2 + 4)
    bias = 2 * 2 * (2 + 2) * 4
    norms = 5 * 8 * 2
    head = 8 * 32 * 2
    assert s.weight_bytes_read == 2 * 576 * 2 + bias + norms + head
    i8 = _s("int8")
    scales = 2 * 4 * (8 + 4 + 4 + 8 + 16 + 16 + 8)
    assert i8.weight_bytes_read == 2 * 576 + scales + bias + norms + head


def test_decode_step_cost():
    s = _s()
    f, b = flops.decode_step_cost(s, [4, 9])
    assert f == flops.token_flops(s, 4, True) + flops.token_flops(s, 9, True)
    assert b == s.weight_bytes_read + (5 + 10) * s.kv_bytes_per_token


def test_dequant_matmul_cost_by_hand():
    f, b = flops.dequant_matmul_cost(2, 8, 4)
    assert f == 2 * 2 * 8 * 4
    assert b == 8 * 4 + 4 * 4 + 2 * 2 * 8 + 2 * 2 * 4


def test_least_time_names_its_bound():
    assert flops.least_time(2e12, 1e6, 1e12, 1e9) == (2.0, "compute")
    assert flops.least_time(1e9, 4e9, 1e12, 1e9) == (4.0, "memory")


def test_forward_dequant_calls():
    calls = flops.forward_dequant_calls(_s("int8"), 3)
    assert len(calls) == 2 * 7 and all(c["m"] == 3 for c in calls)


def test_peaks_table():
    p = peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s) == (197e12, 819e9)
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("cpu")
