"""The trace reduction on a small synthetic trace: busy union, idle share,
kernel and module time, roofline share, and the breakdown."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from chipbench import flops, readers, tracefile
from chipbench.peaks import peaks_for

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_small.json")


@pytest.fixture
def trace():
    with open(FIXTURE) as f:
        return json.load(f)


def test_union_merges_overlaps():
    assert tracefile.union_ns([(0, 2), (1, 3), (5, 6), (6, 7)]) == \
        [[0, 3], [5, 7]]


def test_busy_idle_and_tables(trace):
    r = tracefile.reduce(trace, span=(0.0, 10e-6))
    # ops 1000-3000 and 2500-3500 merge to 2500 ns; plus 100, 1500, 500
    assert r["busy_s"] == pytest.approx(4600e-9)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["op_s"]["fusion.1"] == pytest.approx(2500e-9)
    assert tracefile.seconds_matching(r["scope_s"], "dequant_matmul") == \
        pytest.approx(1600e-9)
    assert tracefile.seconds_matching(r["op_s"], "dequant_matmul") == \
        pytest.approx(1500e-9)
    assert tracefile.seconds_matching(r["module_s"], "decode_step") == \
        pytest.approx(6500e-9)
    ops = r["breakdown"]["device_ops"]
    assert ops[0][0] == "fusion.1" and len(ops) == 4
    gaps = r["breakdown"]["idle_gaps"]
    # 3500 -> 5800 and 7500 -> 9000 start inside the decode_step
    # annotation (500 to 7700); 5900 -> 6000 too
    assert gaps[0] == ["decode_step", pytest.approx(2300e-9)]
    assert gaps[1] == ["decode_step", pytest.approx(1500e-9)]
    assert gaps[2] == ["decode_step", pytest.approx(100e-9)]


def test_window_defaults_to_first_and_last_op(trace):
    r = tracefile.reduce(trace)
    assert r["window_s"] == pytest.approx(8500e-9)


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        tracefile.reduce({"devices": [{"name": "d", "ops": [],
                                       "modules": []}], "host": []})


def _run(trace_dict, calls, sizes, span):
    r = tracefile.reduce(trace_dict, span=span)
    return NS(seconds=100.0, calls=calls, trace=r, trace_span=span,
              sizes=sizes, peaks=peaks_for("TPU v5 lite"), reqs=[],
              compile_times=[])


def _sizes(weights="int8"):
    return flops.Sizes(layers=1, d_model=256, heads=2, kv_heads=1,
                       head_dim=128, d_ff=512, vocab=1024, weights=weights)


def test_idle_share_and_kernel_roofline(trace):
    s = _sizes()
    calls = [NS(kind="decode_step", begin=1.0, end=2.0, n_seqs=4,
                positions=[10, 10, 20, 20], plen=8, hit_counts=[],
                block_size=16)]
    run = _run(trace, calls, s, span=(0.5, 10.0))
    run.trace["window_s"] = 10e-6
    assert readers.idle_share(run) == pytest.approx(54.0)
    least = sum(flops.least_time(*flops.dequant_matmul_cost(4, i, o),
                                 197e12, 819e9)[0] for i, o in s.linears)
    assert readers.dequant_matmul_roofline(run) == pytest.approx(
        100 * least / 1500e-9)
    f, b = flops.decode_step_cost(s, [10, 10, 20, 20])
    assert readers.decode_roofline(run) == pytest.approx(
        100 * max(f / 197e12, b / 819e9) / 6500e-9)
    assert readers.mfu(run) == pytest.approx(100 * f / 10e-6 / 197e12)


def test_readers_return_nothing_without_a_trace():
    run = NS(seconds=1.0, calls=[], trace=None, trace_span=None,
             sizes=_sizes(), peaks=peaks_for("TPU v5 lite"))
    for fn in (readers.idle_share, readers.mfu, readers.decode_roofline,
               readers.dequant_matmul_roofline):
        assert fn(run) is None


def test_bf16_has_no_kernel_roofline(trace):
    run = _run(trace, [], _sizes("bf16"), span=(0.0, 1.0))
    assert readers.dequant_matmul_roofline(run) is None


def test_a_container_op_is_busy_but_not_an_op():
    # a scanned layer stack: one ``while`` op around the ops of its body
    t = {"devices": [{"name": "/device:TPU:0", "modules": [], "ops": [
        ["%while.8 = (s32[]) while(...)", 0, 1000, ""],
        ["%fusion.1 = bf16[8] fusion(...)", 100, 300, ""],
        ["%dequant_matmul_int8_pallas.3 = bf16[8] custom-call(...)", 500,
         400, ""]]}], "host": []}
    r = tracefile.reduce(t, span=(0.0, 2e-6))
    assert r["busy_s"] == pytest.approx(1000e-9)
    assert r["op_s"] == {"fusion.1 = bf16[8]": pytest.approx(300e-9),
                         "dequant_matmul_int8_pallas.3 = bf16[8]":
                             pytest.approx(400e-9)}
    assert [n for n, _ in r["breakdown"]["device_ops"]] == [
        "dequant_matmul_int8_pallas.3 = bf16[8]", "fusion.1 = bf16[8]"]
