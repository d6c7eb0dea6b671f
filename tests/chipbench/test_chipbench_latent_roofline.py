"""`latent_decode_roofline.docqa`: the latent attention's least time over
the paged latent decode kernel's device time, against a hand count."""
import pytest

from chipbench import harness, registry
from chipbench.peaks import peaks_for
from chipbench.stack import Call

PEAKS = peaks_for("TPU v5 lite")
METRIC = "latent_decode_roofline.docqa"


def _run(op_s, traced=True):
    run = harness.Run(seconds=10.0, setup_s=0.0, sizes=None, peaks=PEAKS)
    run.calls = [
        Call("start_batch", 1.0, 1.2, n_seqs=16, batch=0, plen=16480,
             hit_counts=[1024] * 16, block_size=16),
        Call("decode_step", 1.3, 1.34, n_seqs=16, batch=0, plen=16480,
             positions=[16500 + i for i in range(16)])]
    run.trace = {"window_s": 1.0, "busy_s": 1.0, "module_s": {},
                 "scope_s": {}, "op_s": op_s} if traced else None
    run.trace_span = (0.0, 10.0) if traced else None
    return run


def _least():
    """16 sequences at positions 16500..16515: 27 layers of 512 + 64 bf16
    and a 4-byte position a cached token, each read once; 16 heads score
    latent and rope key and weigh the latent (2 x 16 x 1088 FLOPs a key
    and layer), far under the ridge, so the bytes bound."""
    keys = sum(16501 + i for i in range(16))
    nbytes = keys * 27 * (2 * (512 + 64) + 4)
    assert keys * 27 * 2 * 16 * 1088 / PEAKS.bf16_flops < \
        nbytes / PEAKS.hbm_bytes_per_s
    return nbytes / PEAKS.hbm_bytes_per_s


def test_kernel_at_the_least_time_reads_100():
    read = registry.reader(METRIC)
    op = "paged_latent_decode_pallas.14___bf16_16_16_512_"
    assert read(_run({op: _least(), "fusion.1___bf16_16_2048_": 9.0})) \
        == pytest.approx(100.0)
    # two calls of the kernel (the prefix layer and the scan) add up
    assert read(_run({op: _least(), "paged_latent_decode_pallas.13":
                      _least()})) == pytest.approx(50.0)


def test_none_without_the_kernel_or_the_trace():
    read = registry.reader(METRIC)
    assert read(_run({"fusion.396___bf16_16608_16_512_": 0.98})) is None
    assert read(_run({}, traced=False)) is None
