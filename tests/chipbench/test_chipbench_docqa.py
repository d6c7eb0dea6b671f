"""The cells this benchmark adds, at a size a CPU test can hold:
DeepSeek-V2-Lite's document QA comes out correct and its control does not;
chat-unshared's zero-token prefix runs through the generator, the warm-up
and the readers; the DeepSeek counts and readers against hand counts,
never above 100% for a device that does only the least work."""
import time

import numpy as np
import pytest

from chipbench import flops, flops_mla, harness, registry, readers_mla
from chipbench.harness import run_cell
from chipbench.peaks import peaks_for
from chipbench.stack import Call
from chipbench.traffic import Mix

import small_cells
import small_deepseek

DOCQA = "deepseek-v2-lite-ep4.docqa-offline"
UNSHARED = "chatglm3-6b-bf16.chat-unshared"
SEEDS = [2 ** 31 + 17, 23]
PEAKS = peaks_for("TPU v5 lite")


@pytest.fixture
def small_deepseek_program(monkeypatch):
    from chipbench import stack
    monkeypatch.setattr(stack, "arch_of", small_deepseek.small_arch)


def _captured_runs(monkeypatch):
    runs = []

    class Kept(harness.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runs.append(self)

    monkeypatch.setattr(harness, "Run", Kept)
    return runs


def test_docqa_is_correct_and_its_control_is_not(small_deepseek_program,
                                                 monkeypatch):
    # the published vocabulary: the head's log-softmax at its own width
    cell = small_deepseek.small(DOCQA, vocab_size=102400)
    fmt = cell.config["check"]["control"]
    runs = _captured_runs(monkeypatch)
    for seed in SEEDS:
        out = run_cell(cell, seed, 2.0, False, time.perf_counter(),
                       peaks=PEAKS, controls=[fmt])
        assert out["correct"], out["compared"]
        assert out["attempted"] > 0 and out["failed"] == 0
        assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
        ctrl = out["controls"][fmt]
        assert not ctrl["correct"], ctrl["compared"]
        c = ctrl["compared"]["max_lp_err"]
        assert c["value"] > c["limit"]
    run = runs[-1]
    # the questions hit their document's blocks in the pool
    assert registry.reader("prefix_hit_share.docqa")(run) > 50.0
    assert registry.reader("batch_seqs_mean.docqa")(run) >= 1.0
    assert registry.reader("decode_step_ms.docqa")(run) > 0.0


def test_docqa_token_altered_is_not_correct(small_deepseek_program,
                                            monkeypatch):
    from repro.serving.backend import ExecutionBackend
    orig = ExecutionBackend.decode_step

    def altered(self, h):
        more = orig(self, h)
        if h.step == 3:                   # one token of every sequence
            h.tok = (h.tok + 1) % self.model.cfg.vocab_size
            h.out_toks[-1] = np.asarray(h.tok)
        return more

    monkeypatch.setattr(ExecutionBackend, "decode_step", altered)
    out = run_cell(small_deepseek.small(DOCQA), SEEDS[0], 2.0, False,
                   time.perf_counter(), peaks=PEAKS)
    assert not out["correct"]


def test_zero_token_prefix_runs_through_mix_warm_up_and_readers(
        small_program, monkeypatch):
    mix = Mix(registry.traffic("chat-unshared"), 512, SEEDS[0])
    assert mix.prefixes.shape == (1, 0)
    assert mix.prompt_lengths == [1088, 1472]
    reqs = mix.schedule(10.0)
    assert {len(r.prompt) for r in reqs} == {1088, 1472}
    # the published vocabulary: the head's log-softmax at its own width
    cell = small_cells.small(UNSHARED, padded_vocab_size=65024)
    cell.traffic = dict(cell.traffic,
                        prefixes={"count": 1, "tokens": 0, "zipf_s": 0.0})
    fmt = cell.config["check"]["control"]
    runs = _captured_runs(monkeypatch)
    out = run_cell(cell, SEEDS[0], 2.0, False, time.perf_counter(),
                   peaks=PEAKS, controls=[fmt])
    assert out["correct"], out["compared"]
    assert not out["controls"][fmt]["correct"], out["controls"][fmt]
    assert set(out["metrics"]) == {"ttft_p90_ms", "tbt_p95_ms", "setup_s"}
    run = runs[-1]
    assert registry.reader("prefix_hit_share.unshared")(run) == 0.0
    assert registry.reader("prefill_ms_per_ktok.unshared")(run) > 0.0
    assert registry.reader("queue_wait_p90_ms.unshared")(run) >= 0.0


# ------------------------------------------------------------ the counts

def _s():
    # 3 layers (1 dense), d 8, 2 heads, latent 4, nope 2, rope 2, v 2,
    # dense ff 16, expert ff 4, 8 experts of which 2 held, top 2, 1 shared
    return flops_mla.MLASizes(layers=3, dense_layers=1, d_model=8, heads=2,
                              kv_lora=4, nope_dim=2, rope_dim=2, v_dim=2,
                              dense_ff=16, expert_ff=4, experts=8, held=2,
                              top_k=2, shared=1, vocab=32)


def test_mla_sizes_by_hand():
    s = _s()
    # q 8x8, kv_a 8x6, kv_b 4x8, o 4x8
    assert s.attn_params == 64 + 48 + 32 + 32
    assert s.latent_bytes_per_token == 3 * (6 * 2 + 4)
    assert s.replicated_bytes == 2 * (3 * (176 + 16 + 4) + 1 * 3 * 8 * 16
                                      + 2 * (8 * 8 + 96) + 8 + 8 * 32)
    assert s.held_hit(1) == pytest.approx(2 * 0.25)
    assert s.held_hit(1000) == pytest.approx(2.0)
    assert s.held_pairs(4) == 4 * 2 * 2 / 8


def test_token_flops_by_hand():
    s = _s()
    attn = 2 * 176 + 2 * 2 * 4 * 4 + 2 * 2 * 3 * (2 * 4 + 2)
    dense = 2 * 3 * 8 * 16
    moe = 2 * 8 * 8 + 2 * 96 + 2 * 96 * 2 * 2 / 8
    assert flops_mla.token_flops(s, 2, False) == pytest.approx(
        3 * attn + dense + 2 * moe)
    assert flops_mla.token_flops(s, 2, True) - flops_mla.token_flops(
        s, 2, False) == 2 * 8 * 32


def test_the_published_share_by_hand():
    s = flops_mla.MLASizes.of(registry.config("deepseek-v2-lite-ep4"))
    assert s.attn_params == 13_762_560
    assert s.latent_bytes_per_token == 27 * 1156
    assert s.held_hit(16) == pytest.approx(16 * (1 - (58 / 64) ** 16))


def _traced_run(calls, module_s, window_s=1.0):
    run = harness.Run(seconds=10.0, setup_s=0.0, sizes=None, peaks=PEAKS)
    run.calls = calls
    run.trace = {"window_s": window_s, "busy_s": window_s,
                 "module_s": module_s, "scope_s": {}, "op_s": {}}
    run.trace_span = (0.0, 10.0)
    return run


def _calls():
    pos = [16500 + i for i in range(16)]
    return [Call("start_batch", 1.0, 1.2, n_seqs=16, batch=0, plen=16480,
                 hit_counts=[1024] * 16, block_size=16),
            Call("decode_step", 1.3, 1.34, n_seqs=16, batch=0, plen=16480,
                 positions=pos)]


def test_decode_roofline_at_the_least_time_reads_100_and_no_more():
    """A device that needs exactly the least time reads 100%; the least
    bytes are those of the held experts the step's tokens hit, fewer than
    all held, so an implementation that skips idle experts stays at
    100%."""
    cfg = "deepseek-v2-lite-ep4"
    s = readers_mla.sizes(cfg)
    _, step = _calls()
    dec = flops.least_time(*flops_mla.decode_step_cost(s, step.positions),
                           PEAKS.bf16_flops, PEAKS.hbm_bytes_per_s)[0]
    run = _traced_run([step], {"jit__decode_step": dec})
    assert readers_mla.decode_roofline(run, cfg) == pytest.approx(100.0)
    _, nbytes = flops_mla.held_expert_cost(s, 16)
    assert nbytes == pytest.approx(26 * 2 * s.expert_params
                                   * s.held_hit(16))
    assert nbytes < 26 * 2 * s.expert_params * 16


def test_readers_count_prefill_tails_and_return_none_untraced():
    cfg = "deepseek-v2-lite-ep4"
    start, step = _calls()
    run = _traced_run([start, step], {"jit__decode_step": 0.04})
    s = readers_mla.sizes(cfg)
    tail = flops_mla.prefill_flops(s, 16384, 16480)
    want = 16 * tail + sum(flops_mla.token_flops(s, p, True)
                           for p in step.positions)
    assert readers_mla.mfu(run, cfg) == pytest.approx(
        100.0 * want / PEAKS.bf16_flops)
    for name in ("mfu.docqa", "decode_roofline.docqa"):
        assert 0.0 < registry.reader(name)(run) < 100.0, name
    run.trace = None
    run.trace_span = None
    for name in ("mfu.docqa", "decode_roofline.docqa"):
        assert registry.reader(name)(run) is None, name
