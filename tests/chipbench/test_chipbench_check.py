"""A whole run on the CPU at a small size, with the device check skipped:
sound, it comes out correct; with the timed path broken underneath, it
comes out not correct."""
import time

import numpy as np
import pytest

from chipbench import registry
from chipbench.harness import run_cell
from chipbench.peaks import peaks_for

from small_cells import small

SEED = 2 ** 31 + 99
CELLS = ["chatglm3-6b-bf16.chat-shared", "chatglm3-6b-int8.passk-offline"]


def run(name: str):
    return run_cell(small(name), SEED, 2.0, False, time.perf_counter(),
                    peaks=peaks_for("TPU v5 lite"))


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, small_program):
    out = run(name)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out["compared"])[-1] == "bad_samples"
    assert set(out["metrics"]) == {m["name"] for m in
                                   registry.cell(name).end_to_end}


@pytest.mark.parametrize("name", CELLS)
def test_token_altered_where_produced_is_not_correct(name, small_program,
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
    out = run(name)
    assert not out["correct"]
    c = out["compared"]["max_lp_err"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_step_returning_its_state_unchanged_is_not_correct(
        name, small_program, monkeypatch):
    from repro.serving.backend import ExecutionBackend
    orig = ExecutionBackend._decode_step

    def stale(self, params, tok, step_pos, cache, *a, **kw):
        sample, lp, _ = orig(self, params, tok, step_pos, cache, *a, **kw)
        return sample, lp, cache          # the KV pool left as it was

    monkeypatch.setattr(ExecutionBackend, "_decode_step", stale)
    out = run(name)
    assert not out["correct"]
    c = out["compared"]["max_lp_err"]
    assert c["value"] > c["limit"]
