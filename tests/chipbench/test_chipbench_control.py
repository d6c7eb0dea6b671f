"""The control: the reference in a precision below the configuration's,
put in the program's place, comes out not correct through the same
comparison and under the cell's own limit, where the program's run comes
out correct. At a size a CPU test run can hold, on three seeds."""
import time

import pytest

from chipbench.harness import run_cell
from chipbench.peaks import peaks_for

from small_cells import small

SEEDS = [2 ** 31 + 7, 11, 12]
CELLS = ["chatglm3-6b-bf16.chat-shared", "chatglm3-6b-int8.passk-offline"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, small_program):
    # the published vocabulary: the head's log-softmax at its own width
    cell = small(name, padded_vocab_size=65024)
    fmt = cell.config["check"]["control"]
    for seed in SEEDS:
        out = run_cell(cell, seed, 2.0, False, time.perf_counter(),
                       peaks=peaks_for("TPU v5 lite"), controls=[fmt])
        assert out["correct"], out["compared"]
        ctrl = out["controls"][fmt]
        assert not ctrl["correct"], ctrl["compared"]
        c = ctrl["compared"]["max_lp_err"]
        assert c["value"] > c["limit"] == out["compared"]["max_lp_err"][
            "limit"]
