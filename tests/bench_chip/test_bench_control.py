"""The check's control at a size the CPU can hold, through the harness's
own ``correct``: the reference with float8 matmul operands, read at the
positions of the program's served tokens, must read not correct where
the program's own tokens of the same run read correct.

Each fixture's limit sits between the readings of these seeds on the
CPU, 96 served tokens each: at W8 (limit 0.15) the program's widest gap
0.022–0.108 and the control's 0.257–0.549; at W4 with the cell's
initialisation (limit 0.1) 0.001–0.038 and 0.349–0.536.
"""

import time

import pytest

from benchmarks.chip import harness, trace, work

import tiny


@pytest.mark.parametrize("config", ["stablelm-tiny-w8", "qwen3-tiny-w4"])
@pytest.mark.parametrize("seed", [1, 2, 2**32 + 7])
def test_the_float8_control_fails_where_the_program_passes(
        seed, config, tmp_path, monkeypatch):
    cell = tiny.cell(tmp_path, config, "tiny-batch")
    monkeypatch.setitem(cell.traffic, "lead_in_steps", 10)
    monkeypatch.setattr(work, "PEAKS", tiny.FIXTURES / "cpu-peaks.json")
    monkeypatch.setattr(trace, "DEVICE_PLANE", r"^/host:CPU$")
    device = harness.device_info(cell.chips, require_tpu=False)
    res = harness.run_cell(cell, seed, 0.4, False, time.perf_counter(),
                           device, tiny.ROOT, tmp_path / "scratch",
                           control=True)
    assert res["program_correct"] and not res["correct"]
    gap = res["check"]["served_logit_gap"]
    assert res["program_check"]["served_logit_gap"]["value"] <= \
        gap["limit"] < gap["value"]
