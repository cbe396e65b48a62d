"""Work counts and the table of peaks, at small and published shapes."""

import json
from pathlib import Path

import pytest

from benchmarks.chip import work
from benchmarks.chip.weights import Dims

CONFIGS = Path(__file__).resolve().parents[2] / "benchmarks" / "chip" / "configs"
V5E = "TPU v5 lite"


def dims(name):
    return Dims.from_doc(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_peaks_by_device_kind_and_unknown_kind_raises():
    p = work.peaks(V5E)
    assert (p["bf16_flop_s"], p["int8_op_s"], p["hbm_bytes_s"]) == (
        197e12, 393e12, 819e9)
    with pytest.raises(work.UnknownDevice):
        work.peaks("TPU v9 imaginary")


def test_lut_floor_counts_live_rows_not_the_padded_block():
    peak = work.peaks(V5E)
    live, _ = work.lut_matmul_floor(4, 2560, 9728, 4, peak)
    padded, _ = work.lut_matmul_floor(128, 2560, 9728, 4, peak)
    want = ((4 * 2560 + 2560 * 9728) * 0.5 + 4 * 4 * 9728) / 819e9
    assert live == pytest.approx(want) and live < padded
    assert work.lut_matmul_ops(4, 2560, 9728) == 2 * 4 * 2560 * 9728


def test_lut_floor_names_its_bound():
    peak = work.peaks(V5E)
    assert work.lut_matmul_floor(4, 64, 64, 4, peak)[1] == "memory"
    # square and large: operations outweigh the half-byte codes
    assert work.lut_matmul_floor(8192, 8192, 8192, 4, peak)[1] == "compute"


@pytest.mark.parametrize("bits,width", [(4, 0.5), (8, 1.0)])
def test_code_bytes_at_width(bits, width):
    m, k, n = 3, 5, 7
    assert work.lut_matmul_bytes(m, k, n, bits) == (m * k + k * n) * width \
        + 4 * m * n


def test_model_flops_per_token_against_hand_counts():
    # Qwen3-4B: 36 x (2560*4096 + 2*2560*1024 + 4096*2560 + 3*2560*9728)
    # matmul weights plus the tied head 2560*151936
    q = dims("qwen3-4b-w4")
    assert work.matmul_params(q) == 4_022_272_000
    assert work.flops_per_token(q, 0) == 8_044_544_000 + 4 * 32 * 128 * 36
    # StableLM-2-1.6B: 24 x (4*2048*2048 + 3*2048*5632) plus its own head
    s = Dims(layers=24, d_model=2048, heads=32, kv_heads=32, head_dim=64,
             d_ff=5632, vocab=100352, qk_norm=False, rope_theta=10000.0,
             norm_eps=1e-5, tied=False, lut_bits=8)
    assert work.matmul_params(s) == 1_438_646_272
    assert work.flops_per_token(s, 99) == 2_877_292_544 + 19_660_800
    assert work.mlp_shapes(2560, 9728) == [(2560, 9728), (2560, 9728),
                                           (9728, 2560)]
