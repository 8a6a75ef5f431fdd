"""Model FLOPs from shapes, and the peaks table."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import flops, peaks  # noqa: E402


def _config(name):
    with open(ROOT / "benchmarks" / "chip" / "configs" / f"{name}.json") as f:
        return json.load(f)


# Hand counts (per optimizer step, all chips' rows):
# qwen1.5-4b, 8 layers, vocab 37,984: matmul params
#   8 * (4 * 2560^2 + 3 * 2560 * 6912) + 2560 * 37,984 = 731.6 M,
#   6 * 731.6 M * 2048 tokens = 8.99e12, attention
#   12 * 8 * 2560 * 512 * 2048 = 0.26e12; 9.25e12 in all.
# smollm-360m per chip (4 x 512): matmul params
#   32 * (2 * 960^2 + 2 * 960 * 320 + 3 * 960 * 2560) + 960 * 49,152
#   = 361.8 M, 6 * 361.8 M * 2048 = 4.45e12, attention
#   12 * 32 * 960 * 512 * 2048 = 0.39e12; 4.83e12 in all.
@pytest.mark.parametrize("name,rows,expect", [
    ("qwen1.5-4b", 4, 9.25e12),
    ("smollm-360m", 4, 4.83e12),
])
def test_train_step_flops_match_hand_counts(name, rows, expect):
    got = flops.train_step_flops(_config(name), rows, 512)
    assert got == pytest.approx(expect, rel=5e-3)


def test_matmul_params_exact():
    assert flops.matmul_params(_config("smollm-360m")) == (
        32 * (2 * 960 ** 2 + 2 * 960 * 320 + 3 * 960 * 2560) + 960 * 49152)
    assert flops.matmul_params(_config("qwen1.5-4b")) == (
        8 * (4 * 2560 ** 2 + 3 * 2560 * 6912) + 2560 * 37984)


def test_flops_scale_with_global_batch():
    cfg = _config("smollm-360m")
    assert flops.train_step_flops(cfg, 16, 512) == pytest.approx(
        4 * flops.train_step_flops(cfg, 4, 512))


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p.flops_bf16 == 197e12 and p.hbm_bw == 819e9
    assert "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks(kind)
