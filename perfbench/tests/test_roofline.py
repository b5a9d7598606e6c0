"""The work function and the table of peaks: the share they yield for the
kernel times predicted (and for any time the chip could reach) lies
between 0 and 105%, and an unknown device is an error, not a default."""

import json
import os

import pytest

from perfbench import trace
from perfbench.layer_metrics import verify_roofline
from perfbench.rooflines import ed25519_verify

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = json.load(open(os.path.join(BENCH, "peaks.json")))


def test_v5e_peaks_carry_their_source():
    v5e = PEAKS["TPU v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["int8_ops_per_s"], v5e["hbm_bytes_per_s"], v5e["hbm_bytes"]) == (
        197e12, 393e12, 819e9, 16e9)
    assert "Google Cloud" in v5e["source"]
    assert PEAKS.get("TPU v4") is None  # a device that is not in the table has no peak


def test_work_per_row_is_the_derivation():
    w = ed25519_verify.work(1)
    assert ed25519_verify.FIELD_MULS_PER_ROW == 1162
    assert w["ops"] == 1162 * 2048 + 2 * 24384 and w["bytes"] == 257
    least = ed25519_verify.least_seconds(10_000, PEAKS["TPU v5 lite"])
    assert least["bound"] == "int8_ops" and 5e-5 < least["seconds"] < 7e-5


@pytest.mark.parametrize("rows, kernel_ms", [(9_450, 45.0), (9_450, 30.0), (120_960, 700.0), (16_384, 1.2)])
def test_share_for_predicted_kernel_times_is_a_share(rows, kernel_ms):
    reduced = trace.Reduced(
        window_s=1.0, busy_s=0.5, chips=1, requests=1,
        module_s={"jit_verify_stage_scan_tabled": kernel_ms * 0.8e-3,
                  "jit_verify_stage_prepare_tabled_gathered": kernel_ms * 0.2e-3, "jit_other": 9.0},
    )
    share = verify_roofline.read({"trace": reduced, "traced_rows": rows, "peaks": PEAKS["TPU v5 lite"]})
    assert 0.0 < share <= 105.0
