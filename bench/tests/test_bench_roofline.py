"""The work and bytes per operation, pinned to hand-computed values and
to the program's own cost model as it stands."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import roofline as R  # noqa: E402


def test_division_work_at_2p15_bits():
    # windows of the 13 Refine iterations at M = 2048 limbs:
    # 32, 32, 32, 32, 48, 80, 144, 272, 528, 1040, 2048, 2048, 2048
    squares = 4 * 32 ** 2 + 48 ** 2 + 80 ** 2 + 144 ** 2 + 272 ** 2 \
        + 528 ** 2 + 1040 ** 2 + 3 * 2048 ** 2
    assert squares == 14050816
    refine = 2 * squares                     # two products an iteration
    final = 2 * 2048 ** 2                    # u * shinv and q * v
    assert refine + final == 36490240        # limb products
    assert R.divmod_ops(2048) == 291921920   # x 4 sub-digit MACs x 2
    assert R.divmod_bytes(2048) == 32768     # u, v, q, r x 2048 x 4 B


def test_modexp_work_at_rsa2048():
    lad = R.modexp_ladder(2048, 4)
    assert lad["modmuls"] == 2048 + 16 + 512 == 2576
    per_modmul = 128 * 128 + 2 * (128 * 129 // 2)
    assert per_modmul == 32896
    limb_products = 2576 * per_modmul + 2 * 16512
    assert limb_products == 84773120
    assert R.modexp_ops(128, 128, 4) == 8 * limb_products == 678184960
    assert R.modexp_bytes(128, 128) == 1536


@pytest.mark.parametrize("m", [8, 100, 2048, 16384])
def test_copy_agrees_with_the_programs_cost_model(m):
    from repro.obs import costmodel as CM
    assert R.refine_iters(m) == CM.refine_iters(m)
    assert R.refine_mul_work(m) == pytest.approx(CM.refine_mul_work(m),
                                                 rel=1e-12)
    for i in range(R.refine_iters(m)):
        assert R.refine_window(i, m) == CM.refine_window(i, m)


def test_ladder_and_launch_model_agree_with_the_cost_model():
    from repro.obs import costmodel as CM
    assert R.modexp_ladder(2048, 4)["modmuls"] == \
        CM.modexp_ladder(2048, 4)["modmuls"] == 2576
    sys.path.insert(0, str(BENCH / "ops"))
    import harness
    div = harness.load_module(BENCH / "ops" / "divmod.py")
    mex = harness.load_module(BENCH / "ops" / "modexp.py")
    cfg_d = harness.Cell.load("div2p15-batch").cfg
    cfg_m = harness.Cell.load("modexp2048-onekey").cfg
    assert div.model_launches_per_call(cfg_d) == CM.divmod_launches(2048) == 27
    assert mex.model_launches_per_call(cfg_m) == CM.modexp_launches(2048) == 5154
    assert div.ops_per_row(cfg_d) == 291921920
    assert mex.ops_per_row(cfg_m) == 678184960


def test_the_int8_peak_is_the_one_used():
    peaks = R.load_peaks("TPU v5 lite")
    assert peaks["int8_ops_per_s"] == 393e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    share, bound = R.roofline(393e12, 0, 1.0, peaks)
    assert (share, bound) == (100.0, "int8_ops")
    share, bound = R.roofline(0, 819e9, 2.0, peaks)
    assert (share, bound) == (50.0, "hbm_bytes")


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        R.load_peaks("TPU v4")
