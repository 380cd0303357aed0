"""Percentile, TPOT and spread arithmetic of perfbench/harness/numbers.py,
the op counts and the peaks table."""
import numpy as np
import pytest

from perfbench.harness import numbers, opcount, peaks


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100])
def test_percentile_matches_numpy(q, n):
    xs = list(np.random.default_rng(n).normal(size=n))
    assert numbers.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_none():
    assert numbers.percentile([], 90) is None
    assert numbers.median([]) is None


def test_tpot_is_per_request_not_per_gap():
    # 9 tokens: first at 1.0 s, last at 1.8 s, delivered in two bursts
    assert numbers.tpot_s(1.0, 1.8, 9) == pytest.approx(0.1)
    assert numbers.tpot_s(1.0, 1.0, 1) is None


def test_spread_is_interquartile_over_median():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0]
    assert numbers.spread(xs) == pytest.approx((13.0 - 11.0) / 12.0)


def test_resnet50_macs_match_the_published_count():
    # 4.09e9 multiply-adds at 224x224 with the projection shortcuts
    # and the classifier (the paper's 3.8e9 leaves the shortcuts out)
    assert opcount.resnet_forward_macs(50, 224, 1000) == pytest.approx(
        4.09e9, rel=0.01)
    assert opcount.resnet_train_flops_per_image() == pytest.approx(
        6 * opcount.resnet_forward_macs())


def test_gpt2_flops_per_token():
    f = opcount.transformer_train_flops_per_token(50257, 768, 12, 3072, 1024)
    # 6 * (85.0e6 block parameters + 38.6e6 head) + attention term
    assert f == pytest.approx(6 * (12 * 7.078e6 + 38.6e6)
                              + 3 * 12 * 2 * 1024 * 768, rel=0.01)


def test_attention_counts_and_which_bound_binds():
    ops, nbytes = opcount.attention_fwd_bwd(16, 12, 1024, 64, itemsize=2)
    assert ops == 16 * 12 * 6 * 2 * (1024 * 1024 / 2) * 64
    assert nbytes == 16 * 12 * 12 * 1024 * 64 * 2
    p = peaks.for_kind("TPU v5 lite")
    least, bound = opcount.roofline_seconds(ops, nbytes, p)
    assert bound == "compute" and least == pytest.approx(ops / 197e12)
    assert opcount.roofline_seconds(1.0, 1e9, p)[1] == "memory"


def test_peaks_table_and_unknown_kind():
    p = peaks.for_kind("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.for_kind("cpu")
