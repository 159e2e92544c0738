"""The work model: operations and bytes of a GEMM, least time, peaks."""
import pytest

from bench import network, work

V5E = {"ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_ops_and_bytes_of_a_hand_counted_gemm():
    # ResNet-50's 3x3 of the first stage: 56*56 rows, K = 64*9, D = 64.
    g = network.Gemm("s56b0_3x3", 3136, 576, 64)
    assert work.gemm_ops(g, 2) == 2 * 2 * 3136 * 576 * 64
    # x codes (1 byte) + weight codes once + float32 outputs
    assert work.gemm_bytes(g, 2) == 2 * 3136 * 576 + 576 * 64 \
        + 4 * 2 * 3136 * 64


def test_depthwise_counts_its_groups():
    g = network.Gemm("dw", 3136, 9, 1, count=144)
    assert work.gemm_ops(g, 1) == 2 * 3136 * 9 * 144
    assert work.gemm_bytes(g, 1) == 3136 * 9 * 144 + 9 * 144 + 4 * 3136 * 144


def test_least_time_takes_the_larger_bound_per_gemm():
    dense = network.Gemm("big", 4096, 4096, 4096)       # compute-bound
    thin = network.Gemm("thin", 100000, 3, 1)           # bytes-bound
    t_dense = 2 * 4096 ** 3 / V5E["ops_per_s"]
    t_thin = (100000 * 3 + 3 + 4 * 100000) / V5E["hbm_bytes_per_s"]
    assert work.least_time_s([dense, thin], 1, V5E) == pytest.approx(
        t_dense + t_thin)


def test_peak_table():
    assert work.peaks("TPU v5 lite")["ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="not in the peak table"):
        work.peaks("TPU v9 imaginary")
