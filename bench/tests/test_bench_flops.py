"""Operation and byte counts of the kernels at the detectors' shapes, the
round's model FLOPs, and the peak table."""
from __future__ import annotations

import pytest

import bench_testkit  # noqa: F401  (puts the checkout on the path)

from bench import flops  # noqa: E402


@pytest.mark.parametrize("clients,rows", [(10, 54), (100, 49)])
def test_sign_align_counts(clients, rows):
    elems = clients * rows * 1024
    got = flops.sign_align(clients, rows)
    assert got["ops"] == 3 * elems
    partials = -(-rows // 8) * clients * 128 * 4
    assert got["bytes"] == 4 * elems + rows * 1024 + partials


@pytest.mark.parametrize("clients,rows", [(10, 54), (100, 49)])
def test_masked_agg_counts(clients, rows):
    elems = clients * rows * 1024
    got = flops.masked_agg(clients, rows)
    assert got["ops"] == 2 * elems
    assert got["bytes"] == 4 * elems + 4 * clients + 4 * rows * 1024


def test_arena_rows_match_the_configurations():
    unsw, road = [49, 256, 128, 64, 10], [32, 256, 128, 64, 2]
    for dims, n, rows in ((unsw, 54602, 54), (road, 49730, 49)):
        assert flops.weight_count(dims) + sum(dims[1:]) == n
        assert -(-n // flops.LANE) == rows


def test_round_flops():
    unsw = [49, 256, 128, 64, 10]
    # 10 silos x 16 steps x 256 rows, 6 per weight per sample
    assert flops.train_flops(unsw, 10 * 16 * 256) == 6 * 54144 * 40960
    assert flops.eval_flops(unsw, 100) == 2 * 54144 * 100


def test_roofline_is_memory_bound_for_the_kernels():
    peak = flops.peaks("TPU v5 lite")
    cost = flops.masked_agg(10, 54)
    assert flops.roofline_seconds(cost, peak) == pytest.approx(
        cost["bytes"] / 819e9)


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
