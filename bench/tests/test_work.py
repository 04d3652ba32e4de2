"""The inner epoch's algorithmic bytes and the table of peaks."""
from __future__ import annotations

import pytest

from small import REPO

import loader
import work


def test_epoch_bytes_at_the_rcv1_shape_match_a_hand_count():
    # 8 workers of 2,530 rows; every step samples one row of 74 nonzeros:
    # 74 x (4 B value + 4 B column) + 4 B label + 4 B anchor margin
    # + 74 x (4 B iterate read + 4 B iterate write + 4 B anchor gradient)
    per_step = 74 * 8 + 8 + 74 * 12
    assert per_step == 1488 == work.sample_bytes(74)
    # the final catch-up reads the iterate and the anchor gradient and
    # writes the iterate, over all 47,236 coordinates
    per_epoch = 2530 * 1488 + 3 * 47236 * 4
    assert per_epoch == 4_331_472 == work.epoch_bytes(2530, 1, 74, 47236)
    # one round on one chip: eight such epochs, 34.65 MB, ~42 us at HBM peak
    peak = loader.Benchmark(REPO).peak("TPU v5 lite")
    least = work.least_seconds(8 * per_epoch, peak)
    assert least == pytest.approx(34_651_776 / 819e9)
    assert 42e-6 < least < 43e-6


def test_batch_multiplies_the_sampled_rows_only():
    assert (work.epoch_bytes(100, 4, 10, 1000)
            == 400 * work.sample_bytes(10) + 12_000)


def test_unknown_device_kind_is_an_error_not_a_default():
    bench = loader.Benchmark(REPO)
    assert bench.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        bench.peak("TPU v4")
