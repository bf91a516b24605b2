"""The peaks table and the required-work functions."""
import numpy as np
import pytest

from benchmarks.lib import peaks


def test_unknown_device_is_an_error():
  with pytest.raises(KeyError):
    peaks.peaks_for('cpu')
  assert peaks.peaks_for('TPU v5 lite')['hbm_bytes_per_s'] == 819e9


def test_distinct_row_bytes_counts_each_row_once():
  cats = [np.array([[1], [1], [2]]), np.array([[2, 2], [3, 1], [1, 1]]),
          np.array([[5], [5], [5]])]
  # inputs 0 and 1 share table 0 (rows 1, 2, 3), input 2 reads table 1
  assert peaks.distinct_row_bytes(cats, [0, 0, 1], [8, 16]) == (
      3 * 8 * 4 + 1 * 16 * 4)


def test_mlp_flops_and_floor():
  assert peaks.mlp_flops(10, [(4, 8), (8, 1)]) == 6 * 10 * (32 + 8)
  p = peaks.peaks_for('TPU v5 lite')
  floor, binds = peaks.step_floor_seconds(p, 197e12, 0, 1)
  assert floor == pytest.approx(1.0) and binds == 'flops'
  floor, binds = peaks.step_floor_seconds(p, 0, 819e9, 2)
  assert floor == pytest.approx(5.0) and binds == 'bytes'
