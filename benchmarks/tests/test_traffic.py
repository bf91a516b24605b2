"""The traffic generator: a seed changes the order of the work, never
its amount."""
import json
import os

import numpy as np

from benchmarks.lib import traffic

TOY = os.path.join(os.path.dirname(__file__), 'toy', 'traffic')


def _mix(name):
  with open(os.path.join(TOY, f'{name}.json')) as f:
    return json.load(f)


def test_train_pool_batches_depend_on_seed_and_index_alone():
  mix = _mix('toy-train')
  inputs = [(1000, 1), (1000, 10), (37, 1)]
  pool = traffic.train_pool(mix, inputs, 10, 9)
  first = traffic.train_pool(mix, inputs, 10, 9, batches=1)
  assert len(pool) == mix['pool_batches']
  for a, b in zip(pool[0][0], first[0][0]):
    assert np.array_equal(a, b)
  cats, numerical, labels = pool[1]
  assert [c.shape for c in cats] == [(512, 1), (512, 10), (512, 1)]
  assert numerical.shape == (512, 10) and 0 <= numerical.min() < numerical.max() < 1
  assert set(np.unique(labels)) == {0.0, 1.0}


def test_power_law_ids_skew_with_alpha_and_are_uniform_at_nought():
  uniform = traffic.power_law_ids(np.random.default_rng(1), 20000, 100, 0.0)
  skewed = traffic.power_law_ids(np.random.default_rng(1), 20000, 100, 1.05)
  assert uniform.min() == 0 and uniform.max() == 99
  assert np.bincount(uniform, minlength=100).max() < 300   # 200 each, flat
  assert np.bincount(skewed, minlength=100)[0] > 5 * np.bincount(uniform, minlength=100)[0]
