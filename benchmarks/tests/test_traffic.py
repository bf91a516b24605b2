"""The traffic generator: a seed changes the order of the work, never
its amount."""
import json
import os

import numpy as np
import pytest

from benchmarks.lib import traffic

TOY = os.path.join(os.path.dirname(__file__), 'toy', 'traffic')


def _mix(name):
  with open(os.path.join(TOY, f'{name}.json')) as f:
    return json.load(f)


def test_train_pool_batches_depend_on_seed_and_index_alone():
  mix = _mix('toy-train')
  inputs = [(1000, 1), (1000, 10), (37, 1)]
  config = {'num_numerical_features': 10}
  pool = traffic.train_pool(mix, inputs, config, 9)
  first = traffic.train_pool(mix, inputs, config, 9, batches=1)
  assert len(pool) == mix['pool_batches']
  for a, b in zip(pool[0][0], first[0][0]):
    assert np.array_equal(a, b)
  cats, (numerical, labels) = pool[1]
  assert [c.shape for c in cats] == [(512, 1), (512, 10), (512, 1)]
  assert numerical.shape == (512, 10) and 0 <= numerical.min() < numerical.max() < 1
  assert set(np.unique(labels)) == {0.0, 1.0}


def test_power_law_ids_skew_with_alpha_and_are_uniform_at_nought():
  uniform = traffic.power_law_ids(np.random.default_rng(1), 20000, 100, 0.0)
  skewed = traffic.power_law_ids(np.random.default_rng(1), 20000, 100, 1.05)
  assert uniform.min() == 0 and uniform.max() == 99
  assert np.bincount(uniform, minlength=100).max() < 300   # 200 each, flat
  assert np.bincount(skewed, minlength=100)[0] > 5 * np.bincount(uniform, minlength=100)[0]


TOKENS = {'global_batch': 8, 'seq_len': 64, 'alpha': 1.05, 'pool_batches': 3,
          'doc_len_median': 6, 'doc_len_sigma': 1.2}
SEEDS = (1, 22, 2**31 + 333, 4444, 2**31 + 2**20)


def _tokens(seed, **mix):
  return traffic.train_tokens({**TOKENS, **mix}, [(500, 1)], {}, seed)


def test_train_tokens_shapes_do_not_depend_on_the_seed():
  shapes = set()
  for seed in SEEDS:
    pool = _tokens(seed)
    assert len(pool) == 3
    shapes.add(tuple((tuple(c.shape for c in cats), targets.shape,
                      segments.shape, targets.dtype, segments.dtype)
                     for cats, (targets, segments) in pool))
    first = traffic.train_tokens(TOKENS, [(500, 1)], {}, seed, batches=1)
    assert np.array_equal(first[0][0][0], pool[0][0][0])
    assert np.array_equal(first[0][1][0], pool[0][1][0])
  assert shapes == {(((((512, 1),), (8, 64), (8, 64), np.dtype('int32'),
                       np.dtype('int32')),) * 3)}


def test_train_tokens_targets_stay_inside_a_document():
  for seed in SEEDS:
    for cats, (targets, segments) in _tokens(seed):
      ids = cats[0].reshape(targets.shape)
      assert ids.min() >= 0 and ids.max() < 500      # no padding id
      assert segments[:, 0].tolist() == [0] * 8
      step = np.diff(segments, axis=1)
      assert set(np.unique(step)) <= {0, 1}          # documents end to end
      # a document's last position: the next one opens another, or the
      # sequence ends; there, and nowhere else, the target is -1
      last = np.concatenate([step == 1, np.ones((8, 1), bool)], axis=1)
      assert np.array_equal(targets == -1, last)
      # elsewhere the target is the next id, which the same document holds
      inside = ~last
      assert np.array_equal(targets[inside], np.roll(ids, -1, axis=1)[inside])
      assert np.array_equal(segments[inside],
                            np.roll(segments, -1, axis=1)[inside])


def test_train_tokens_document_lengths_follow_the_mix():
  def lengths(**mix):
    out = []
    for _, (targets, _) in _tokens(5, global_batch=64, seq_len=256, **mix):
      flat = (targets == -1).reshape(-1)
      out.append(np.diff(np.flatnonzero(flat)))
    return np.concatenate(out)
  short, long = lengths(doc_len_median=4), lengths(doc_len_median=40)
  assert 3 <= np.median(short) <= 5 and 25 <= np.median(long) <= 45
  # heavy-tailed: the longest documents are many medians long
  assert short.max() > 10 * np.median(short)


def test_train_tokens_takes_one_table_of_single_lookups():
  with pytest.raises(ValueError):
    traffic.train_tokens(TOKENS, [(500, 2)], {}, 1)
  with pytest.raises(ValueError):
    traffic.train_tokens(TOKENS, [(500, 1), (20, 1)], {}, 1)
