"""``correct`` has to come out false where it should, at a size a test
run can hold (the toy cells, CPU, one device and four virtual ones).

- The control: the reference put in the program's place, one step of
  precision lower in every part the configuration states.
- The faults a training cell can have, planted in the reference put in
  the program's place, and, through the whole harness with the chip look
  skipped (``rehearse.rehearse``), planted under the timed path: a step
  that returns its state unchanged, half of the batch left out with the
  mean over the rest, the exchange between chips left out.
The same readings at the cells' own sizes on the chip are in PERF.md.
"""
import json
import os

import numpy as np
import pytest

from benchmarks import rehearse
from benchmarks.lib import reference, weights

TOY = rehearse.TOY
CELLS = {'toy-synthetic-1': ('toy-synthetic', 1), 'toy-dlrm-4': ('toy-dlrm', 4)}
SEEDS = (7, 2**31 + 5, 123456789)


def _load(kind, name):
  with open(os.path.join(TOY, kind, f'{name}.json')) as f:
    return json.load(f)


def _fails(cell, numbers):
  limits = _load('limits', cell)
  return [n for n, limit in limits.items() if numbers[n] > limit]


@pytest.fixture(scope='module')
def stated():
  mix = _load('traffic', 'toy-train')
  return {(cell, seed): reference.run_reference(
      _load('configs', config), mix, seed, chips=chips)
          for cell, (config, chips) in CELLS.items() for seed in SEEDS}


@pytest.mark.parametrize('seed', SEEDS)
@pytest.mark.parametrize('cell', sorted(CELLS))
def test_control_is_not_correct(stated, cell, seed):
  config, chips = CELLS[cell]
  control = reference.run_reference(_load('configs', config),
                                    _load('traffic', 'toy-train'), seed,
                                    precision='control', chips=chips)
  numbers, _ = reference.compare(control, stated[cell, seed])
  assert _fails(cell, numbers), numbers


@pytest.mark.parametrize('seed', SEEDS[:2])
@pytest.mark.parametrize('cell,fault', [
    ('toy-synthetic-1', 'state_unchanged'), ('toy-synthetic-1', 'half_batch'),
    ('toy-dlrm-4', 'state_unchanged'), ('toy-dlrm-4', 'half_batch'),
    ('toy-dlrm-4', 'no_exchange')])
def test_fault_in_the_reference_is_not_correct(stated, cell, fault, seed):
  config, chips = CELLS[cell]
  broken = reference.run_reference(_load('configs', config),
                                   _load('traffic', 'toy-train'), seed,
                                   fault=fault, chips=chips)
  numbers, _ = reference.compare(broken, stated[cell, seed])
  assert _fails(cell, numbers), numbers


def _plant(monkeypatch, fault):
  import jax
  from distributed_embeddings_tpu import parallel
  from distributed_embeddings_tpu.models import dlrm as dlrm_model
  if fault == 'state_unchanged':
    real = parallel.make_hybrid_train_step

    def broken(dist, head_loss_fn, dense_optimizer, emb_optimizer):
      raw = real(dist, head_loss_fn, dense_optimizer, emb_optimizer,
                 jit=False)

      def step(state, cats, batch):
        return state, raw(state, cats, batch)[1]

      run = lambda state, cats, batch: run.jitted(state, cats, batch)
      run.jitted = jax.jit(step)
      return run

    monkeypatch.setattr(parallel, 'make_hybrid_train_step', broken)
  elif fault == 'half_batch':
    real_loss = dlrm_model.bce_with_logits

    def half(logits, labels):
      n = logits.shape[0] // 2
      return real_loss(logits[:n], labels[:n])

    monkeypatch.setattr(dlrm_model, 'bce_with_logits', half)
  elif fault == 'no_exchange':
    monkeypatch.setattr(jax.lax, 'all_to_all', lambda x, *a, **k: x)
  else:
    assert fault is None


@pytest.mark.parametrize('cell,fault,correct', [
    ('toy-synthetic-1', None, True),
    ('toy-synthetic-1', 'state_unchanged', False),
    ('toy-synthetic-1', 'half_batch', False),
    ('toy-dlrm-4', None, True),
    ('toy-dlrm-4', 'state_unchanged', False),
    ('toy-dlrm-4', 'half_batch', False),
    ('toy-dlrm-4', 'no_exchange', False)])
def test_run_with_the_timed_path_broken(monkeypatch, tmp_path, cell, fault,
                                        correct):
  _plant(monkeypatch, fault)
  result = rehearse.rehearse(cell, seed=2**31 + 77, trace=0, seconds=0.2,
                             cache_dir=str(tmp_path))
  assert result['correct'] is correct, result['compared']
  assert result['attempted'] > 0 and result['failed'] == 0


def test_written_tables_match_the_reference_rows():
  """Every element ``make_tables`` writes is the row the reference
  computes from the seed, through the program's own layout (packed
  narrow groups, several tables to a shard, padding past the last)."""
  import jax
  from benchmarks.lib import builders, program_state
  from distributed_embeddings_tpu.parallel import create_mesh
  config = _load('configs', 'toy-synthetic')
  seed = 2**31 + 9
  model = builders.synthetic(config, create_mesh(jax.devices()[:1]), seed)
  layout = program_state.table_layout(model.dist)
  words = weights.table_words(seed, len(model.tables))
  params = program_state.make_tables(model.dist, layout, model.tables, words)
  claimed = {key: 0 for key in params}
  for tid, (key, dev, start, count) in enumerate(layout):
    rows, width, half = model.tables[tid]
    flat = np.asarray(params[key][dev]).reshape(-1)
    want = weights.numpy_rows(words[tid], np.arange(rows), width, half)
    assert np.array_equal(flat[start:start + count], want.reshape(-1))
    assert np.abs(want).max() <= half and want.std() > 0.4 * half
    claimed[key] = max(claimed[key], start + count)
  for key, end in claimed.items():
    assert not np.asarray(params[key]).reshape(-1)[end:].any()
