"""``correct`` has to come out false where it should, at a size a test
run can hold (the toy cells of ``toy/manifest.json``, CPU, one device and
four virtual ones; a cell added there is tested here with no edit).

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
from benchmarks.lib import names, reference, weights

TOY = rehearse.TOY
with open(os.path.join(TOY, 'manifest.json')) as _f:
  CELLS = {w['name']: (w['config'], w['traffic'], w['chips'])
           for w in json.load(_f)['workloads']}
SEEDS = (7, 2**31 + 5, 123456789)
FAULTS = [(cell, fault) for cell, (_, _, chips) in sorted(CELLS.items())
          for fault in ('state_unchanged', 'half_batch')
          + (('no_exchange',) if chips > 1 else ())]


def _files(cell):
  config, mix, chips = CELLS[cell]
  return (names.load_json(TOY, 'configs', config),
          names.load_json(TOY, 'traffic', mix), chips)


def _fails(cell, numbers):
  limits = names.load_json(TOY, 'limits', cell)
  return [n for n, limit in limits.items() if numbers[n] > limit]


@pytest.fixture(scope='module')
def stated():
  out = {}
  for cell in CELLS:
    config, mix, chips = _files(cell)
    for seed in SEEDS:
      out[cell, seed] = reference.run_reference(config, mix, seed, chips=chips)
  return out


@pytest.mark.parametrize('seed', SEEDS)
@pytest.mark.parametrize('cell', sorted(CELLS))
def test_control_is_not_correct(stated, cell, seed):
  config, mix, chips = _files(cell)
  control = reference.run_reference(config, mix, seed, precision='control',
                                    chips=chips)
  numbers, _ = reference.compare(control, stated[cell, seed])
  assert _fails(cell, numbers), numbers


@pytest.mark.parametrize('seed', SEEDS[:2])
@pytest.mark.parametrize('cell,fault', FAULTS)
def test_fault_in_the_reference_is_not_correct(stated, cell, fault, seed):
  config, mix, chips = _files(cell)
  broken = reference.run_reference(config, mix, seed, fault=fault, chips=chips)
  numbers, _ = reference.compare(broken, stated[cell, seed])
  assert _fails(cell, numbers), numbers


def _plant(monkeypatch, fault):
  """Break the timed path underneath the harness, whatever the class."""
  import jax
  from distributed_embeddings_tpu import parallel
  real = parallel.make_hybrid_train_step
  if fault == 'state_unchanged':

    def broken(dist, head_loss_fn, dense_optimizer, emb_optimizer, **kw):
      raw = real(dist, head_loss_fn, dense_optimizer, emb_optimizer,
                 jit=False, **kw)

      def step(state, cats, batch):
        return state, raw(state, cats, batch)[1]

      run = lambda state, cats, batch: run.jitted(state, cats, batch)
      run.jitted = jax.jit(step)
      return run

    monkeypatch.setattr(parallel, 'make_hybrid_train_step', broken)
  elif fault == 'half_batch':
    half = lambda a: a[:a.shape[0] // 2]

    def broken(dist, head_loss_fn, *args, **kw):
      # the head sees the first half of every array: its mean is over
      # that half, and the other half gets no gradient
      def halved(dense, emb_outs, batch):
        return head_loss_fn(dense, tuple(half(e) for e in emb_outs),
                            jax.tree.map(half, batch))

      return real(dist, halved, *args, **kw)

    monkeypatch.setattr(parallel, 'make_hybrid_train_step', broken)
  elif fault == 'no_exchange':
    monkeypatch.setattr(jax.lax, 'all_to_all', lambda x, *a, **k: x)
  else:
    assert fault is None


@pytest.mark.parametrize(
    'cell,fault,correct',
    [(cell, None, True) for cell in sorted(CELLS)]
    + [(cell, fault, False) for cell, fault in FAULTS])
def test_run_with_the_timed_path_broken(monkeypatch, tmp_path, cell, fault,
                                        correct):
  _plant(monkeypatch, fault)
  result = rehearse.rehearse(cell, seed=2**31 + 77, trace=0, seconds=0.2,
                             cache_dir=str(tmp_path))
  assert result['correct'] is correct, result['compared']
  assert result['attempted'] > 0 and result['failed'] == 0


def test_readers_run_before_the_trace_is_deleted(monkeypatch, tmp_path):
  from benchmarks.lib import cell as cell_lib
  real, seen = cell_lib._function, []

  def spy(directory, name, function):
    found = real(directory, name, function)
    if directory != 'metrics':
      return found

    def read(context):
      seen.append(os.path.isdir(context['trace_dir'])
                  and 'phase_s' in context['trace'])
      return found(context)

    return read

  monkeypatch.setattr(cell_lib, '_function', spy)
  result = rehearse.rehearse(sorted(CELLS)[0], seed=5, trace=1, seconds=0.1,
                             cache_dir=str(tmp_path))
  assert result['correct'] and seen and all(seen)
  assert not os.path.exists(os.path.join(str(tmp_path), 'trace'))


def test_written_tables_match_the_reference_rows():
  """Every element ``make_tables`` writes is the row the reference
  computes from the seed, through the program's own layout (packed
  narrow groups, several tables to a shard, padding past the last)."""
  import jax
  from benchmarks.lib import program_state
  from distributed_embeddings_tpu.parallel import create_mesh
  config = names.load_json(TOY, 'configs', 'toy-synthetic')
  seed = 2**31 + 9
  model = names.resolve(config['builder'])(
      config, create_mesh(jax.devices()[:1]), seed)
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
