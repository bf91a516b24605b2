"""The three phase readers PR 35 brought (``route_ms``, ``residual_ms``,
``attention_core_ms``): on traces recorded on the chip, on a step
compiled before their phases existed (nothing to read, and no raise:
the parent's side of a check), and in the manifest.
"""
import json
import os

import pytest

from benchmarks.lib import cell as cell_lib
from benchmarks.lib import layer, xtrace

DATA = os.path.join(os.path.dirname(__file__), 'data')
NEW = ('route_ms', 'residual_ms', 'attention_core_ms')
LANGUAGE_MODELS = ['granite-train-packed', 'trinity-train-packed-8k',
                   'lfm2-train-packed-8k']


def _read(name, trace):
  return cell_lib._function('metrics', name, 'read')({'trace': trace})


@pytest.fixture(scope='module')
def hybrid():
  # three steps of the small hybrid step (``dev/record_hybrid_trace.py``)
  # from PR 35's tree: ``residual`` and ``attention/core`` are in it
  return xtrace.reduce_trace(
      os.path.join(DATA, 'v5e_hybrid_step_scoped.trace.json.gz'),
      program='jit_step')


@pytest.fixture(scope='module')
def before():
  # the same step recorded under PR 27: neither phase existed
  return xtrace.reduce_trace(
      os.path.join(DATA, 'v5e_hybrid_step.trace.json.gz'),
      program='jit_step')


@pytest.fixture(scope='module')
def recommender():
  # three steps of a three-group recommender step (PR 24)
  return xtrace.reduce_trace(
      os.path.join(DATA, 'v5e_scoped_step.trace.json.gz'),
      program='jit_step')


def test_the_manifest_names_each_reader_with_its_cells():
  with open(os.path.join(os.path.dirname(cell_lib.BENCH_DIR),
                         'BENCHMARK.json')) as f:
    manifest = json.load(f)
  entries = {m['name']: m for m in manifest['per_layer']}
  assert [m['name'] for m in manifest['per_layer'][-3:]] == list(NEW)
  for name in NEW:
    entry = entries[name]
    assert callable(cell_lib._function('metrics', name, 'read'))
    assert (entry['source'], entry['moves'], entry['unit']) == (
        'device_trace', 'train_samples_per_s', 'ms')
  assert entries['route_ms']['layer'] == 'route + exchange'
  assert entries['route_ms']['workloads'] == [
      'tiny-train-zipf', 'tiny-train-uniform', 'dlrm-train-4chip']
  for name in ('residual_ms', 'attention_core_ms'):
    assert entries[name]['layer'] == 'dense head'
    assert entries[name]['workloads'] == LANGUAGE_MODELS
  # every cell of a new reader also reports the accepted reader beside it
  assert set(LANGUAGE_MODELS) <= set(entries['attention_ms']['workloads'])


def test_route_ms_sums_both_route_phases(recommender):
  parts = [layer.phase_ms({'trace': recommender}, p)
           for p in ('fwd/route', 'bwd/route')]
  assert parts[0] == pytest.approx(0.0473, abs=6e-5)
  assert _read('route_ms', recommender) == pytest.approx(
      sum(p for p in parts if p is not None))


@pytest.mark.parametrize('name', ['residual_ms', 'attention_core_ms'])
def test_a_step_compiled_before_the_phases_reads_nothing(before, name):
  assert before['steps'] == 3
  assert _read(name, before) is None
  # and a step with no language model in it: the recommender's
  assert _read(name, {**before, 'phase_s': {'/device:TPU:0': {'head': 1.0}}}
               ) is None


def test_route_ms_reads_nothing_in_a_step_without_a_route(before):
  assert _read('route_ms', {**before, 'phase_s': {
      '/device:TPU:0': {'head': 1.0}}}) is None


@pytest.mark.parametrize('name,value', [
    ('residual_ms', 0.00820716), ('attention_core_ms', 0.0531840),
    ('attention_ms', 0.0680710)])
def test_the_new_phases_on_the_recorded_step(hybrid, name, value):
  """Three steps of the small hybrid step on one v5e (my chip run, PR
  35): the blocks' norms and residual adds have a number of their own,
  and attention's core is told apart from what stands around it."""
  assert hybrid['steps'] == 3 and hybrid['module'] == 'jit_step'
  assert _read(name, hybrid) == pytest.approx(value, rel=1e-4)


def test_the_core_lies_inside_attention_and_residual_outside_it(hybrid):
  phases = hybrid['phase_s'][hybrid['fullest']]
  core = {p for p in phases if layer.under(p, 'attention/core')}
  assert core and all(layer.under(p, 'attention') for p in core)
  assert _read('attention_core_ms', hybrid) < _read('attention_ms', hybrid)
  residual = {p for p in phases if layer.under(p, 'residual')}
  assert residual and all(
      layer.under(p, 'head') and not layer.under(p, 'attention')
      and not layer.under(p, 'mlp') for p in residual)
  # what is left as the head's own, outside every inner phase, is small
  # beside the norms and adds that were there before
  inner = ('mixer/proj', 'mixer/conv', 'mixer/selective_scan', 'attention',
           'mlp', 'vocab', 'residual')
  own = sum(s for p, s in phases.items() if layer.under(p, 'head')
            and not any(layer.under(p, q) for q in inner))
  assert own < sum(phases[p] for p in residual)
  assert sum(phases.values()) == pytest.approx(hybrid['busy_mean_s'])
